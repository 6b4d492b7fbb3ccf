"""Tests of the benchmark's own code, on the CPU at the tiny size.

Run with ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`` from the
root of the repo.  Nothing here measures: a number from these runs is a
count or a comparison, never a speed.
"""

import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

STUB_DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
TINY_CONFIGS = ("tiny64", "tinyturbo64", "tinyxl64")


def tiny_spec() -> dict:
    """The real BENCHMARK.json with the tiny configurations (two of the
    one-tower family, one of the two-tower family) and the test traffic in
    place of the cells (same metrics, same keys)."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"] = [
        {"name": n, "source": "tests", "file": f"benchmark/configs/{n}.json",
         "reduced": [], "why": "tiny test family"}
        for n in TINY_CONFIGS
    ]
    spec["workloads"] = [
        {"name": f"{n}.duo20", "config": n, "traffic": "duo20", "chips": 1,
         "why": "two sessions, CPU test"}
        for n in TINY_CONFIGS
    ]
    # the tiny cells are judged on every end-to-end metric, also on one
    # that lists the real cells it is judged in
    spec["end_to_end"] = [
        {k: v for k, v in m.items() if k != "workloads"} for m in spec["end_to_end"]
    ]
    return spec


def make_root(tmp, spec=None) -> str:
    """A checkout-shaped directory: BENCHMARK.json plus a ``benchmark/``
    holding copies of the data files (so a test can add or remove one)."""
    root = str(tmp)
    home = os.path.join(root, "benchmark")
    for sub in ("configs", "traffic", "layer_metrics", "reference", "flops"):
        os.makedirs(os.path.join(home, sub), exist_ok=True)
    src = os.path.join(REPO, "benchmark")
    for sub in ("layer_metrics", "reference", "flops"):
        for f in os.listdir(os.path.join(src, sub)):
            if f.endswith(".py"):
                shutil.copy(os.path.join(src, sub, f), os.path.join(home, sub, f))
    for f in os.listdir(os.path.join(HERE, "data", "configs")):
        shutil.copy(os.path.join(HERE, "data", "configs", f), os.path.join(home, "configs", f))
    for f in os.listdir(os.path.join(HERE, "data", "traffic")):
        shutil.copy(os.path.join(HERE, "data", "traffic", f), os.path.join(home, "traffic", f))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec or tiny_spec(), f)
    return root


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    """One root for the whole session: its compile cache stays warm from
    one end-to-end run to the next."""
    return make_root(tmp_path_factory.mktemp("root"))


@pytest.fixture
def run_cell(tiny_root, monkeypatch, capsys):
    """Drive ``benchmark.run.main`` in this process, past the look for a
    chip -> (exit code, parsed last stdout line or None, stderr text)."""
    import benchmark.harness as harness
    import benchmark.run as run

    def _run(workload, seed=3, seconds=3, trace=0, control=None, root=None):
        monkeypatch.setattr(harness, "ROOT", root or tiny_root)
        monkeypatch.setattr(run, "require_chips", lambda n: dict(STUB_DEVICE))
        argv = ["--workload", workload, "--seed", str(seed),
                "--seconds", str(seconds), "--trace", str(trace)]
        if control:
            argv += ["--control", control]
        capsys.readouterr()
        try:
            code = run.main(argv)
        except SystemExit as e:
            code = e.code if isinstance(e.code, int) else 1
            out, err = capsys.readouterr()
            return code, None, err + str(e.code)
        out, err = capsys.readouterr()
        lines = [l for l in out.splitlines() if l.strip()]
        return code, json.loads(lines[-1]) if lines else None, err

    return _run
