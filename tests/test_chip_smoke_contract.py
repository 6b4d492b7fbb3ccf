"""chip_smoke.py's contract, on the paths runnable without a chip.

The script proves the default serving path on a TPU; here, on a CPU that was
asked for by name, it must (a) stop at the device phase without serving a
frame, (b) in ``--tiny`` mode run every phase end to end — the same agent
CLI, the same client, the same checks — and still fail on the platform, and
(c) never exit 0 when any phase failed.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

# what a CPU run is EXPECTED to fail on: the platform, the TPU-default graph
# (dtype, kernels) and the HBM gauge the CPU backend does not report — and
# nothing else: sessions served, the warm boot hit its cache, kernels agree
EXPECTED_ON_CPU = (
    "platform is 'cpu', not 'tpu'",
    "agent platform is 'cpu', not 'tpu'",
    "served dtype is 'float32', the TPU default is 'bfloat16'",
    "served attn_impl is 'xla', the TPU default is 'pallas'",
    "served fused_epilogue is False, the TPU default is True",
    "holds no Mosaic call for",
    "device_mem_peak_bytes_in_use missing or 0",
)


def _run_smoke(args, tmp_path, timeout):
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    # conftest's 8 virtual CPU devices are for the in-process mesh tests;
    # the agent under test is the single-device program a user would start
    env.pop("XLA_FLAGS", None)
    env.update({
        "JAX_PLATFORMS": "cpu",
        # the cache is placed from outside, so the run leaves the checkout
        # alone; threshold 0 because tiny-test compiles in under the default
        # one second and a warm boot would otherwise have nothing to find
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache"),
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
    })
    return subprocess.run(
        [sys.executable, "chip_smoke.py", *args],
        env=env, capture_output=True, text=True, timeout=timeout, cwd=REPO,
    )


def _ok_lines(stdout: str) -> list:
    return [ln for ln in stdout.splitlines() if ln.startswith('{"ok"')]


def test_no_accelerator_stops_before_a_frame_is_served(tmp_path):
    r = _run_smoke([], tmp_path, timeout=120)
    assert r.returncode != 0
    assert "== device: FAILED" in r.stdout
    assert "== serve-cold" not in r.stdout  # nothing was started
    assert not _ok_lines(r.stdout)


def test_tiny_run_goes_through_every_phase_and_fails_on_the_platform(tmp_path):
    r = _run_smoke(["--tiny"], tmp_path, timeout=600)
    assert r.returncode != 0, r.stdout[-2000:]
    assert not _ok_lines(r.stdout)
    for marker in ("== device: FAILED", "== serve-cold: FAILED",
                   "== serve-warm: FAILED", "== kernels: ok"):
        assert marker in r.stdout, (marker, r.stdout[-3000:])
    fails = [ln for ln in r.stdout.splitlines() if ln.startswith("FAIL [")]
    assert any("[device] platform is 'cpu', not 'tpu'" in ln for ln in fails)
    unexpected = [
        ln for ln in fails if not any(e in ln for e in EXPECTED_ON_CPU)
    ]
    assert not unexpected, unexpected
    # both bucket sizes stepped and both boots were described
    assert "cold boot: ready in" in r.stdout and "warm boot: ready in" in r.stdout
    assert "session b: sent" in r.stdout


def _fake_phases(monkeypatch, failing=None):
    def device(run):
        run.device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
        return ["no chip"] if failing == "device" else []

    def phase(name):
        return lambda run: [f"{name} broke"] if failing == name else []

    names = ("serve-cold", "serve-warm", "kernels")
    monkeypatch.setattr(
        chip_smoke, "PHASES",
        (("device", device),) + tuple((n, phase(n)) for n in names),
    )


def test_every_phase_passing_prints_the_result_as_the_last_line(
    monkeypatch, capsys
):
    _fake_phases(monkeypatch)
    assert chip_smoke.main([]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }


def test_any_failed_phase_exits_non_zero_and_prints_no_result(
    monkeypatch, capsys
):
    for failing in ("device", "serve-cold", "serve-warm", "kernels"):
        _fake_phases(monkeypatch, failing)
        assert chip_smoke.main([]) == 1, failing
        out = capsys.readouterr().out
        assert not _ok_lines(out), failing
        assert f"== {failing}: FAILED" in out
        # and the run stopped there: a later phase never started
        later = ("device", "serve-cold", "serve-warm", "kernels")
        for name in later[later.index(failing) + 1:]:
            assert f"== {name}" not in out, (failing, name)


def test_a_tiny_run_can_never_pass(monkeypatch, capsys):
    _fake_phases(monkeypatch)
    assert chip_smoke.main(["--tiny"]) == 1
    assert not _ok_lines(capsys.readouterr().out)
