"""The harness takes a configuration with a side network by files alone
(``tiny64canny``: the program's ``tiny-test+tiny-cnet``,
``reference/sd_control_stream.py``, ``flops/sd_control_stream.py``), and
the output check sees the mechanism: the program at conditioning scale 0
against the reference at the file's scale reads not correct."""

import json

import pytest

from .conftest import STUB_DEVICE, make_root, tiny_spec


@pytest.fixture(scope="module")
def canny_root(tmp_path_factory):
    spec = tiny_spec()
    spec["configs"].append({
        "name": "tiny64canny", "source": "tests",
        "file": "benchmark/configs/tiny64canny.json", "reduced": [],
        "why": "tiny test family with its side network",
    })
    spec["workloads"].append({
        "name": "tiny64canny.duo20", "config": "tiny64canny", "traffic": "duo20",
        "chips": 1, "why": "two sessions, CPU test",
    })
    return make_root(tmp_path_factory.mktemp("canny"), spec)


def test_the_cell_runs_by_files_alone_and_is_correct(run_cell, canny_root):
    code, line, err = run_cell("tiny64canny.duo20", seed=2**31 + 34, root=canny_root)
    assert code == 0, err
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 20 and line["failed"] == 0
    counted = line["counters_window"]
    assert counted["batchsched_controlnet_rows_total"] == sum(
        int(k) * v for k, v in counted["batchsched_occupancy_hist"].items()
    ) > 20
    assert counted["batchsched_controlnet_scale_writes_total"] == 0


@pytest.mark.parametrize("control", ["wrong_frame", "scale0"])
def test_each_control_reads_not_correct(run_cell, canny_root, monkeypatch, capsys, control):
    """The reference fed the frame after the one consumed (so every row's
    edge map and latent are one frame off), and the side network switched
    off in the program: each far over the limit."""
    if control == "wrong_frame":
        code, line, err = run_cell(
            "tiny64canny.duo20", seed=77, control="wrong_frame", root=canny_root
        )
        assert code == 0, err
    else:
        import benchmark.harness as harness
        import benchmark.run as run
        from benchmark.tools import control_scale

        monkeypatch.setattr(harness, "ROOT", canny_root)
        monkeypatch.setattr(control_scale, "T_PROCESS_START", 0.0)
        monkeypatch.setattr(run, "require_chips", lambda n: dict(STUB_DEVICE))
        capsys.readouterr()
        assert control_scale.main(
            ["--workload", "tiny64canny.duo20", "--seed", "77", "--seconds", "3"]
        ) == 0
        line = json.loads(capsys.readouterr().out.splitlines()[-1])
        assert line["control"] == {
            "program_conditioning_scale": 0.0, "reference_conditioning_scale": 1.0,
        }
        assert line["failed"] == 0
    c = line["compared"]["session_bias_rel_max"]
    assert line["correct"] is False and c["value"] > 5 * c["limit"], c
