#!/usr/bin/env python
"""Capture the golden output fingerprint with REAL weights (VERDICT r2 #5).

Run ONCE on any host that has the model's safetensors locally:

    python scripts/golden_capture.py --model-id stabilityai/sd-turbo

then commit the emitted tests/golden/<model>.json.  From then on
tests/test_golden_output.py validates every weights-bearing environment
against it (skipped where weights are absent).  Prints one JSON line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model-id", default="stabilityai/sd-turbo")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from ai_rtc_agent_tpu.utils import golden

    out = args.out or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tests", "golden",
        args.model_id.replace("/", "--") + ".json",
    )
    result = {"ok": False, "check": "golden_capture", "model_id": args.model_id}
    from ai_rtc_agent_tpu.utils.contract import sigterm_to_exception

    sigterm_to_exception("timeout")
    try:
        cap = golden.capture(args.model_id)
        os.makedirs(os.path.dirname(out), exist_ok=True)
        golden.save(cap, out)
        result.update(ok=True, path=out, fingerprint_stats={
            "mean": cap["fingerprint"]["mean"], "std": cap["fingerprint"]["std"],
        })
        import jax

        result["backend"] = jax.default_backend()
    except BaseException as e:  # noqa: BLE001
        result["error"] = f"{type(e).__name__}: {e}"
    finally:
        print(json.dumps(result))
        sys.stdout.flush()
    sys.exit(0 if result["ok"] else 1)


if __name__ == "__main__":
    main()
