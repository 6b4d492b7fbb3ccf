"""``python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>``

One run of one cell on the machine it is started on.  Prints one JSON object
as the last line of standard output, and the numbers the output check
compared, each beside its limit, as the last lines of standard error.  Any
failure (no TPU, fewer chips than the cell asks for, a name the disk lacks,
a compile inside the window, a graph other than the configuration's) is a
message on standard error and a non-zero exit code, never a result line.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()  # before the heavy imports: set-up counts them

import argparse  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

# what an operator's environment could change under the benchmark: the cell
# fixes these itself (traffic file, configuration file) or leaves the
# program's default
_SCRUB = (
    "ATTN_IMPL", "FUSED_EPILOGUE", "QUANT_WEIGHTS", "QUANT_MIN_SIZE",
    "UNET_CACHE", "WARMUP_FRAMES", "DROP_FRAMES", "PIPELINE_DEPTH",
    "BATCHSCHED_MAX_SESSIONS", "BATCHSCHED_WINDOW_MS", "BATCHSCHED_QUEUE_BOUND",
    "BATCHSCHED_DP", "BATCHSCHED_PREWARM", "MESH_SHAPE", "AOT_ENGINES",
    "SIMILAR_IMAGE_FILTER", "HW_ENCODE", "FAULT_PLAN", "DEVTEL_ENABLE",
)

logger = logging.getLogger("benchmark")


def parse(argv=None):
    ap = argparse.ArgumentParser(prog="benchmark.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # not for the driver: the output check's controls.  w8: the program's own
    # lower-precision path in the program's place; wrong_frame: the program as
    # it is, the reference fed the source frame after the one consumed
    ap.add_argument("--control", choices=("w8", "wrong_frame"), default=None)
    return ap.parse_args(argv)


def require_chips(n: int) -> dict:
    """The device as JAX reports it; refuses anything but ``n`` or more TPU
    chips.  Never falls back to a CPU."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"JAX found no accelerator: {e}") from None
    d = devices[0]
    if d.platform != "tpu":
        raise SystemExit(
            f"this benchmark measures on a TPU; JAX reports platform "
            f"{d.platform!r} ({d.device_kind})"
        )
    if len(devices) < n:
        raise SystemExit(f"the cell asks for {n} chip(s), JAX reports {len(devices)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices)}


def place_compile_cache(root: str) -> str:
    """JAX's persistent cache where ``JAX_COMPILATION_CACHE_DIR`` says, else
    at the fixed path ``.jax_cache/`` inside the checkout.  Only the size cap
    is lifted (``JAX_COMPILATION_CACHE_MAX_SIZE`` is not followed): a cache
    capped at 192 MiB, as the chip tool's is, evicts a cell's own executables
    between two of its runs (a bucket executable is 47 MB, the reference's
    step 100 MB), and then every run compiles (PERF.md section 6).  Every
    program is kept, also the sub-second eager ones a boot compiles by the
    hundred."""
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", placed)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return placed


def main(argv=None) -> int:
    args = parse(argv)
    logging.basicConfig(
        level=logging.INFO, stream=sys.stderr,
        format="%(asctime)s %(name)s %(message)s",
    )
    for name in _SCRUB:
        os.environ.pop(name, None)

    from .harness import ROOT, Benchmark, MissingPiece

    try:
        bench = Benchmark(ROOT)
        cell = bench.cell(args.workload)
        cfg = bench.config(cell)
        traffic = bench.traffic(cell)
        ref_module, flops = bench.reference(cfg), bench.flops(cfg)
        readers = (
            [(m, bench.reader(m)) for m in bench.per_layer(cell)] if args.trace else []
        )
    except MissingPiece as e:
        raise SystemExit(f"benchmark: {e}") from None
    limits = cfg["check"]["limits"]

    device = require_chips(cell["chips"])
    cache_dir = place_compile_cache(ROOT)
    logger.info("device %s; compile cache %s", device, cache_dir)

    from . import check, metrics, serve, trace_reduce
    from .peaks import peaks_of

    peaks = peaks_of(device["kind"])
    result = serve.run_window(
        cfg, ref_module.weight_shapes, traffic, args.seed, args.seconds,
        bool(args.trace), T_PROCESS_START,
        quant="w8" if args.control == "w8" else None,
    )
    if result.compiles_in_window:
        raise SystemExit(
            f"{result.compiles_in_window} XLA compile(s) inside the measured "
            "window: a shape was not warmed up during set-up"
        )
    attempted, failed = metrics.attempted_failed(result)
    e2e = metrics.end_to_end(result)
    n_frames = len(metrics.window_frames(result))
    logger.info(
        "window %.1f s: %d stylized frames returned (the latency sample), "
        "%d source frames superseded, %d picked up, %d failed; set-up %.1f s",
        args.seconds, n_frames, result.superseded, attempted, failed, result.setup_s,
    )
    counted = result.counters_window()
    logger.info("window: steps by riders %s", result.steps_by_riders())
    logger.info("window: the program's counters %s", json.dumps(counted))
    device["memory_peak_bytes"] = result.memory_peak_bytes

    out = {"correct": False, "attempted": attempted, "failed": failed}
    units = {m["name"]: m["unit"] for g in ("end_to_end", "per_layer") for m in bench.spec[g]}
    if not args.trace:
        out["metrics"] = {
            m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
            for m in bench.end_to_end(cell)
        }
    else:
        t0 = time.monotonic()
        reduced = trace_reduce.reduce_trace(
            trace_reduce.from_bytes(result.xspace),
            kernel_names=cfg["stream"]["mosaic_kernels"],
        )
        result.xspace = None
        logger.info("trace: reduction took %.1f s", time.monotonic() - t0)
        for name, ts in reduced["modules"].items():
            logger.info(
                "trace: program %s ran %d times whole inside the span, mean %.3f ms",
                name, len(ts), 1e3 * sum(ts) / len(ts),
            )
        logger.info(
            "trace: steps by riders over the span %s", result.steps_by_riders(traced=True)
        )
        by_riders: dict = {}
        for name, seconds, riders in reduced["steps"]:
            by_riders.setdefault((name, riders), []).append(seconds)
        for (name, riders), ts in sorted(by_riders.items(), key=str):
            logger.info(
                "trace: step program %s with %s rider(s): %d whole steps, mean %.3f ms",
                name, riders, len(ts), 1e3 * sum(ts) / len(ts),
            )
        ctx = serve.ReaderContext(cfg, traffic, result, reduced, peaks, flops)
        out["metrics"] = {}
        for m, read in readers:
            value = read(ctx)
            if value is not None:
                out["metrics"][m["name"]] = {"value": float(value), "unit": units[m["name"]]}
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = {
            "device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"],
        }
    out["device"] = device
    # all four whatever the cell is judged on, and in a traced run too
    out["end_to_end_window"] = e2e

    t0 = time.monotonic()
    verdict = check.compare(
        cfg, check.reference_for(cfg, args.seed, ref_module), result,
        frame_shift=1 if args.control == "wrong_frame" else 0,
    )
    # the process's peak never falls: this is the check's own peak where the
    # check went above the window's, and the window's again where it did not
    device["check_memory_peak_bytes"] = serve.memory_peak()
    logger.info(
        "output check took %.1f s; peak memory after it %.3f GB (the window's %.3f)",
        time.monotonic() - t0, device["check_memory_peak_bytes"] / 1e9,
        device["memory_peak_bytes"] / 1e9,
    )
    correct, compared = check.judge(verdict["numbers"], limits)
    out["correct"] = correct
    out["readings"] = verdict["numbers"]
    out["counters_window"] = counted
    out["compared"] = compared  # last, as the contract asks
    print(json.dumps(out), flush=True)
    for name, c in compared.items():
        print(f"compared {name} = {c['value']:.6g} (limit {c['limit']:g})", file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
