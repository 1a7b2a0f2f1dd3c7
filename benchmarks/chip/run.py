"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

Prints, as the last line of standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, ``breakdown`` (traced runs) and ``checks``: every number the
correctness check compared, beside its limit.  The same numbers are the
last lines of standard error.  Without a TPU, or with fewer chips than
the cell asks for, it exits with code 3 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

import bench  # noqa: E402

NO_CHIP = 3


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def find_chips(need: int):
    """The devices, or None (with the reason on standard error) when
    JAX finds no TPU or fewer than ``need`` chips."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"run.py: JAX found no devices: {e}", file=sys.stderr)
        return None
    if devices[0].platform != "tpu":
        print(f"run.py: needs a TPU; JAX found {devices[0].platform} "
              f"({devices[0].device_kind}).  There is no CPU fallback.",
              file=sys.stderr)
        return None
    if len(devices) < need:
        print(f"run.py: the cell needs {need} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return None
    return devices


def enable_compile_cache():
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (``JAX_COMPILATION_CACHE_DIR`` wins where it is set)."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(bench.CHECKOUT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def result_line(res: dict, cell, devices, trace: bool) -> dict:
    import breakdown

    ctx = res["ctx"]
    entries = cell.per_layer if trace else cell.end_to_end
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell.chips,
              "memory_peak_bytes": ctx.get("peak_bytes")}
    out = {"correct": res["correct"], "attempted": res["attempted"],
           "failed": res["failed"],
           "metrics": ({} if res["error"] else
                       bench.read_metrics(entries, ctx)),
           "device": device}
    if trace and ctx.get("trace") is not None:
        busy_s, window_s, brk = breakdown.summarize(ctx)
        device.update(busy_s=busy_s, window_s=window_s)
        out["breakdown"] = brk
    if res.get("error"):
        out["error"] = res["error"]
    out["extra"] = res.get("extra", {})
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in res["checks"].items()}
    return out


def main(argv=None) -> int:
    args = parse(argv)
    cell = bench.resolve(bench.load_json(bench.CHECKOUT / "BENCHMARK.json"),
                         args.workload)
    devices = find_chips(cell.chips)
    if devices is None:
        return NO_CHIP
    enable_compile_cache()
    entry = bench.load_module(bench.HERE / "entries"
                              / f"{cell.traffic['entry']}.py")
    res = entry.run(cell, args.seed, args.seconds, bool(args.trace),
                    T_START)
    line = result_line(res, cell, devices, bool(args.trace))
    for name, c in line["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "OVER"
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r}) {ok}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
