"""Readings that set a cell's check limits (not part of a benchmark run).

    python3 benchmarks/chip/readings.py --workload <name> \\
        --seeds 1-12 --control-seeds 1-3 [--out readings.json]

For every ``--seeds`` seed: the program's numbers (the run's check call
against the float32 reference, in the order closest to it), the lower
readings.  For every
``--control-seeds`` seed: the same numbers with the reference itself put
in the program's place one precision step down (float8 e4m3 matrix
products, a bfloat16 master state) — the control, which must read far
above the program — and with two faults planted in that place: half of
each batch left out (the mean taken over the rest), and the gradient's
first leaf doubled where it is produced; each applies every round in
worker order.  A state left unchanged reads
1 on ``dtheta_gap`` and ``loss_gap`` by their definition and needs no run.
Everything runs in one process, so the programs compile once.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

import bench  # noqa: E402
import run  # noqa: E402


def seeds(text: str) -> list:
    out = []
    for part in text.split(","):
        a, _, b = part.partition("-")
        out += list(range(int(a), int(b) + 1)) if b else [int(a)]
    return out


def half_batch(grad):
    """The gradient of half of each batch: half the rows, or half the
    positions of a one-row batch."""
    def g(params, toks):
        b, s = toks.shape
        return grad(params, toks[:b // 2] if b > 1 else toks[:, :s // 2])
    return g


def altered_answer(grad):
    """The gradient with its first leaf (the embedding's) doubled where
    the worker produces it."""
    import jax

    def g(params, toks):
        out = grad(params, toks)
        leaves, tree = jax.tree.flatten(out)
        leaves[0] = 2.0 * leaves[0]
        return jax.tree.unflatten(tree, leaves)
    return g


def readings(cell, program_seeds, control_seeds, log=print):
    entry = bench.load_module(bench.HERE / "entries"
                              / f"{cell.traffic['entry']}.py")
    program = entry.build_program(cell)
    sync = entry.device_sync()
    out = {"program": {}, "control": {}, "half_batch": {},
           "altered_answer": {}}
    # the control and the faults apply each round in worker order
    orders = entry.round_orders(cell.traffic["workers"],
                                cell.traffic["check_rounds"])[:1]
    for seed in sorted(set(program_seeds) | set(control_seeds)):
        params0, rows = entry.make_inputs(cell, seed)
        ref = entry.Judge(cell, params0, rows)
        if seed in program_seeds:
            theta_p, norms, _, drains = entry.check_call(
                program, params0, rows, cell.traffic["workers"],
                cell.traffic["check_rounds"], sync)
            out["program"][seed] = dict(ref.numbers(norms,
                                                    ref.loss(theta_p)),
                                        drains=drains)
            del theta_p
            log(f"seed {seed} program {out['program'][seed]}")
        if seed in control_seeds:
            for kind, kw in (("control", dict(prec="fp8",
                                              state_dtype="bfloat16")),
                             ("half_batch", dict(grad_wrap=half_batch)),
                             ("altered_answer",
                              dict(grad_wrap=altered_answer))):
                cand = entry.Judge(cell, params0, rows, orders=orders, **kw)
                out[kind][seed] = ref.numbers(*cand.candidate())
                del cand
                log(f"seed {seed} {kind} {out[kind][seed]}")
        del ref, params0
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1-12")
    ap.add_argument("--control-seeds", default="1-3")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    cell = bench.resolve(bench.load_json(bench.CHECKOUT / "BENCHMARK.json"),
                         args.workload)
    if run.find_chips(cell.chips) is None:
        return run.NO_CHIP
    run.enable_compile_cache()
    res = readings(cell, seeds(args.seeds), seeds(args.control_seeds))
    summary = {}
    for kind, per_seed in res.items():
        for num in ("dtheta_gap", "loss_gap"):
            vals = [r[num] for r in per_seed.values()]
            if vals:
                summary[f"{kind}.{num}"] = {"min": min(vals),
                                            "max": max(vals)}
    text = json.dumps({"workload": args.workload, "summary": summary,
                       "per_seed": res}, indent=1)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text)
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
