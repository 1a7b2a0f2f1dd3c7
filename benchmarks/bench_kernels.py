"""Kernel-level benchmark: the fused DANA master update (paper Sec. C.1
"above 20 workers the master becomes a bottleneck") + the model hot-spot
kernels.

On this CPU container wall-clock timings of the Pallas path are
meaningless (interpret mode); what we CAN measure/report:

  * correctness: pallas(interpret) == ref to tight tolerance;
  * the HBM-traffic model: bytes moved per master round, fused vs unfused
    (the roofline-relevant number — the master is bandwidth-bound);
  * wall time of the *reference* path (the XLA fallback that ops.py
    dispatches on CPU), as a sanity number.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.dana_update.ops import dana_master_update_leaf
from repro.kernels.dana_update.ref import dana_master_update_ref
from repro.kernels.flat_update.kernel import flat_master_update_batch_2d
from repro.kernels.flat_update.ref import flat_master_update_batch_ref
from repro.roofline.analysis import HBM_BW

from .common import print_csv, save_json


def _time(fn, *args, reps=5):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def master_update_row(k: int, dtype=jnp.float32):
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 4)
    theta, vi, v0, g = (jax.random.normal(kk, (k,), dtype) for kk in ks)
    lr, gamma = 0.1, 0.9

    ref = jax.jit(lambda *a: dana_master_update_ref(*a, lr, gamma))
    t_ref = _time(ref, theta, vi, v0, g)

    # interpret-mode correctness of the fused kernel
    outs_k = dana_master_update_leaf(theta, vi, v0, g, lr, gamma,
                                     use_pallas=True)
    outs_r = dana_master_update_ref(theta, vi, v0, g, lr, gamma)
    err = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(outs_k, outs_r))

    nbytes = np.dtype(np.float32).itemsize * k
    fused_bytes = 8 * nbytes           # 4 reads + 4 writes
    # unfused (one HLO op per line of Alg. 4): v'=gv+g (3), v0'=v0-v+v' (4),
    # th'=th-lr v' (3), hat=th'-lr g v0' (3)  => ~13 stream passes
    unfused_bytes = 13 * nbytes
    return {
        "kernel": "dana_update", "k": k,
        "max_err": err,
        "ref_cpu_ms": t_ref * 1e3,
        "fused_bytes": fused_bytes,
        "unfused_bytes": unfused_bytes,
        "traffic_ratio": unfused_bytes / fused_bytes,
        "tpu_roundtrip_us_fused": fused_bytes / HBM_BW * 1e6,
        "tpu_roundtrip_us_unfused": unfused_bytes / HBM_BW * 1e6,
    }


def batched_update_row(rows: int, n_workers: int, k: int):
    """Batched k-message flat kernel vs k sequential fused rounds.

    Wall time compares the two jnp reference paths (what the CPU fallback
    actually dispatches; Pallas wall time is meaningless in interpret
    mode); correctness checks the batched Pallas kernel (interpret)
    against the batched reference; the HBM model gives the TPU-roofline
    numbers — sequential re-reads theta/v0 per message (8 streams x k),
    batched keeps state VMEM-resident and streams only grads + views.
    """
    ks = jax.random.split(jax.random.PRNGKey(rows + k), 4)
    theta = jax.random.normal(ks[0], (rows, 128))
    v = jax.random.normal(ks[1], (n_workers, rows, 128)) * 0.1
    v0 = jnp.sum(v, axis=0)
    g = jax.random.normal(ks[2], (k, rows, 128))
    ids = jnp.asarray([j % n_workers for j in range(k)], jnp.int32)
    lrs = jnp.full((k,), 0.05)
    gammas = jnp.full((k,), 0.9)
    cgs = jnp.ones((k,))

    def sequential(theta, v, v0, g):
        hats = []
        for j in range(k):
            vi = v[ids[j]]
            th, vi_n, v0, hat = dana_master_update_ref(
                theta, vi, v0, g[j], lrs[j], gammas[j])
            theta = th
            v = v.at[ids[j]].set(vi_n)
            hats.append(hat)
        return theta, v, v0, jnp.stack(hats)

    vscales = jnp.ones((k,))
    seq = jax.jit(sequential)
    bat = jax.jit(lambda t, vv, s, gg: flat_master_update_batch_ref(
        t, vv, s, None, None, None, gg, ids, lrs, lrs, gammas, cgs,
        vscales, nesterov=False))
    t_seq = _time(seq, theta, v, v0, g)
    t_bat = _time(bat, theta, v, v0, g)

    # correctness of the batched Pallas kernel (interpret mode off-TPU)
    outs_k = flat_master_update_batch_2d(
        theta, v, v0, None, None, g, ids, lrs, lrs, gammas, cgs, vscales,
        nesterov=False, interpret=jax.default_backend() != "tpu")
    outs_r = bat(theta, v, v0, g)
    err = max(float(jnp.max(jnp.abs(a - b)))
              for a, b in zip(outs_k[:3] + (outs_k[5],),
                              outs_r[:3] + (outs_r[6],)))

    p_bytes = np.dtype(np.float32).itemsize * rows * 128
    # sequential fused rounds: per message read+write theta, v_i, v0 and
    # read g / write hat => 8 full passes x k
    seq_bytes = 8 * k * p_bytes
    # one batched kernel: state streams once (theta/v0 in+out = 4, the
    # (N, R, 128) momentum slab in+out = 2N) + per-message g in / hat out
    bat_bytes = (4 + 2 * n_workers) * p_bytes + 2 * k * p_bytes
    return {
        "kernel": "flat_update", "rows": rows, "workers": n_workers,
        "k": k, "max_err": err,
        "seq_ref_cpu_us": t_seq * 1e6,
        "batched_ref_cpu_us": t_bat * 1e6,
        "cpu_speedup_x": t_seq / t_bat,
        "traffic_ratio": seq_bytes / bat_bytes,
        "tpu_roundtrip_us_seq": seq_bytes / HBM_BW * 1e6,
        "tpu_roundtrip_us_batched": bat_bytes / HBM_BW * 1e6,
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", type=int, nargs="*",
                    default=[1 << 16, 1 << 20, 1 << 22])
    ap.add_argument("--batch-rows", type=int, nargs="*", default=[256, 2048])
    ap.add_argument("--batch-k", type=int, nargs="*", default=[4, 8, 16])
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--out", default="results/bench_kernels.json")
    args = ap.parse_args(argv)

    rows = [master_update_row(k) for k in args.sizes]
    print_csv(rows, ["kernel", "k", "max_err", "ref_cpu_ms",
                     "traffic_ratio", "tpu_roundtrip_us_fused",
                     "tpu_roundtrip_us_unfused"])
    batched = [batched_update_row(r, args.workers, k)
               for r in args.batch_rows for k in args.batch_k]
    print_csv(batched, ["kernel", "rows", "workers", "k", "max_err",
                        "seq_ref_cpu_us", "batched_ref_cpu_us",
                        "cpu_speedup_x", "traffic_ratio"])
    # NB: no cpu_speedup claim — on CPU both paths dispatch near-identical
    # jnp loops (the dispatch-amortization win is measured on the real hot
    # path in bench_cluster); the kernel-level claims are correctness and
    # the HBM-traffic model.
    claims = {"fused_correct": all(r["max_err"] < 1e-5 for r in rows),
              "traffic_saving_x": rows[-1]["traffic_ratio"],
              "batched_correct": all(r["max_err"] < 1e-5 for r in batched),
              "batched_traffic_ratio": batched[-1]["traffic_ratio"]}
    print("claims:", claims)
    save_json(args.out, {"rows": rows, "batched": batched, "claims": claims})
    return rows + batched, claims


if __name__ == "__main__":
    main()
