"""Bring the asynchronous DANA cluster up on a TPU, at qwen2-1.5b widths.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # four chips: the SPMD pod round

One chip: the parameter-server cluster (``repro.cluster.run_cluster``,
the function behind ``python -m repro.launch.cluster``) trains qwen2-1.5b
at its published widths, cut to 4 layers and the first 1/8 of its
vocabulary, from random weights made from a seed: dana-zero, 2 threaded
workers in free mode, coalescing window 2, 8 x 512 tokens per gradient,
24 gradients, on the flat Pallas path.  Before the run it compiles the
master's fused receive program and the worker's backward->wire program;
after it, it checks one receive batch and one send view of the Pallas
kernels against their jnp references at the run's real row count.

Four chips: the SPMD DANA pod round (``repro.launch.steps``) with 4 pods
on a (4, 1, 1) mesh, step by step against the same round algebra
computed by a plain single-device reference.

Each phase prints what it measured, and any failure exits nonzero.  The
last line of a passing run is one JSON object naming the device.  There
is no CPU fallback: without a TPU the script exits nonzero at once.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

MODEL = "qwen2-1.5b"
# published widths kept (d_model 1536, 12 query and 2 KV heads of 128,
# d_ff 8960, QKV bias); depth cut from 28 layers to 4 and the vocabulary
# to its first 1/8 (18,992 of 151,936 ids), which LMTask draws from
OVERRIDES = dict(num_layers=4, unit_repeats=4, vocab_size=18_992)
PUBLISHED = dict(d_model=1536, num_heads=12, num_kv_heads=2, head_dim=128,
                 d_ff=8960, qkv_bias=True)
WORKERS = 2
COALESCE = 2
SEQ = 512
BATCH = 8
GRADS = 24
LR = 0.05
MOMENTUM = 0.9
SEED = 0
PODS = 4
POD_STEPS = 3
POD_LR = 3e-3
# four-chip limits, set from a passing run on four v5e chips: per-round
# losses agreed to 3e-5, 1.3e-4 and 2.4e-4 (bf16 backward passes batched
# differently), while the loss moved 1.1e-2 from round 1 to 2; theta's
# 3-round update agreed to 1.4e-2 relative L2.  A reference that drops
# the momentum term misses by 3.9e-2 in round 3's loss and by 1.1 in
# theta (CPU mutation check at a tiny width)
POD_LOSS_TOL = 1e-3
POD_THETA_TOL = 5e-2


def _gib(n: float) -> str:
    return f"{n / 2**30:.3f} GiB"


def _fail(msg: str) -> None:
    print(f"FAIL: {msg}", flush=True)
    raise SystemExit(1)


def max_abs_diffs(pairs):
    """[(max |a - b|, max |b|)] for (a, b) pairs, reduced on the device."""
    import jax.numpy as jnp
    return [(jnp.max(jnp.abs(a - b)), jnp.max(jnp.abs(b))) for a, b in pairs]


def kernel_checks(rows: int, workers: int, k: int, seed: int):
    """One receive batch and one send view of the Pallas kernels against
    their jnp references on the same seeded inputs, at ``rows`` rows.

    The tolerance is 16 f32 ulps of the reference's largest magnitude:
    a k-message chain runs about ten f32 operations per element, and the
    Mosaic and XLA lowerings may each round them (or fuse them into an
    FMA) differently."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.flat_update import (flat_send_view,
                                           flat_send_view_ref, prefetch_pays)
    from repro.kernels.flat_update.ops import flat_master_update_batch
    from repro.kernels.flat_update.ref import flat_master_update_batch_ref

    ulps = 16 * float(np.finfo(np.float32).eps)
    ids = jnp.asarray([(j + 1) % workers for j in range(k)], jnp.int32)
    lrs = jnp.full((k,), LR, jnp.float32)
    gammas = jnp.full((k,), MOMENTUM, jnp.float32)
    ones = jnp.ones((k,), jnp.float32)

    @jax.jit
    def inputs(key):
        ks = jax.random.split(key, 3)
        theta = jax.random.normal(ks[0], (rows, 128), jnp.float32)
        v = 0.1 * jax.random.normal(ks[1], (workers, rows, 128), jnp.float32)
        g = jax.random.normal(ks[2], (k, rows, 128), jnp.float32)
        return theta, v, jnp.sum(v, axis=0), g

    def scalars(*state):
        return state + (ids, lrs, lrs, gammas, ones, ones)

    @jax.jit
    def receive(theta, v, v0, g):
        out = flat_master_update_batch(
            *scalars(theta, v, v0, None, None, None, g), nesterov=False,
            use_pallas=True)
        return tuple(out[i] for i in (0, 1, 2, 6))

    # the reference runs on one row chunk at a time (the update is
    # row-local), so the pallas outputs, the inputs and one chunk of
    # reference outputs fit the device together
    chunk = rows // 8 if rows % 8 == 0 else rows

    @jax.jit
    def receive_diffs(got, theta, v, v0, g, r0):
        def rows_of(x):
            return jax.lax.dynamic_slice_in_dim(x, r0, chunk, axis=-2)
        ref = flat_master_update_batch_ref(
            *scalars(rows_of(theta), rows_of(v), rows_of(v0), None, None,
                     None, rows_of(g)), nesterov=False)
        return max_abs_diffs([(rows_of(a), ref[i])
                              for a, i in zip(got, (0, 1, 2, 6))])

    @jax.jit
    def send(theta, v0):
        c = jnp.float32(LR * MOMENTUM)
        w = jnp.ones((1,), jnp.float32)
        return max_abs_diffs([(flat_send_view(theta, v0[None], w, c,
                                              use_pallas=True),
                               flat_send_view_ref(theta, v0[None], w, c))])

    theta, v, v0, g = inputs(jax.random.PRNGKey(seed + 1))
    got = receive(theta, v, v0, g)
    parts = [receive_diffs(got, theta, v, v0, g, r0)
             for r0 in range(0, rows, chunk)]
    recv = [(max(float(p[i][0]) for p in parts),
             max(float(p[i][1]) for p in parts)) for i in range(4)]
    del got
    route = ("prefetch" if prefetch_pays(rows, workers, k) else "dense")
    ok = True
    checks = [(f"receive ({route} kernel, N={workers}, k={k})",
               ("theta", "v", "v0", "hats"), recv),
              ("send view (dana-zero look-ahead)", ("view",), send(theta, v0))]
    for label, names, diffs in checks:
        for name, (d, scale) in zip(names, diffs):
            d, tol = float(d), ulps * float(scale)
            good = d <= tol
            ok &= good
            print(f"pallas-vs-ref {label} {name}: max|diff| {d:.3e} "
                  f"(tol {tol:.3e}) {'ok' if good else 'FAIL'}")
    return ok


def compile_programs(grad_fn, algo, workers: int, k: int, batch: int,
                     seq: int):
    """Compile the two programs ``run_cluster`` runs on this path, built
    by the runtime's own builders: the master's fused receive
    (``fused_flat_program``, k unstacked gradients, state donated) and
    the worker's backward->wire program (``flat_grad_program``, view
    donated).  The run finds both in the persistent compile cache.
    Returns the receive program's HLO text; prints compile times and
    memory."""
    import jax
    import jax.numpy as jnp

    from repro.cluster.master import fused_flat_program
    from repro.cluster.runtime import flat_grad_program
    from repro.kernels.flat_update import FlatAlgorithm

    fa = FlatAlgorithm(algo)
    params = jax.eval_shape(grad_fn.init, jax.random.PRNGKey(SEED))
    flat = jax.eval_shape(lambda p: fa.init(p, workers), params)
    rows = fa.spec.rows
    sds = jax.ShapeDtypeStruct
    programs = [
        ("fused receive", fused_flat_program(fa, k, False),
         (flat, sds((k,), jnp.int32), sds((k,), jnp.float32),
          tuple(sds((rows, 128), jnp.float32) for _ in range(k)), None)),
        ("worker backward->wire", flat_grad_program(fa.spec, grad_fn, (0,)),
         (sds((rows, 128), jnp.float32), sds((batch, seq), jnp.int32))),
    ]
    text = None
    for name, fn, args in programs:
        t0 = time.perf_counter()
        compiled = fn.lower(*args).compile()
        dt = time.perf_counter() - t0
        mem = compiled.memory_analysis()
        print(f"compile {name}: {dt:.1f} s; args "
              f"{_gib(mem.argument_size_in_bytes)}, out "
              f"{_gib(mem.output_size_in_bytes)}, temp "
              f"{_gib(mem.temp_size_in_bytes)}")
        if text is None:
            text = compiled.as_text()
    return text


def one_chip(overrides=OVERRIDES, *, workers=WORKERS, k=COALESCE,
             seq=SEQ, batch=BATCH, grads=GRADS, seed=SEED) -> bool:
    """The parameter-server cluster on one device; True when every check
    passed."""
    import jax
    import numpy as np

    from repro.cluster import ClusterConfig, run_cluster
    from repro.core import HyperParams, make_algorithm
    from repro.core.flat import FlatSpec
    from repro.data.synthetic import LMTask
    from repro.models.api import ModelGradFn

    grad_fn = ModelGradFn(MODEL, reduced=False, overrides=overrides)
    cfg = grad_fn.build_config()
    model = grad_fn.build_model()
    widths = {f: getattr(cfg, f) for f in PUBLISHED}
    shapes = jax.eval_shape(grad_fn.init, jax.random.PRNGKey(seed))
    spec = FlatSpec.from_tree(shapes)
    print(f"model {cfg.name}: {widths}, layers {cfg.num_layers}, vocab "
          f"{cfg.vocab_size}; P = {spec.n_elems:,} params, R = "
          f"{spec.rows:,} rows, {_gib(4 * spec.padded)} per f32 copy")
    algo = make_algorithm("dana-zero", HyperParams(lr=LR, momentum=MOMENTUM))

    text = compile_programs(grad_fn, algo, workers, k, batch, seq)
    custom = text.count("tpu_custom_call")
    print(f"fused receive program: {custom} tpu_custom_call op(s)")
    ok = custom > 0

    task = LMTask(vocab_size=cfg.vocab_size, seq_len=seq, batch_size=batch,
                  seed=seed)
    eval_tokens = task.eval_batch(batch)

    def eval_fn(p):
        return model.loss(p, {"tokens": eval_tokens})

    params0 = jax.jit(grad_fn.init)(jax.random.PRNGKey(seed))
    loss0 = float(jax.jit(eval_fn)(params0))
    ccfg = ClusterConfig(num_workers=workers, total_grads=grads,
                         eval_every=grads, mode="free", coalesce=k,
                         record_telemetry=False, rpc_timeout=900.0)
    stats: dict = {}
    t0 = time.perf_counter()
    hist = run_cluster(algo, grad_fn, params0, task.batch, ccfg, eval_fn,
                       stats_out=stats)
    wall = time.perf_counter() - t0
    loss1 = float(hist.eval_loss[-1])
    print(f"cluster run: {stats['applied']} grads applied in {wall:.1f} s "
          f"(compiles included), flat kernel path {stats['use_kernel']}, "
          f"drained-batch sizes {stats['coalesce_counts']}, grads per "
          f"worker {stats['grads_per_worker']}")
    good = bool(np.isfinite(loss0) and np.isfinite(loss1) and loss1 < loss0)
    print(f"eval loss: {loss0:.4f} before -> {loss1:.4f} after "
          f"{stats['applied']} grads {'ok' if good else 'FAIL'}")
    ok &= good and stats["use_kernel"] and stats["applied"] == grads
    # read before the kernel checks, whose seeded inputs and outputs
    # (~12 GiB) would otherwise set the process peak
    mem = jax.devices()[0].memory_stats() or {}
    peak = mem.get("peak_bytes_in_use")
    print(f"peak_bytes_in_use after the cluster run: {peak} "
          f"({_gib(peak or 0)}); bytes_limit {mem.get('bytes_limit')}")
    ok &= peak is not None
    del hist, params0
    ok &= kernel_checks(spec.rows, workers, k, seed)
    return bool(ok)


# ---------------------------------------------------------------------------
# four chips: the SPMD pod round against a plain single-device reference
# ---------------------------------------------------------------------------
def reference_round(model, lr: float, gamma: float, pods: int):
    """One DANA pod round as plain code on one device: every pod's
    gradient at the shared look-ahead point theta - lr*gamma*v0 (in
    bf16, like the SPMD step), v_p' = gamma*v_p + g_p, S = sum_p v_p',
    theta' = theta - lr*S, v0' = S."""
    import jax
    import jax.numpy as jnp

    def round_(state, tokens):
        theta, v, v0 = state["theta"], state["v"], state["v0"]
        hat = jax.tree.map(lambda t, s: (t - lr * gamma * s)
                           .astype(jnp.bfloat16), theta, v0)
        per_pod = tokens.reshape((pods, -1) + tokens.shape[1:])
        losses, v_new = [], []
        for p in range(pods):
            loss, g = jax.value_and_grad(model.loss)(
                hat, {"tokens": per_pod[p]})
            losses.append(loss)
            v_new.append(jax.tree.map(
                lambda vp, gp, p=p: gamma * vp[p] + gp.astype(jnp.float32),
                v, g))
        s = jax.tree.map(lambda *xs: sum(xs), *v_new)
        new = {"theta": jax.tree.map(lambda t, si: t - lr * si, theta, s),
               "v": jax.tree.map(lambda *xs: jnp.stack(xs), *v_new),
               "v0": s, "t": state["t"] + 1}
        return new, jnp.mean(jnp.stack(losses))
    return round_


def four_chips(overrides=OVERRIDES, *, pods=PODS, steps=POD_STEPS,
               seq=SEQ, batch=BATCH, seed=SEED) -> bool:
    """The SPMD pod round on a (pods, 1, 1) mesh against
    ``reference_round`` on one device; True when they agree."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.schedules import constant
    from repro.data.synthetic import LMTask
    from repro.launch.mesh import make_host_mesh
    from repro.launch.sharding import batch_specs, to_shardings
    from repro.launch.steps import (TrainSettings, build_train_step,
                                    init_train_state)
    from jax.sharding import SingleDeviceSharding

    from repro.models.api import ModelGradFn

    model = ModelGradFn(MODEL, reduced=False,
                        overrides=overrides).build_model()
    mesh = make_host_mesh((pods, 1, 1), ("pod", "data", "model"))
    settings = TrainSettings(lr=POD_LR, momentum=MOMENTUM, fsdp=False)
    task = LMTask(vocab_size=model.cfg.vocab_size, seq_len=seq,
                  batch_size=pods * batch, seed=seed)
    key = jax.random.PRNGKey(seed)
    dev0 = jax.devices()[0]
    # the reference starts from the same seeded state, on device 0 only
    ref_state = jax.jit(lambda k: init_train_state(model, k, pods),
                        out_shardings=SingleDeviceSharding(dev0))(key)
    with mesh:
        step, _, in_sh, out_sh = build_train_step(
            model, mesh, settings, constant(POD_LR),
            global_batch=pods * batch)
        b_sh = to_shardings(mesh, batch_specs(model.cfg, mesh,
                                              {"tokens": task.batch(0, 0)}))
        state = jax.jit(lambda k: init_train_state(model, k, pods),
                        out_shardings=in_sh[0])(key)
        jstep = jax.jit(step, in_shardings=(in_sh[0], b_sh),
                        out_shardings=(out_sh[0], None),
                        donate_argnums=(0,))
        t0 = time.perf_counter()
        compiled = jstep.lower(state, {"tokens": task.batch(0, 0)}).compile()
        mem = compiled.memory_analysis()
        print(f"compile SPMD pod round ({pods} pods): "
              f"{time.perf_counter() - t0:.1f} s; per device args "
              f"{_gib(mem.argument_size_in_bytes)}, temp "
              f"{_gib(mem.temp_size_in_bytes)}")
        theta0 = jax.device_get(state["theta"])
        ref_step = jax.jit(reference_round(model, POD_LR, MOMENTUM, pods),
                           donate_argnums=(0,))
        ok = True
        for i in range(steps):
            tokens = task.batch(0, i)
            state, metrics = jstep(state, {"tokens": tokens})
            ref_state, ref_loss = ref_step(ref_state,
                                           jax.device_put(tokens, dev0))
            loss, rl = float(metrics["loss"]), float(ref_loss)
            good = abs(loss - rl) <= POD_LOSS_TOL
            ok &= good
            print(f"pod round {i + 1}: loss {loss:.5f} SPMD vs {rl:.5f} "
                  f"reference, |diff| {abs(loss - rl):.2e} (tol "
                  f"{POD_LOSS_TOL:g}) {'ok' if good else 'FAIL'}")
        for d in jax.devices()[:pods]:
            v_bytes = {}
            for leaf in jax.tree.leaves(state["v"]):
                for sh in leaf.addressable_shards:
                    if sh.device == d:
                        p = sh.index[0].start or 0
                        v_bytes[p] = v_bytes.get(p, 0) + sh.data.nbytes
            used = (d.memory_stats() or {}).get("bytes_in_use")
            print(f"device {d.id}: bytes_in_use {used}; v_p bytes by pod "
                  f"{v_bytes}")
            ok &= len(v_bytes) == 1
        # theta' - theta0 is lr times the summed momenta; compare the
        # SPMD update with the reference's in relative L2 (bf16 grads:
        # the two programs batch the pods' backward passes differently)
        host = jax.device_get
        num = den = 0.0
        for a, b, c in zip(jax.tree.leaves(host(state["theta"])),
                           jax.tree.leaves(host(ref_state["theta"])),
                           jax.tree.leaves(theta0)):
            num += float(np.sum((a - b).astype(np.float64) ** 2))
            den += float(np.sum((b - c).astype(np.float64) ** 2))
        rel = (num / max(den, 1e-30)) ** 0.5
        good = rel <= POD_THETA_TOL
        ok &= good
        print(f"theta update after {steps} rounds: relative L2 diff "
              f"SPMD vs reference {rel:.3e} (tol {POD_THETA_TOL:g}) "
              f"{'ok' if good else 'FAIL'}")
        s = jax.tree.map(lambda x: jnp.sum(x, axis=0), state["v"])
        inv = max(float(jnp.max(jnp.abs(a - b)))
                  for a, b in zip(jax.tree.leaves(s),
                                  jax.tree.leaves(state["v0"])))
        good = inv <= 1e-5
        ok &= good
        print(f"invariant v0 == sum_p v_p: max|diff| {inv:.3e} "
              f"{'ok' if good else 'FAIL'}")
    return bool(ok)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the SPMD pod round on four chips and "
                         "its single-device reference")
    args = ap.parse_args(argv)
    import jax

    from repro.launch.cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform} "
              f"({dev.device_kind}).  There is no CPU fallback.",
              file=sys.stderr)
        return 1
    need = PODS if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} TPU chips, found {len(devices)}",
              file=sys.stderr)
        return 1
    enable_compile_cache()
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}",
          flush=True)
    t0 = time.perf_counter()
    ok = four_chips() if args.four_chips else one_chip()
    print(f"total {time.perf_counter() - t0:.1f} s")
    if not ok:
        _fail("a check failed (see above)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
