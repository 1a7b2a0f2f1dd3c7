"""Entry ``run_cluster``: the asynchronous parameter-server cluster.

One run of a cell:

1. Set-up.  Weights on the device from the seed (the reference's own
   initializer, one jitted call).  A *check call* of
   ``repro.cluster.run_cluster`` with the window's configuration and feed
   type, whose feed releases the check's gradients in rounds: one batch
   to every worker at once, so every worker's gradient of a round is in
   flight together and the master applies them in whatever order they
   arrive, as in the window.  Its final parameters are compared with the
   reference afterwards, and its round times size the window.  Then the
   *window call*: the same configuration with ``total_grads`` sized from
   that rate, fed from a pool of distinct seeded rows.
2. The window opens once every worker has asked for its second batch
   (compiles, first-call traces and cache loads are behind it) and the
   device has drained; it closes when ``run_cluster`` has returned and
   the final parameters are ready.
3. After the window: peak device memory, then the program's state is
   freed and the plain reference replays the check's gradients in every
   order the master could have applied them (float32,
   ``Precision.HIGHEST``); the program is held to the closest order.
"""
from __future__ import annotations

import dataclasses
import gc
import itertools
import math
import shutil
import statistics
import sys
import tempfile
import threading
import time
import weakref
from functools import partial

import numpy as np

import bench
import tokens

SYNC_TIMEOUT_S = 600.0


# ---------------------------------------------------------------------------
# feeds: the next_batch the cluster's workers call
# ---------------------------------------------------------------------------
class RoundFeed:
    """Releases round ``r`` of the check at once: worker ``w`` gets batch
    ``r * workers + w`` once every worker has asked for its batch of
    round ``r`` (so all of round ``r - 1`` has been answered) and the
    device has drained.  The round's gradients are then in flight
    together and the master applies them in arrival order.  A worker
    that asks beyond the last round waits until another worker's thread
    has ended (the run is over); its push is refused by the stopped
    master.  ``round_s[r]`` is the time from releasing round ``r`` to
    releasing round ``r + 1``."""

    def __init__(self, batches, workers, rounds, sync):
        self.batches = batches
        self.workers = workers
        self.rounds = rounds
        self.sync = sync
        self.cond = threading.Condition()
        self.asked = [0] * workers
        self.threads = [None] * workers
        self.t_release = []

    @property
    def round_s(self):
        t = self.t_release
        return [b - a for a, b in zip(t, t[1:])]

    def _another_gone(self, wid) -> bool:
        for j, ref in enumerate(self.threads):
            if j != wid and ref is not None:
                thread = ref()
                if thread is None or not thread.is_alive():
                    return True
        return False

    def __call__(self, wid, counter):
        deadline = time.monotonic() + SYNC_TIMEOUT_S
        with self.cond:
            self.asked[wid] += 1
            self.threads[wid] = weakref.ref(threading.current_thread())
            r = self.asked[wid] - 1
            self.cond.notify_all()
            while not (r < self.rounds and min(self.asked) > r):
                if self._another_gone(wid):
                    if r >= self.rounds:
                        return self.batches[-1]  # refused: the run is over
                    raise RuntimeError(f"check feed: a worker ended before "
                                       f"round {r}")
                if time.monotonic() > deadline:
                    raise TimeoutError(f"check feed: round {r} never "
                                       f"released")
                self.cond.wait(0.005)
            if len(self.t_release) == r:
                if r:
                    self.sync()
                self.t_release.append(time.perf_counter())
        return self.batches[r * self.workers + wid]


class WindowFeed:
    """Serves the pool in call order and opens the window once every
    worker has asked for its second batch (after draining the device)."""

    def __init__(self, pool, workers, sync, annotate):
        self.pool = pool
        self.workers = workers
        self.sync = sync
        self.annotate = annotate
        self.lock = threading.Lock()
        self.calls = [[] for _ in range(workers)]
        self.served = 0
        self.opening = False
        self.t_open = None

    def __call__(self, wid, counter):
        now = time.perf_counter()
        with self.lock:
            self.calls[wid].append(now)
            i = self.served % len(self.pool)
            self.served += 1
            opening = (not self.opening
                       and all(len(c) >= 2 for c in self.calls))
            self.opening = self.opening or opening
        if opening:
            with self.annotate("bench.window_open"):
                self.sync()
            self.t_open = time.perf_counter()
        with self.annotate("bench.next_batch"):
            return self.pool[i]

    def counts(self, applied: int):
        """(completed in the window, attempted, not applied, cycles ms)."""
        before = sum(max(0, sum(1 for t in c if t < self.t_open) - 1)
                     for c in self.calls)
        calls = sum(len(c) for c in self.calls)
        cycles = [1e3 * (b - a) for c in self.calls
                  for a, b in zip(c, c[1:]) if a >= self.t_open]
        in_window = applied - before
        not_applied = calls - applied
        return in_window, in_window + not_applied, not_applied, cycles


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Program:
    """The program's objects for one cell, built once per process."""
    grad_fn: object
    algo: object
    cluster_config: object
    run_cluster: object


def build_program(cell, hp_overrides=None, grad_wrap=None) -> Program:
    """``hp_overrides`` and ``grad_wrap`` break the timed path for the
    fault tests; a benchmark run passes neither."""
    from repro.cluster import ClusterConfig, run_cluster
    from repro.core import HyperParams, make_algorithm
    from repro.models.api import ModelGradFn

    conf, tr = cell.config, cell.traffic
    prog = conf["program"]
    grad_fn = ModelGradFn(prog["model"], reduced=False,
                          overrides=prog["overrides"])
    cfg = grad_fn.build_config()
    arch = conf["arch"]
    for ours, theirs in (("d_model", "d_model"), ("num_heads", "num_heads"),
                         ("num_kv_heads", "num_kv_heads"),
                         ("head_dim", "head_dim"), ("d_ff", "d_ff"),
                         ("vocab_size", "vocab_size"),
                         ("num_layers", "num_layers"),
                         ("qkv_bias", "qkv_bias")):
        if arch[ours] != getattr(cfg, theirs):
            raise ValueError(f"program config {cfg.name}: {theirs} = "
                             f"{getattr(cfg, theirs)}, the benchmark's "
                             f"configuration says {arch[ours]}")
    hp = dict(lr=tr["lr"], momentum=tr["momentum"])
    hp.update(hp_overrides or {})
    algo = make_algorithm(tr["algorithm"], HyperParams(**hp))
    ccfg = ClusterConfig(num_workers=tr["workers"], total_grads=1,
                         mode=tr["mode"], coalesce=tr["coalesce"],
                         record_telemetry=False, use_kernel=True,
                         backend="thread", rpc_timeout=SYNC_TIMEOUT_S)
    if grad_wrap is not None:
        grad_fn = grad_wrap(grad_fn)
    return Program(grad_fn, algo, ccfg, run_cluster)


def device_sync():
    """Block until the device has run everything enqueued before."""
    import jax
    import jax.numpy as jnp

    def bench_sync(x):
        return x + 1.0

    one = jnp.ones((), jnp.float32)
    step = jax.jit(bench_sync)
    jax.block_until_ready(step(one))

    def sync():
        jax.block_until_ready(step(one))
    return sync


def leaf_norms(tree_a, tree_b=None):
    """Host floats: the L2 norm of each leaf (of ``a - b``), in leaf
    order, reduced on the device."""
    import jax
    import jax.numpy as jnp

    def norm(a, b=None):
        d = a if b is None else a - b
        return jnp.sqrt(jnp.sum(jnp.square(d.astype(jnp.float32))))
    leaves = (jax.tree.map(norm, tree_a) if tree_b is None
              else jax.tree.map(norm, tree_a, tree_b))
    return [float(x) for x in jax.tree.leaves(leaves)]


def check_call(program: Program, params0, check_batches, workers, rounds,
               sync):
    """The check call: the master's parameters after ``rounds`` rounds of
    one gradient per worker, on the host; the leaf norms of their change;
    the time per gradient (the median round after the first, which
    compiles, over the workers); and the drained-batch sizes."""
    import jax

    feed = RoundFeed(check_batches, workers, rounds, sync)
    ccfg = dataclasses.replace(program.cluster_config,
                               total_grads=workers * rounds)
    stats: dict = {}
    hist = program.run_cluster(program.algo, program.grad_fn, params0,
                               feed, ccfg, stats_out=stats)
    theta = hist.final_params
    norms = leaf_norms(theta, params0)
    host = jax.device_get(theta)
    del hist, theta
    gc.collect()                # free the check run's state before the window
    rounds_s = feed.round_s[1:] or feed.round_s
    return (host, norms, statistics.median(rounds_s) / workers,
            stats.get("coalesce_counts"))


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------
def reference_parts(cell):
    conf, tr = cell.config, cell.traffic
    ref = bench.load_module(bench.HERE / "references"
                            / f"{conf['reference']}.py")
    master = bench.load_module(bench.HERE / "masters"
                               / f"{tr['algorithm']}.py")
    return ref, master, ref.Arch.from_config(conf)


def make_inputs(cell, seed: int):
    """Device weights and the check's host rows, both from ``seed``."""
    import jax

    ref, _, arch = reference_parts(cell)
    tr = cell.traffic
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                             (seed >> 32) & 0x7FFFFFFF)
    params0 = jax.jit(partial(ref.init_params, arch=arch))(key)
    n = tr["workers"] * tr["check_rounds"]
    rows = tokens.batches(seed, 1, n, tr["batch"], tr["seq"],
                          arch.vocab_size, **tr["markov"])
    return params0, rows


def round_orders(workers: int, rounds: int) -> list:
    """Every order in which the master can apply the check's rounds, as
    ``[(round, worker), ...]``: each round's gradients in any order, the
    rounds one after another.  Orders that differ only in the last round
    give the same gradients and parameters equal to rounding (its replies
    are never used), so the last round is taken in one order."""
    perms = list(itertools.permutations(range(workers)))
    last = tuple(range(workers))
    return [[(r, w) for r, perm in enumerate(choice + (last,))
             for w in perm]
            for choice in itertools.product(perms, repeat=rounds - 1)]


class Judge:
    """The plain reference's side of the check for one seed: the check's
    gradients replayed in each order of ``orders`` (every order the
    master could have applied them, by default), each reduced to the
    leaf norms of its change and its loss on the check's rows."""

    def __init__(self, cell, params0, rows, prec="f32",
                 state_dtype="float32", grad_wrap=None, orders=None):
        import jax
        import jax.numpy as jnp

        ref, master, arch = reference_parts(cell)
        tr = cell.traffic
        workers = tr["workers"]
        self.limits = cell.limits
        self.params0 = params0
        self.rows = rows
        self._loss = jax.jit(partial(ref.loss, arch=arch, prec="f32"))
        grad = jax.jit(jax.grad(partial(ref.loss, arch=arch, prec=prec)))
        if grad_wrap is not None:
            grad = grad_wrap(grad)
        self.loss0 = self.loss(params0)
        self.first_norms = None
        self.replays = []          # (order, leaf norms of the change, loss)
        for order in orders or round_orders(workers, tr["check_rounds"]):
            steps = [(w, jnp.asarray(rows[r * workers + w]))
                     for r, w in order]
            theta, first = master.replay(
                params0, grad, steps, lr=tr["lr"], momentum=tr["momentum"],
                workers=workers, dtype=jnp.dtype(state_dtype))
            if self.first_norms is None:
                self.first_norms = [float(x) for x in jax.tree.leaves(first)]
            self.replays.append((order, leaf_norms(theta, params0),
                                 self.loss(theta)))
            del theta, steps

    def loss(self, params) -> float:
        import jax
        import jax.numpy as jnp
        params = jax.device_put(params)
        return float(np.mean([float(self._loss(params, jnp.asarray(r)))
                              for r in self.rows]))

    def numbers(self, cand_norms, cand_loss) -> dict:
        """The check's numbers for a candidate's change after the check's
        gradients, against the replay of the order closest to it: the
        order whose larger number, as a share of its limit, is least."""
        med_g = statistics.median(self.first_norms)
        kept = [i for i, g in enumerate(self.first_norms)
                if g >= 1e-3 * med_g]
        per_order = []
        for order, ref_norms, ref_loss in self.replays:
            med = statistics.median(ref_norms[i] for i in kept)
            gap = max(abs(cand_norms[i] - ref_norms[i])
                      / max(ref_norms[i], med) for i in kept)
            lgap = abs(cand_loss - ref_loss) / abs(self.loss0 - ref_loss)
            per_order.append((max(gap / self.limits["dtheta_gap"],
                                  lgap / self.limits["loss_gap"]),
                              gap, lgap, ref_loss))
        best = min(range(len(per_order)), key=lambda j: per_order[j][0])
        _, gap, lgap, ref_loss = per_order[best]
        return {"dtheta_gap": gap, "loss_gap": lgap,
                "order": best, "orders": len(per_order),
                "dtheta_gap_by_order": [p[1] for p in per_order],
                "loss_gap_by_order": [p[2] for p in per_order],
                "left_out_leaves": len(self.first_norms) - len(kept),
                "loss0": self.loss0, "loss_ref": ref_loss,
                "loss_cand": cand_loss}

    def candidate(self, replay: int = 0):
        """A replay of this judge as a candidate: (leaf norms, loss)."""
        _, norms, loss = self.replays[replay]
        return norms, loss


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        program: Program | None = None):
    import jax

    tr = cell.traffic
    workers = tr["workers"]
    tokens_per_grad = tr["batch"] * tr["seq"]
    program = program or build_program(cell)
    sync = device_sync()

    phases = {"program": time.perf_counter() - t_start}
    params0, check_rows = make_inputs(cell, seed)
    jax.block_until_ready(params0)
    phases["inputs"] = time.perf_counter() - t_start
    theta_p, prog_norms, step_s, check_drains = check_call(
        program, params0, check_rows, workers, tr["check_rounds"], sync)
    phases["check_call"] = time.perf_counter() - t_start
    total = workers + max(1, math.ceil(seconds / step_s))
    _, _, arch = reference_parts(cell)
    pool = tokens.batches(seed, 2, total + 2 * workers, tr["batch"],
                          tr["seq"], arch.vocab_size, **tr["markov"])
    phases["pool"] = time.perf_counter() - t_start

    annotate = (jax.profiler.TraceAnnotation if trace
                else _no_annotation)
    feed = WindowFeed(pool, workers, sync, annotate)
    ccfg = dataclasses.replace(program.cluster_config, total_grads=total)
    tmp = None
    if trace:
        tmp = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(tmp)
    stats: dict = {}
    error = None
    pauses = GcPauses()
    gc.callbacks.append(pauses)
    try:
        hist = program.run_cluster(program.algo, program.grad_fn, params0,
                                   feed, ccfg, stats_out=stats)
        final = jax.block_until_ready(hist.final_params)
    except Exception as e:  # noqa: BLE001 - reported as a failed run
        # keep the message only: the traceback holds the run's state
        error, hist, final = repr(e), None, None
        print(f"window call failed: {error}", file=sys.stderr)
    t_close = time.perf_counter()
    gc.callbacks.remove(pauses)
    if trace:
        with jax.profiler.TraceAnnotation("bench.window_close"):
            pass
        jax.profiler.stop_trace()
    dev = jax.devices()[0]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    nonfinite = 0
    if final is not None:
        nonfinite = sum(int(not bool(jax.numpy.all(jax.numpy.isfinite(l))))
                        for l in jax.tree.leaves(final))
    del hist, final
    gc.collect()

    applied = stats.get("applied", 0)
    if error is None and feed.t_open is not None:
        in_window, attempted, not_applied, cycles = feed.counts(applied)
        failed = max(0, not_applied - (workers - 1))
        window_s = t_close - feed.t_open
        setup_s = feed.t_open - t_start
    else:
        in_window = attempted = failed = len(pool)
        cycles, window_s, setup_s = [], None, None

    t_ref = time.perf_counter()
    judge = Judge(cell, params0, check_rows)
    nums = judge.numbers(prog_norms, judge.loss(theta_p))
    del theta_p
    phases["reference_s"] = time.perf_counter() - t_ref
    lim = cell.limits
    checks = {
        "dtheta_gap": (nums["dtheta_gap"], lim["dtheta_gap"]),
        "loss_gap": (nums["loss_gap"], lim["loss_gap"]),
        "window_nonfinite_leaves": (nonfinite, 0),
        "window_failed_grads": (failed, 0),
    }
    correct = (error is None
               and all(v <= l for v, l in checks.values()))

    ctx = {
        "cell": cell, "arch": arch, "traffic": tr, "chips": cell.chips,
        "window_s": window_s, "setup_s": setup_s, "grads": in_window,
        "tokens": in_window * tokens_per_grad, "cycles_ms": cycles,
        "peak_bytes": peak, "stats": stats,
        "peaks": bench.peaks_for(dev.device_kind)
        if dev.platform == "tpu" else None,
        "params": int(sum(np.prod(l.shape)
                          for l in jax.tree.leaves(params0))),
        "trace": None,
    }
    if trace and error is None:
        from devtrace import Trace
        t = Trace.load(tmp)
        ctx["trace"] = t
        ctx["trace_window"] = t.window("bench.window_open",
                                       "bench.window_close")
    if tmp is not None:
        shutil.rmtree(tmp, ignore_errors=True)
    return {
        "correct": bool(correct), "attempted": int(attempted),
        "failed": int(failed), "ctx": ctx, "checks": checks,
        "error": error,
        "extra": {"check_step_s": step_s, "total_grads": total,
                  "window_s": window_s, "phases": phases,
                  "coalesce_counts": stats.get("coalesce_counts"),
                  "longest_cycles_ms": longest_cycles(feed),
                  "gc_pauses_ms": pauses.longest(feed.t_open),
                  "check_drains": check_drains, "check": nums},
    }


class GcPauses:
    """A ``gc.callbacks`` entry: the length of every collection of the
    interpreter's cyclic garbage collector, which holds the GIL (and so
    every cluster thread) while it runs."""

    def __init__(self):
        self.t_start = None
        self.pauses = []           # (ms, generation, perf_counter at end)

    def __call__(self, phase, info):
        now = time.perf_counter()
        if phase == "start":
            self.t_start = now
        elif self.t_start is not None:
            self.pauses.append((1e3 * (now - self.t_start),
                                info["generation"], now))

    def longest(self, t_open, top=3):
        """The ``top`` longest pauses after ``t_open``: [ms, generation,
        seconds from the window's opening to its end]."""
        if t_open is None:
            return []
        inside = sorted(p for p in self.pauses if p[2] >= t_open)
        return [[ms, gen, end - t_open] for ms, gen, end in inside[::-1][:top]]


def longest_cycles(feed, top=3):
    """The ``top`` longest gradient cycles of the window, in ms, each with
    the seconds from the window's opening to its end (where a stall
    sits)."""
    if feed.t_open is None:
        return []
    ends = sorted((1e3 * (b - a), b - feed.t_open) for c in feed.calls
                  for a, b in zip(c, c[1:]) if a >= feed.t_open)
    return [[ms, at] for ms, at in ends[::-1][:top]]


class _no_annotation:
    def __init__(self, name):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False
