"""Cluster orchestration: ``run_cluster`` mirrors ``run_simulation``.

Same signature shape, same ``History`` result, same ``Algorithm`` objects
— but executed by real threads through a mailbox instead of a
single-threaded event loop.  In ``deterministic`` mode the run is
step-for-step identical to the engine (tested bit-for-bit); ``paced`` and
``free`` modes trade that for actual wall-clock concurrency.
"""
from __future__ import annotations

import dataclasses
import sys
import threading
import time
from typing import Any, Callable

import jax

import numpy as np

from ..core.algorithms import SSGD, Algorithm
from ..core.gamma import GammaModel
from ..core.metrics import History
from ..core.types import Pytree
from ..kernels.flat_update import kernel_eligible
from ..obs import compiles, trace
from ..obs.metrics import (MetricsRegistry, SnapshotPublisher,
                           history_observer, serve_instruments)
from .clock import VirtualClock
from .faults import FaultInjector, FaultPlan
from .mailbox import Mailbox
from .master import Master
from .sharded import ShardedMaster
from .worker import TurnGate, Worker

MODES = ("deterministic", "paced", "free")


@dataclasses.dataclass(frozen=True)
class ClusterConfig:
    num_workers: int = 8
    total_grads: int = 1000
    eval_every: int = 100
    mode: str = "deterministic"
    coalesce: int = 1              # max messages per fused master receive
    exec_model: GammaModel = GammaModel()
    time_scale: float = 1e-3       # model time unit -> seconds (paced mode)
    faults: FaultPlan | None = None
    record_telemetry: bool = True
    use_kernel: bool | None = None  # None = auto (dana-zero, live modes)
    shards: int = 1                 # row-range master shards (flat path)
    mailbox_capacity: int = 0       # 0 = unbounded
    rpc_timeout: float = 120.0
    # memory tier: per-worker hot flat-row ranges for pull-only requests
    # (a tuple of num_workers entries, each None or (r0, r1)); masters
    # that cannot honor a range (tree path, sent-snapshot family) fall
    # back to full-range pulls
    hot_rows: tuple | None = None
    # row-sharded placement: optional custom initial shard row ranges,
    # and online busy_s-driven rebalancing at eval watermarks
    shard_ranges: tuple | None = None
    rebalance: bool = False
    rebalance_threshold: float = 1.1
    # execution backend: "thread" (default — deterministic/test substrate)
    # or "process" (shard servers + workers as OS processes over
    # shared-memory mailboxes; live modes, flat kernel path only — see
    # repro.cluster.procs for the support matrix)
    backend: str = "thread"
    # pin the message schedule to strict round-robin worker order (live
    # modes): makes a run schedule-deterministic on BOTH backends, which
    # is what the cross-backend bit-exactness tests compare under
    pin_schedule: bool = False
    # worker pull-ahead (live modes): each worker keeps up to this many
    # pushes in flight, computing its next gradient against the newest
    # reply it HAS — the RPC round trip overlaps gradient compute at the
    # cost of exactly `depth` extra designed staleness (the paper's
    # pipeline-induced-momentum regime).  0 = today's synchronous
    # push-pull, bit-exact; deterministic mode requires 0 (the virtual
    # clock serializes every RPC).  pin_schedule composes with depth=1:
    # the message ORDER stays round-robin-pinned, only the view each
    # gradient is computed against ages by one reply.
    pipeline_depth: int = 0


def flat_grad_program(spec, grad_fn, donate=()):
    """The worker's program on the flat wire: unpack the (R, 128) view,
    take the gradient, ``pack_fused`` it into the (R, 128) wire — one
    jit, ``jit(view, batch) -> wire``, its operations under the name
    scope ``worker_grad``; ``donate=(0,)`` donates the view."""
    def worker_grad(fv, batch):
        with jax.named_scope("worker_grad"):
            return spec.pack_fused(grad_fn(spec.unpack(fv), batch))

    # a lambda, so the program keeps its name (``jit__lambda``)
    return jax.jit(lambda fv, batch: worker_grad(fv, batch),
                   donate_argnums=donate)


def run_cluster(
    algo: Algorithm,
    grad_fn: Callable[[Pytree, Any], Pytree],
    params0: Pytree,
    next_batch: Callable[[int, int], Any],
    cfg: ClusterConfig,
    eval_fn: Callable[[Pytree], Any] | None = None,
    stats_out: dict | None = None,
    metrics: MetricsRegistry | None = None,
) -> History:
    """Run one threaded parameter-server training session.

    Arguments match ``repro.core.engine.run_simulation``; ``stats_out``
    (optional dict) receives runtime statistics: applied message count,
    wall time, per-worker message counts and the coalescing histogram.

    ``metrics`` (optional ``repro.obs.MetricsRegistry``) wires the
    observability layer in: telemetry rows feed the staleness/gap
    histograms through ``History.record``, the serve loops feed the
    drained-batch-size histogram and pull/overflow counters, and a
    background ``SnapshotPublisher`` samples mailbox depth + per-shard
    busy time off the hot path (its series lands in
    ``stats_out["obs_series"]``).  ``metrics=None`` (the default) leaves
    the hot path exactly as before — the instruments are never touched.

    Tracing (``repro.obs.trace``): when ``jax.profiler`` is recording as
    the call starts and the tracer is off, the call turns the tracer on
    for its own duration (no ``SnapshotPublisher``: that needs
    ``metrics`` or an explicit ``trace.enable()``), so its spans land on
    the profile's host plane.  Whenever the tracer was on, the call's
    spans are returned in ``stats_out["spans"]`` (``trace.events``
    format).  ``stats_out["compile"]`` is the process's running compile
    total (``repro.obs.compiles``) plus ``in_call``: the
    ``(fun_name, seconds, applied_at)`` of every compile event during
    the call, ``applied_at`` being the gradients applied by then.
    """
    if cfg.mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {cfg.mode!r}")
    if cfg.num_workers < 1 or cfg.total_grads < 1:
        raise ValueError("need at least one worker and one gradient")
    if cfg.shards < 1:
        raise ValueError(f"need shards >= 1, got {cfg.shards}")
    if cfg.backend not in ("thread", "process"):
        raise ValueError(f"backend must be 'thread' or 'process', "
                         f"got {cfg.backend!r}")
    if cfg.pin_schedule and cfg.mode == "deterministic":
        raise ValueError("pin_schedule is a live-mode pin (deterministic "
                         "mode already serializes the schedule through "
                         "the virtual clock)")
    if cfg.pin_schedule and cfg.faults is not None \
            and cfg.faults.any_dropout:
        raise ValueError("pin_schedule cannot combine with dropout (an "
                         "offline worker would wedge the turn gate)")
    if cfg.pipeline_depth < 0:
        raise ValueError(f"pipeline_depth must be >= 0, "
                         f"got {cfg.pipeline_depth}")
    if cfg.pipeline_depth > 0 and cfg.mode == "deterministic":
        raise ValueError("pipeline_depth > 0 requires a live mode "
                         "(deterministic mode serializes every RPC "
                         "through the virtual clock, so pull-ahead "
                         "would deadlock it); use paced or free")
    if cfg.backend == "process":
        from .procs import run_cluster_procs
        return run_cluster_procs(algo, grad_fn, params0, next_batch, cfg,
                                 eval_fn=eval_fn, stats_out=stats_out,
                                 metrics=metrics)
    if isinstance(algo, SSGD):
        raise ValueError(
            "ssgd needs the engine's synchronous barrier (per-message "
            "receive would silently change its semantics); use "
            "run_simulation, or an asynchronous algorithm here")
    n = cfg.num_workers
    deterministic = cfg.mode == "deterministic"
    if deterministic and cfg.faults is not None and cfg.faults.any_dropout:
        raise ValueError("dropout/rejoin is not supported in deterministic "
                         "mode (it would leave the virtual clock); use "
                         "stalls, or a live mode")

    sharded = cfg.shards > 1
    if cfg.rebalance and not sharded:
        raise ValueError("rebalance=True requires shards > 1 (there is "
                         "nothing to move rows between)")
    if cfg.shard_ranges is not None and not sharded:
        raise ValueError("shard_ranges requires shards > 1")
    use_kernel = cfg.use_kernel
    if use_kernel is None:
        # auto-routing is numerically silent for the elementwise family:
        # the flat fused path feeds per-message lr(t)/lr(t+1) scalars and
        # the lazy momentum-correction rescale into the kernel, so it
        # reproduces the algorithm path bit-for-bit, moving schedules
        # included (gap-aware and dana-hetero's rate-weighted views
        # agree to reduction-order tolerance).  dana-hetero's rate
        # telemetry is wired from real message timestamps: the master
        # passes each drained message's t_send into the fused pass as
        # its ``now``, exactly what the tree path's receive(now=...)
        # sees.  The sharded master exists only on the flat path, so
        # shards > 1 forces it (ShardedMaster rejects ineligible
        # algorithms itself).
        use_kernel = sharded or (not deterministic
                                 and kernel_eligible(algo))
    if sharded and not use_kernel:
        raise ValueError("shards > 1 requires the flat kernel master "
                         "(use_kernel must not be False)")

    # the publisher samples every 5 ms, so only metrics or an explicit
    # trace.enable() start it, never a profile alone
    publish = metrics is not None or trace.enabled
    own_trace = not trace.enabled and trace.profiler_recording()
    if own_trace:
        trace.enable()
    traced = trace.enabled
    t_call = time.perf_counter()
    watch = compiles.watch()
    try:
        history = _run_threads(algo, grad_fn, params0, next_batch, cfg,
                               eval_fn, stats_out, metrics, use_kernel,
                               publish, watch)
    finally:
        watch.close()
        if own_trace:
            trace.disable()
    if stats_out is not None:
        stats_out["compile"] = dict(compiles.totals(),
                                    in_call=watch.records)
        if traced:
            stats_out["spans"] = trace.events(since=t_call)
    return history


def _run_threads(algo, grad_fn, params0, next_batch, cfg, eval_fn,
                 stats_out, metrics, use_kernel, publish, watch):
    """``run_cluster``'s threaded backend, once the call is validated."""
    n = cfg.num_workers
    deterministic = cfg.mode == "deterministic"
    sharded = cfg.shards > 1
    injector = (FaultInjector(cfg.faults, n, cfg.exec_model.batch_size)
                if cfg.faults is not None else None)
    stop = threading.Event()
    history = History()
    state = algo.init(params0, n)
    t0 = time.perf_counter()

    if deterministic:
        time_fn = None                      # virtual time from the clock
        now_fn = None
    elif cfg.mode == "paced":
        def now_fn():                       # model-time units
            return (time.perf_counter() - t0) / cfg.time_scale
        time_fn = (lambda m: m.t_send)
    else:
        def now_fn():                       # wall seconds
            return time.perf_counter() - t0
        time_fn = (lambda m: m.t_send)

    # deterministic mode forces per-message receive so eval points and
    # event order match the engine exactly
    coalesce = 1 if deterministic else cfg.coalesce
    if sharded:
        shard_injectors = None
        if cfg.faults is not None:
            # shard injectors are reorder-only (num_workers=0: no stall
            # streams) — worker-side stalls/dropout stay on the shared
            # `injector` above
            shard_injectors = [
                FaultInjector(cfg.faults, 0, cfg.exec_model.batch_size,
                              shard_id=s)
                for s in range(cfg.shards)
            ]
        master = ShardedMaster(
            algo, state, shards=cfg.shards, history=history, stop=stop,
            total_grads=cfg.total_grads, coalesce=coalesce,
            record_telemetry=cfg.record_telemetry, eval_fn=eval_fn,
            eval_every=cfg.eval_every, injectors=shard_injectors,
            time_fn=time_fn, mailbox_capacity=cfg.mailbox_capacity,
            ranges=cfg.shard_ranges, rebalance=cfg.rebalance,
            rebalance_threshold=cfg.rebalance_threshold)
        mailbox = master.frontdoor
    else:
        mailbox = Mailbox(cfg.mailbox_capacity)
        master = Master(
            algo, state, mailbox=mailbox, history=history, stop=stop,
            total_grads=cfg.total_grads, coalesce=coalesce,
            use_kernel=use_kernel, record_telemetry=cfg.record_telemetry,
            eval_fn=eval_fn, eval_every=cfg.eval_every, injector=injector,
            time_fn=time_fn, pipeline_depth=cfg.pipeline_depth)
    watch.progress = lambda: master.applied
    # the master packed (or adopted) the algorithm state; dropping this
    # reference frees the pytree copy, which at real widths is
    # (N + 2) parameter copies of device memory
    del state

    # -- observability wiring (None-guarded: zero hot-path cost when off)
    publisher = None
    if metrics is not None:
        history.observer = history_observer(metrics)
        serve_mx = serve_instruments(metrics)
        if sharded:
            for srv in master.shards_:
                srv.metrics = serve_mx       # shared: per-thread cells
        else:
            master.metrics = serve_mx
    if publish:
        # gauge sources are lock-free reads (Mailbox.depth contract),
        # sampled by a background thread — never by cluster threads
        if sharded:
            sources = {}
            for s, (mb, srv) in enumerate(zip(master.mailboxes,
                                              master.shards_)):
                sources[f"mailbox_depth/shard{s}"] = \
                    (lambda mb=mb: mb.depth)
                sources[f"busy_s/shard{s}"] = \
                    (lambda srv=srv: srv.busy_s)
        else:
            sources = {"mailbox_depth": lambda: mailbox.depth,
                       "busy_s/master": lambda: master.busy_s}
        publisher = SnapshotPublisher(sources, registry=metrics)

    # warm-up pulls, in worker order on one thread (engine semantics);
    # master.warm() runs AFTER the hot-row ranges are validated below,
    # so the declared row-sliced view closures pre-compile too
    init_views = [master.initial_view(i) for i in range(n)]

    clock = None
    draw = None
    if deterministic:
        clock = VirtualClock(cfg.exec_model.sampler(n), n)
    elif cfg.mode == "paced":
        # one gamma stream per worker (np.random.Generator is not
        # thread-safe; statistics match, schedules don't need to)
        samplers = [
            dataclasses.replace(cfg.exec_model,
                                seed=cfg.exec_model.seed
                                + 1000003 * (wid + 1)).sampler(n)
            for wid in range(n)
        ]
        draw = (lambda wid: samplers[wid](wid))

    # fused backward->wire donation: the worker's view buffer feeds ONE
    # jit (unpack -> backward -> pack_fused), so the (R, 128) view can be
    # donated into it — flat views are always fresh copies (``_view_flat``
    # / reply buffers), never master state.  The view must not outlive
    # the call: telemetry attaches it to the GradMsg, pull-ahead computes
    # extra gradients against a cached view, and hot-row merges patch the
    # old view — those runs keep the copying path.
    donate = ((0,) if (not cfg.record_telemetry
                       and cfg.pipeline_depth == 0
                       and cfg.hot_rows is None) else ())
    if sharded and master.rebalancer is not None:
        # rebalance wire format: shard ranges move at run time, so the
        # worker ships the FULL packed gradient (the fan-out hands every
        # shard the same buffer and each slices its current rows in-jit);
        # the view stays the range-ordered tuple of (current-width)
        # slices, re-traced per width combination after a move
        spec = master.spec

        def _rebalance_grad(fv, batch):
            return spec.pack_fused(
                grad_fn(spec.unpack(spec.concat_rows(fv)), batch))

        grad_jit = jax.jit(_rebalance_grad, donate_argnums=donate)
        if publisher is not None:
            # the rebalancer's busy_s signal prefers the published
            # series (the PR-6 observability path) over the live gauges
            master.rebalancer.series_fn = publisher.series
    elif sharded:
        # sharded wire format: the worker's own jit gathers its view from
        # the range-ordered shard slices and scatters its packed gradient
        # back into per-shard slices — the worker pushes ONE gradient and
        # each shard consumes only its row range
        spec = master.spec
        subs = master.subs

        def _sharded_grad(fv, batch):
            g = spec.pack_fused(
                grad_fn(spec.unpack(spec.concat_rows(fv)), batch))
            return tuple(sub.take(g) for sub in subs)

        grad_jit = jax.jit(_sharded_grad, donate_argnums=donate)
    elif master.state_is_flat:
        # flat wire format: the worker unpacks its (R, 128) view and
        # emits its packed gradient inside ITS OWN jit (the fused
        # backward->wire pack) — the pytree<->flat traffic runs on the
        # (parallel) worker threads, never on the master hot path
        grad_jit = flat_grad_program(master._flat_algo.spec, grad_fn,
                                     donate)
    else:
        # tree path: views ALIAS master state (send returns theta0
        # itself), so donation is never safe here
        grad_jit = jax.jit(grad_fn)
    # hot-row pulls: one jitted merge closure per declaring worker, built
    # against the STATIC layout (skipped under rebalancing — ranges move,
    # so those runs fall back to full-range pulls automatically)
    hot_rows: list = [None] * n
    merge_views: list = [None] * n
    if cfg.hot_rows is not None:
        if len(cfg.hot_rows) != n:
            raise ValueError(f"hot_rows needs one entry per worker "
                             f"({n}), got {len(cfg.hot_rows)}")
        if not master.state_is_flat:
            raise ValueError("hot_rows requires the flat kernel master "
                             "(use_kernel must not be False)")
        rows_total = master._flat_algo.spec.rows
        rebalancing = sharded and master.rebalancer is not None
        for wid, hr in enumerate(cfg.hot_rows):
            if hr is None:
                continue
            r0, r1 = int(hr[0]), int(hr[1])
            if not 0 <= r0 < r1 <= rows_total:
                # the upper bound is INCLUSIVE (r1 == rows_total is the
                # full-height range); the message must say so
                raise ValueError(f"hot_rows[{wid}]={hr} invalid: need "
                                 f"0 <= r0 < r1 <= {rows_total} "
                                 f"(r1 bound inclusive)")
            if rebalancing:
                continue
            if sharded:
                plans = []
                for s, (s0, s1) in enumerate(master.ranges):
                    a, b = max(r0, s0), min(r1, s1)
                    if a < b:
                        plans.append((s, a - s0, b - s0))

                def merge(old, piece, plans=tuple(plans)):
                    new = list(old)
                    for s, a, b in plans:
                        new[s] = new[s].at[a:b].set(piece[s])
                    return tuple(new)

                merge_views[wid] = jax.jit(merge)
            else:
                merge_views[wid] = jax.jit(
                    lambda old, piece, a=r0, b=r1:
                    old.at[a:b].set(piece))
            hot_rows[wid] = (r0, r1)

    if not deterministic:
        # compile fused variants AND the declared hot-row view closures
        # before the clock starts — no trace lands mid-run (tested)
        master.warm(hot_ranges=tuple(sorted(
            {hr for hr in hot_rows if hr is not None})))

    gate = TurnGate(n, stop) if cfg.pin_schedule else None
    workers = [
        Worker(wid, master=master, mailbox=mailbox, grad_jit=grad_jit,
               next_batch=next_batch, stop=stop, mode=cfg.mode,
               init_view=init_views[wid], clock=clock, draw=draw,
               now_fn=now_fn, time_scale=cfg.time_scale, injector=injector,
               telemetry=cfg.record_telemetry, rpc_timeout=cfg.rpc_timeout,
               hot_rows=hot_rows[wid], merge_view=merge_views[wid],
               gate=gate, pipeline_depth=cfg.pipeline_depth)
        for wid in range(n)
    ]

    master_thread = threading.Thread(target=master.serve, name="ps-master",
                                     daemon=True)
    # CPython's default 5ms GIL switch interval turns every mailbox/reply
    # hand-off into a multi-millisecond convoy; the cluster is made of many
    # sub-millisecond critical sections, so ask for fast switching while
    # the run is live (restored afterwards).
    prev_switch = sys.getswitchinterval()
    sys.setswitchinterval(2e-4)
    try:
        if publisher is not None:
            publisher.start()
        master_thread.start()
        for w in workers:
            w.start()

        # the master join is bounded like the workers' below: the join IS
        # the run, so the deadline starts only once the serve loop has no
        # legitimate reason to keep running (stop raised, or every worker
        # gone) — a wedged loop then surfaces as a diagnosable error with
        # its pending messages rejected, instead of hanging the caller
        m_deadline = None
        while master_thread.is_alive():
            master_thread.join(timeout=0.05)
            if not master_thread.is_alive():
                break
            if m_deadline is None:
                if stop.is_set() or not any(w.is_alive() for w in workers):
                    m_deadline = (time.monotonic()
                                  + max(cfg.rpc_timeout, 2.0))
            elif time.monotonic() > m_deadline:
                stop.set()
                master.reject_pending()
                err = (f" (master error: {master.error!r})"
                       if master.error else "")
                raise RuntimeError(f"master failed to shut down{err}")
        stop.set()
        if clock is not None:
            clock.stop()
        deadline = time.monotonic() + max(cfg.rpc_timeout, 10.0)
        for w in workers:
            while w.is_alive():
                master.reject_pending()   # unblock stragglers mid-push
                w.join(timeout=0.05)
                if time.monotonic() > deadline:
                    raise RuntimeError(
                        f"worker {w.wid} failed to shut down")
    finally:
        sys.setswitchinterval(prev_switch)
        if publisher is not None:
            publisher.stop()

    errors = [("master", master.error)] if master.error else []
    errors += [(f"worker-{w.wid}", w.error) for w in workers if w.error]
    if errors:
        name, first = errors[0]
        raise RuntimeError(
            f"cluster run failed in {name} "
            f"({len(errors)} thread error(s))") from first

    if master.applied != cfg.total_grads:
        raise RuntimeError(f"cluster stopped early: applied "
                           f"{master.applied}/{cfg.total_grads} gradients")

    history.final_params = master.master_params()
    if stats_out is not None:
        t_end = time.perf_counter()
        applied_total = sum(k * v for k, v in
                            master.coalesce_counts.items())
        steady = None
        if master.steady_t is not None and t_end > master.steady_t:
            steady = ((master.applied - master._steady_mark)
                      / (t_end - master.steady_t))
        stats_out.update(
            applied=master.applied,
            wall_s=t_end - t0,
            updates_per_s=master.applied / max(t_end - t0, 1e-9),
            steady_updates_per_s=steady,
            master_busy_s=master.busy_s,
            master_updates_per_s=master.applied / max(master.busy_s, 1e-9),
            coalesce_counts=dict(sorted(master.coalesce_counts.items())),
            mean_coalesce=(applied_total
                           / max(sum(master.coalesce_counts.values()), 1)),
            grads_per_worker={w.wid: w.grads_sent for w in workers},
            use_kernel=use_kernel,
            shards=cfg.shards,
        )
        if sharded:
            stats_out["shard_applied"] = master.shard_applied
            stats_out["telemetry_dropped"] = master.tele_dropped
            if master.rebalancer is not None:
                stats_out["rebalance_moves"] = master.rebalance_moves
                stats_out["shard_ranges"] = master.current_ranges
        if publisher is not None:
            stats_out["obs_series"] = publisher.series()
        if master.state_is_flat:
            fa = master._flat_algo
            flat = (master.shards_[0].state if sharded
                    else master._flat_state)
            if fa.lane is not None:
                # staleness signal from the flat scalar lane: age (in
                # master updates) of each worker's sent snapshot
                stats_out["sent_staleness"] = [
                    float(x) for x in np.asarray(fa.staleness(flat))]
            if fa.fam.rate_weighted:
                # rate telemetry from the flat rate lane: the EMA of
                # each worker's inter-push interval (dana-hetero's
                # weighting signal, fed from real message timestamps)
                from ..core.flat import RATE_INTERVAL, RATE_LANE
                stats_out["rate_intervals"] = [
                    float(x) for x in np.asarray(
                        RATE_LANE.get(flat["rate"], RATE_INTERVAL))]
    return history
