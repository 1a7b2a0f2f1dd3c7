"""Observability layer tests: tracer, metrics registry, cluster wiring.

Three contracts are load-bearing:

* the exported trace is valid Chrome-trace JSON with spans from every
  component type (worker, master/shard, mailbox) plus counter tracks —
  the same check CI runs against the bench artifact;
* staleness telemetry is backend-identical: the discrete-event engine,
  the tree-path cluster, and the flat-kernel cluster record the same
  ``History.staleness`` series in deterministic mode;
* observability is inert when off: telemetry/tracing toggles must not
  change a single bit of the trained parameters.
"""
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cluster import ClusterConfig, Mailbox, run_cluster
from repro.cluster.mailbox import FanoutMailbox
from repro.cluster.master import RUN_AHEAD
from repro.core import (GammaModel, HyperParams, SimulationConfig,
                        make_algorithm, run_simulation)
from repro.data.synthetic import ClassificationTask
from repro.models.toy import make_classifier_fns
from repro.obs import (DRAIN_K_EDGES, STALENESS_EDGES, Counter, Gauge,
                       Histogram, MetricsRegistry, SnapshotPublisher,
                       history_observer, trace, validate_chrome_trace)

HP = HyperParams(lr=0.05, momentum=0.9)
TASK = ClassificationTask(dim=8, num_classes=4, batch_size=8, seed=3)
INIT, GRAD_FN, MAKE_EVAL = make_classifier_fns([8, 16, 4])
PARAMS0 = INIT(jax.random.PRNGKey(0))
EVAL_FN = MAKE_EVAL(TASK.eval_batch(32))


@pytest.fixture(autouse=True)
def _trace_off():
    """The tracer is module-global state: never leak it across tests."""
    yield
    trace.disable()


def _run_cluster(name, *, workers=4, grads=80, seed=5, metrics=None, **kw):
    algo = make_algorithm(name, HP)
    cfg = ClusterConfig(num_workers=workers, total_grads=grads,
                        eval_every=1000, exec_model=GammaModel(seed=seed),
                        mode=kw.pop("mode", "deterministic"), **kw)
    return run_cluster(algo, GRAD_FN, PARAMS0, TASK.batch, cfg, EVAL_FN,
                       metrics=metrics)


def _run_engine(name, *, workers=4, grads=80, seed=5, metrics=None):
    algo = make_algorithm(name, HP)
    cfg = SimulationConfig(num_workers=workers, total_grads=grads,
                           eval_every=1000, exec_model=GammaModel(seed=seed))
    return run_simulation(algo, GRAD_FN, PARAMS0, TASK.batch, cfg, EVAL_FN,
                          metrics=metrics)


def _assert_params_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# tracer unit behavior
# ---------------------------------------------------------------------------
def test_ring_drop_oldest():
    trace.enable(capacity=8)
    for j in range(20):
        trace.complete(f"ev{j}", "test", 0.0, 1e-6, j=j)
    trace.disable()
    obj = trace.export()
    spans = [e for e in obj["traceEvents"] if e["ph"] == "X"]
    assert len(spans) == 8
    # drop-oldest: the tail survives, the head is gone
    assert sorted(e["args"]["j"] for e in spans) == list(range(12, 20))
    assert obj["otherData"]["dropped_events"] == 12


def test_begin_end_nesting_and_span_cm():
    trace.enable()
    trace.begin("outer", "test")
    trace.begin("inner", "test")
    trace.end(k=1)
    trace.end()
    with trace.span("cm", "test", tag="x"):
        pass
    trace.disable()
    spans = [e for e in trace.export()["traceEvents"] if e["ph"] == "X"]
    names = [e["name"] for e in spans]
    assert names == ["outer", "inner", "cm"]   # export sorts by start ts
    inner = next(e for e in spans if e["name"] == "inner")
    outer = next(e for e in spans if e["name"] == "outer")
    assert inner["dur"] <= outer["dur"]
    assert next(e for e in spans if e["name"] == "cm")["args"] == {
        "tag": "x"}


def test_disabled_guard_records_nothing():
    trace.enable()
    trace.disable()
    # call sites are guarded by trace.enabled; a correctly-guarded hot
    # path emits nothing once disabled
    if trace.enabled:  # pragma: no cover - the guard is the point
        trace.complete("x", "test", 0.0, 1.0)
    with trace.span("guarded", "test"):
        pass
    assert all(e["ph"] == "M" or e["ph"] != "X"
               for e in trace.export()["traceEvents"])


def test_export_writes_file_and_validates(tmp_path):
    trace.enable()
    trace.complete("apply", "master", 0.0, 1e-5, k=4)
    trace.instant("dropout", "faults", worker=2)
    trace.counter("mailbox_depth", 3)
    trace.disable()
    path = tmp_path / "t.json"
    obj = trace.export(str(path))
    assert validate_chrome_trace(obj) == []
    on_disk = json.loads(path.read_text())
    assert validate_chrome_trace(on_disk) == []
    phs = {e["ph"] for e in on_disk["traceEvents"]}
    assert {"M", "X", "i", "C"} <= phs


def test_validator_rejects_malformed():
    assert validate_chrome_trace([]) != []                 # not an object
    assert validate_chrome_trace({}) != []                 # no traceEvents
    base = {"pid": 1, "tid": 1, "ts": 0.0}
    good = dict(base, ph="X", name="a", dur=1.0)
    cases = [
        dict(base, ph="Q", name="a"),                      # unknown ph
        dict(base, ph="X", name="a"),                      # X without dur
        dict(base, ph="X", name="a", dur=-1.0),            # negative dur
        dict(base, ph="X", dur=1.0),                       # missing name
        dict(base, ph="C", name="a", args={"v": "high"}),  # non-numeric C
        dict(ph="X", name="a", ts=0.0, dur=1.0),           # missing pid/tid
    ]
    for bad in cases:
        errs = validate_chrome_trace({"traceEvents": [good, bad]})
        assert errs, f"accepted malformed event {bad}"
    # zero spans is itself invalid (wiring regression, not a trace)
    assert validate_chrome_trace({"traceEvents": []}) == [
        "trace contains no complete spans"]
    assert validate_chrome_trace({"traceEvents": [good]}) == []


# ---------------------------------------------------------------------------
# metrics instruments
# ---------------------------------------------------------------------------
def test_counter_multithreaded_exact():
    c = Counter("c")
    barrier = threading.Barrier(8)

    def work():
        barrier.wait()
        for _ in range(1000):
            c.add(1.0)

    ts = [threading.Thread(target=work) for _ in range(8)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    assert c.value == 8000.0


def test_histogram_buckets_quantiles_nan():
    h = Histogram("h", STALENESS_EDGES)
    for x in [0, 0, 1, 3, 5, 100, float("nan")]:
        h.observe(x)
    assert h.count == 6                      # NaN is not a sample
    snap = h.snapshot()
    assert snap["buckets"]["le_0"] == 2
    assert snap["buckets"]["le_1"] == 1
    assert snap["buckets"]["le_3"] == 1
    assert snap["buckets"]["le_6"] == 1      # 5 falls in (4, 6]
    assert snap["buckets"]["le_128"] == 1
    assert snap["min"] == 0 and snap["max"] == 100
    assert h.quantile(0.5) == 1.0
    assert h.quantile(0.99) == 128.0
    assert h.nonzero_buckets() == 5
    empty = Histogram("e", DRAIN_K_EDGES)
    assert np.isnan(empty.quantile(0.5))


def test_histogram_multithreaded_merge():
    h = Histogram("h", (10, 20, 30))

    def work(v):
        for _ in range(500):
            h.observe(v)

    ts = [threading.Thread(target=work, args=(v,)) for v in (5, 15, 99)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    snap = h.snapshot()
    assert snap["count"] == 1500
    assert snap["buckets"] == {"le_10": 500, "le_20": 500, "le_30": 0,
                               "inf": 500}


def test_histogram_rejects_bad_edges():
    with pytest.raises(ValueError):
        Histogram("h", (3, 1, 2))
    with pytest.raises(ValueError):
        Histogram("h", ())


def test_registry_idempotent_and_kind_checked():
    reg = MetricsRegistry()
    assert reg.counter("x") is reg.counter("x")
    assert reg.histogram("h", STALENESS_EDGES) is reg.histogram(
        "h", STALENESS_EDGES)
    with pytest.raises(TypeError):
        reg.gauge("x")
    g = reg.gauge("g", fn=lambda: 7.0)
    assert g.value == 7.0
    snap = reg.snapshot()
    assert snap["g"] == {"type": "gauge", "value": 7.0}
    assert reg.names() == ["g", "h", "x"]


def test_gauge_set_and_fn():
    g = Gauge("g")
    g.set(3)
    assert g.value == 3.0
    assert Gauge("g2", fn=lambda: 11).value == 11.0


def test_history_observer_feeds_instruments():
    reg = MetricsRegistry()
    obs = history_observer(reg)
    obs(lag=2.0, gap=1e-3, grad_norm=2.0, staleness=2.0)
    obs(lag=4.0, gap=1e-2, grad_norm=0.0, staleness=float("nan"))
    snap = reg.snapshot()
    assert snap["updates"]["value"] == 2.0
    assert snap["staleness"]["count"] == 2
    assert snap["sent_staleness"]["count"] == 1      # NaN dropped
    assert snap["gap"]["count"] == 2
    assert snap["normalized_gap"]["count"] == 1      # grad_norm==0 skipped


def test_snapshot_publisher_samples_and_stops():
    vals = iter(range(1000))
    pub = SnapshotPublisher({"x": lambda: next(vals), "bad": None},
                            interval=0.002)
    pub.start()
    time.sleep(0.05)
    pub.stop()
    assert not pub.is_alive()
    series = pub.series()
    assert len(series["x"]) >= 2                 # sampled + final sample
    xs = [v for _, v in series["x"]]
    assert xs == sorted(xs)
    assert series["bad"] == []                   # failing source swallowed


def _msg(wid=0):
    from repro.cluster.mailbox import GradMsg
    return GradMsg(wid, grad=("g0", "g1"), view=None, view_step=0,
                   t_send=0.0)


def test_mailbox_depth_lock_free_read():
    mb = Mailbox()
    stop = threading.Event()
    for j in range(3):
        mb.put(_msg(j), stop)
    assert mb.depth == 3
    got = []
    with mb._cond:                       # holder blocks put/drain...
        t = threading.Thread(target=lambda: got.append(mb.depth))
        t.start()
        t.join(timeout=1.0)              # ...but depth reads never wait
        assert not t.is_alive()
    assert got == [3]
    mb.drain(8, stop)
    assert mb.depth == 0


def test_fanout_mailbox_depth_is_max_over_shards():
    fo = FanoutMailbox([Mailbox(), Mailbox()])
    stop = threading.Event()
    for j in range(3):
        fo.put(_msg(j), stop)            # fan-out: every shard gets a part
    assert fo.mailboxes[0].depth == 3
    fo.mailboxes[0].drain(2, stop)
    assert fo.mailboxes[0].depth == 1
    assert fo.mailboxes[1].depth == 3
    assert fo.depth == 3                 # deepest shard queue


# ---------------------------------------------------------------------------
# cluster wiring: traced runs export component spans + counter tracks
# ---------------------------------------------------------------------------
def _traced_run(name, **kw):
    trace.enable()
    try:
        _run_cluster(name, mode="free", grads=120, coalesce=4, **kw)
    finally:
        trace.disable()
    return trace.export()


def test_traced_single_master_run():
    obj = _traced_run("dc-asgd")
    assert validate_chrome_trace(obj) == []
    spans = [e for e in obj["traceEvents"] if e["ph"] == "X"]
    cats = {e.get("cat") for e in spans}
    assert {"worker", "master", "mailbox"} <= cats
    tracks = {e["name"] for e in obj["traceEvents"] if e["ph"] == "C"}
    assert {"mailbox_depth", "busy_s/master"} <= tracks
    # thread_name metadata makes Perfetto tracks readable
    tnames = {e["args"]["name"] for e in obj["traceEvents"]
              if e["ph"] == "M"}
    assert any(n.startswith("ps-worker") for n in tnames)


def test_traced_sharded_run():
    obj = _traced_run("dana-zero", shards=2)
    assert validate_chrome_trace(obj) == []
    cats = {e.get("cat") for e in obj["traceEvents"] if e["ph"] == "X"}
    assert {"worker", "shard", "mailbox"} <= cats
    tracks = {e["name"] for e in obj["traceEvents"] if e["ph"] == "C"}
    assert {"busy_s/shard0", "busy_s/shard1", "mailbox_depth/shard0",
            "mailbox_depth/shard1"} <= tracks


def test_cluster_metrics_registry_populated():
    reg = MetricsRegistry()
    _run_cluster("dc-asgd", mode="free", grads=120, coalesce=4,
                 metrics=reg)
    snap = reg.snapshot()
    assert snap["updates"]["value"] == 120
    assert snap["staleness"]["count"] == 120
    # dc-asgd carries a sent snapshot: its staleness series is real
    assert snap["sent_staleness"]["count"] == 120
    assert snap["drain_k"]["count"] >= 120 / 4     # coalesced batches
    assert snap["drain_k"]["sum"] == 120
    assert snap["gap"]["count"] == 120


# ---------------------------------------------------------------------------
# satellite 1: staleness series is backend-identical
# ---------------------------------------------------------------------------
def test_staleness_identical_across_backends_sent_family():
    h_e = _run_engine("dc-asgd")
    h_tree = _run_cluster("dc-asgd", use_kernel=False)
    h_flat = _run_cluster("dc-asgd", use_kernel=True)
    assert len(h_e.staleness) == 80
    # sent-snapshot refreshed on every send => staleness == lag
    np.testing.assert_array_equal(h_e.staleness, h_e.lag)
    np.testing.assert_array_equal(h_e.staleness, h_tree.staleness)
    np.testing.assert_array_equal(h_e.staleness, h_flat.staleness)
    assert max(h_e.staleness) > 0          # non-degenerate with 4 workers


def test_staleness_nan_for_snapshot_free_algo():
    h_e = _run_engine("dana-zero")
    h_c = _run_cluster("dana-zero")
    assert len(h_c.staleness) == 80
    assert all(np.isnan(h_e.staleness))
    assert all(np.isnan(h_c.staleness))


# ---------------------------------------------------------------------------
# satellite 3: observability off == observability on, bit for bit
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name,kernel", [("dc-asgd", True),
                                         ("dana-zero", False)])
def test_telemetry_toggle_params_bit_identical(name, kernel):
    h_on = _run_cluster(name, use_kernel=kernel, record_telemetry=True)
    h_off = _run_cluster(name, use_kernel=kernel, record_telemetry=False)
    assert h_off.lag == []                 # telemetry really off
    _assert_params_equal(h_on.final_params, h_off.final_params)


def test_tracing_toggle_params_bit_identical():
    h_off = _run_cluster("dana-zero", use_kernel=True)
    trace.enable()
    try:
        h_on = _run_cluster("dana-zero", use_kernel=True)
    finally:
        trace.disable()
    _assert_params_equal(h_on.final_params, h_off.final_params)


def test_metrics_registry_params_bit_identical():
    h_plain = _run_cluster("dc-asgd", use_kernel=True)
    h_metered = _run_cluster("dc-asgd", use_kernel=True,
                             metrics=MetricsRegistry())
    _assert_params_equal(h_plain.final_params, h_metered.final_params)


# ---------------------------------------------------------------------------
# the profiler sink: a profile recording puts the cluster's spans on its
# host plane and in stats_out["spans"]
# ---------------------------------------------------------------------------
HOT_SPANS = ("worker.next_batch", "worker.grad", "worker.rpc",
             "mailbox.drain", "master.apply")


def _pinned_run(stats, grad_fn=GRAD_FN, grads=24):
    """A free-mode flat-kernel run whose message order is pinned, so its
    parameters are reproducible."""
    algo = make_algorithm("dana-zero", HP)
    cfg = ClusterConfig(num_workers=2, total_grads=grads, mode="free",
                        pin_schedule=True, record_telemetry=False,
                        use_kernel=True, exec_model=GammaModel(seed=5))
    return run_cluster(algo, grad_fn, PARAMS0, TASK.batch, cfg,
                       stats_out=stats)


def _host_plane_spans(directory):
    import glob
    import os

    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(str(directory), "**",
                                         "*.xplane.pb"), recursive=True))[-1]
    names = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names += [ev.name for ev in line.events
                          if ev.name.startswith(HOT_SPANS)]
    return names


@pytest.fixture
def no_publisher(monkeypatch):
    """Fails the test if run_cluster starts a SnapshotPublisher."""
    import repro.cluster.runtime as runtime

    started = []

    class Refused(SnapshotPublisher):
        def start(self):
            started.append(self)
            super().start()

    monkeypatch.setattr(runtime, "SnapshotPublisher", Refused)
    return started


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """One pinned run recorded by jax.profiler, and the same run without
    it: (stats, host-plane span names, params), (stats, params)."""
    d = tmp_path_factory.mktemp("xplane")
    stats_p, stats_off = {}, {}
    h_off = _pinned_run(stats_off)
    jax.profiler.start_trace(str(d))
    try:
        h_on = _pinned_run(stats_p)
    finally:
        jax.profiler.stop_trace()
    assert not trace.enabled          # the call turned its own ring off
    return ((stats_p, _host_plane_spans(d), h_on.final_params),
            (stats_off, h_off.final_params))


def _spans(stats, name):
    return [e for e in stats["spans"] if e["ph"] == "X"
            and e["name"] == name]


def test_profiled_run_puts_spans_on_the_host_plane(profiled):
    (stats, host, _), _ = profiled
    for name in ("worker.grad", "worker.rpc", "master.apply",
                 "mailbox.drain"):
        assert name in host, name
    # stats_out["spans"] holds the same spans as the profile
    ring = [e["name"] for e in stats["spans"] if e["ph"] == "X"
            and e["name"].startswith(HOT_SPANS)]
    assert sorted(ring) == sorted(host)
    assert validate_chrome_trace({"traceEvents": stats["spans"]}) == []
    cats = {e["name"]: e["cat"] for e in stats["spans"] if e["ph"] == "X"}
    assert cats["worker.grad"] == "worker"
    assert cats["master.apply"] == "master"
    assert cats["mailbox.drain"] == "mailbox"
    assert not [e for e in stats["spans"] if e["name"] == "mailbox.put"]


def test_profiler_alone_starts_no_publisher(tmp_path, no_publisher):
    jax.profiler.start_trace(str(tmp_path))
    try:
        stats = {}
        _pinned_run(stats, grads=6)
    finally:
        jax.profiler.stop_trace()
    assert no_publisher == []
    assert _spans(stats, "worker.grad")
    assert "obs_series" not in stats


def test_profiler_off_records_nothing(no_publisher):
    assert not trace.enabled
    before = len(trace.events())
    stats = {}
    _pinned_run(stats, grads=6)
    assert "spans" not in stats
    assert len(trace.events()) == before
    assert no_publisher == []
    assert not trace.enabled


def test_spans_of_one_gradient_share_its_identity(profiled):
    (stats, _, _), _ = profiled
    rpc = {(e["args"]["worker"], e["args"]["seq"]): e["args"]["step"]
           for e in _spans(stats, "worker.rpc") if "step" in e["args"]}
    assert sorted(rpc.values()) == list(range(1, 25))
    for name in ("worker.next_batch", "worker.grad"):
        ids = [(e["args"]["worker"], e["args"]["seq"])
               for e in _spans(stats, name)]
        assert len(ids) == len(set(ids))
        assert set(rpc) <= set(ids)
    applies = sorted(_spans(stats, "master.apply"), key=lambda e: e["ts"])
    steps = [e["args"]["step"] for e in applies]
    assert steps == sorted(steps) == list(range(1, 25))
    for e in applies:                  # one gradient per drain here
        a = e["args"]
        assert e["args"]["k"] == 1
        assert rpc[(a["worker"], a["seq"])] == a["step"]
        # the receive read its one gradient in place: nothing stacked,
        # and no eager stack of its own
        assert a["stacked"] == 0
    assert not _spans(stats, "master.stack")
    # each worker's gradients come back in its own order
    for w in (0, 1):
        mine = sorted((s, st) for (ww, s), st in rpc.items() if ww == w)
        assert [st for _, st in mine] == sorted(st for _, st in mine)


def test_in_flight_counted_and_params_unchanged_by_the_profiler(profiled):
    (stats, _, params_on), (stats_off, params_off) = profiled
    in_flight = [e["args"]["in_flight"]
                 for e in _spans(stats, "master.apply")]
    assert len(in_flight) == 24
    assert all(isinstance(n, int) and 0 <= n <= RUN_AHEAD
               for n in in_flight)
    _assert_params_equal(params_on, params_off)


def test_compile_counter_lists_the_worker_program(profiled):
    def fresh_grad(params, batch):      # a function no run has traced
        return GRAD_FN(params, batch)

    stats = {}
    _pinned_run(stats, grad_fn=fresh_grad, grads=4)
    comp = stats["compile"]
    names = [name for name, secs, at in comp["in_call"]]
    assert "<lambda>" in names          # runtime.flat_grad_program
    assert all(secs >= 0 and at >= 0 for _, secs, at in comp["in_call"])
    assert comp["count"] >= 1
    assert comp["seconds"] >= sum(s for _, s, _ in comp["in_call"]) - 1e-9
    # the profiled run put its compiles on the ring as spans
    (profiled_stats, _, _), _ = profiled
    kinds = {e["name"] for e in profiled_stats["spans"]
             if e.get("cat") == "compile"}
    assert kinds <= {"compile.trace", "compile.lower", "compile.backend"}


def test_worker_and_receive_programs_carry_stable_scopes():
    from repro.cluster.master import fused_flat_program
    from repro.cluster.runtime import flat_grad_program
    from repro.kernels.flat_update import FlatAlgorithm

    fa = FlatAlgorithm(make_algorithm("dana-zero", HP))
    flat = fa.init(PARAMS0, 2)
    rows = fa.spec.rows
    sds = jax.ShapeDtypeStruct
    worker = flat_grad_program(fa.spec, GRAD_FN).lower(
        sds((rows, 128), jnp.float32), TASK.batch(0, 0))
    receive = fused_flat_program(fa, 1, False).lower(
        flat, sds((1,), jnp.int32), sds((1,), jnp.float32),
        (sds((rows, 128), jnp.float32),), None)
    for lowered, module, scope in ((worker, "jit__lambda", "worker_grad"),
                                   (receive, "jit_fused", "receive")):
        assert lowered.as_text().startswith(f"module @{module} ")
        assert f"/{scope}/" in lowered.as_text(debug_info=True)
