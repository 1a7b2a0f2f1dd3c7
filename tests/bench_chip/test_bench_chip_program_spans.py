"""The readers of the program's own spans and compile counter, on a
``stats`` built by hand: the window is the last ``grads`` gradients by
apply step, and every reader returns None where the program gave no
spans (as a program without them does)."""
import random

import pytest

import bench

READERS = ("worker_dispatch_ms", "reply_wait_ms", "serve_host_ms",
           "receives_in_flight", "compile_s")


def metric(name):
    return bench.load_module(bench.HERE / "metrics" / f"{name}.py")


def span(name, dur, **args):
    return {"ph": "X", "name": name, "cat": name.split(".")[0], "pid": 1,
            "tid": 1, "ts": 0.0, "dur": float(dur), "args": args}


def hand_stats(k=1):
    """Two workers, six applied gradients (steps 1-6), a seventh pushed
    and turned away (no step), and a pull-only request (seq -1).  Worker
    w's gradient s has step 2s + w + 1; its ``worker.grad`` lasts
    1000 + 100 (step - 1) us and its ``worker.rpc`` 5000 + 100 (step - 1)
    us.  Receives apply ``k`` gradients each; the one starting at step t
    lasts 100 t us and saw ``in_flight`` (t - 1) % 3."""
    events = [{"ph": "M", "name": "thread_name", "pid": 1, "tid": 1,
               "args": {"name": "ps-worker-0"}}]
    for step in range(1, 7):
        w, s = (step - 1) % 2, (step - 1) // 2
        events += [
            span("worker.next_batch", 50, worker=w, seq=s),
            span("worker.grad", 1000 + 100 * (step - 1), worker=w, seq=s),
            span("worker.rpc", 5000 + 100 * (step - 1), worker=w, seq=s,
                 step=step),
        ]
    for t in range(1, 7, k):
        events += [span("mailbox.drain", 10, k=k),
                   span("master.stack", 20, k=k, worker=0, seq=0),
                   span("master.apply", 100 * t, k=k, step=t, worker=0,
                        seq=0, in_flight=(t - 1) % 3)]
    events += [span("worker.grad", 9000, worker=0, seq=3),
               span("worker.rpc", 9000, worker=0, seq=3),
               span("worker.rpc", 7000, worker=1, seq=-1, step=6)]
    random.Random(0).shuffle(events)
    return {"applied": 6, "spans": events,
            "compile": {"count": 9, "seconds": 12.5, "in_call": []}}


def test_window_is_the_last_grads_by_step():
    sel = metric("worker_dispatch_ms")
    ctx = {"stats": hand_stats(), "grads": 4}
    assert sel.window_grads(ctx) == {(0, 1): 3, (1, 1): 4, (0, 2): 5,
                                     (1, 2): 6}
    assert sorted(e["args"]["step"] for e in sel.window_receives(ctx)) == \
        [3, 4, 5, 6]


def test_readers_against_hand_counts():
    ctx = {"stats": hand_stats(), "grads": 4}
    # the window's worker.grad spans last 1200, 1300, 1400, 1500 us
    assert metric("worker_dispatch_ms").read(ctx) == pytest.approx(1.35)
    # and their worker.rpc spans 5200 ... 5500 us
    assert metric("reply_wait_ms").read(ctx) == pytest.approx(5.35)
    # receives at steps 3-6: (300 + 400 + 500 + 600) us over 4 gradients
    assert metric("serve_host_ms").read(ctx) == pytest.approx(0.45)
    # their in_flight: 2, 0, 1, 2
    assert metric("receives_in_flight").read(ctx) == pytest.approx(1.25)
    assert metric("compile_s").read(ctx) == 12.5


def test_a_receive_of_two_counts_when_it_reaches_the_window():
    # receives start at steps 1, 3, 5; the window is steps 4-6, so the
    # receive of steps 3-4 and that of 5-6 are in it
    ctx = {"stats": hand_stats(k=2), "grads": 3}
    assert metric("serve_host_ms").read(ctx) == pytest.approx(
        1e-3 * (300 + 500) / 3)
    assert metric("receives_in_flight").read(ctx) == pytest.approx(
        (2 + 1) / 2)


@pytest.mark.parametrize("name", READERS)
def test_readers_return_none_without_the_program_s_spans(name):
    plain = {k: v for k, v in hand_stats().items()
             if k not in ("spans", "compile")}
    for ctx in ({"stats": plain, "grads": 4}, {"stats": None, "grads": 4},
                {"grads": 4}):
        assert metric(name).read(ctx) is None
    if name != "compile_s":
        assert metric(name).read({"stats": hand_stats(), "grads": 0}) \
            is None


def test_readers_are_declared_for_every_cell():
    spec = bench.load_json(bench.CHECKOUT / "BENCHMARK.json")
    per_layer = {m["name"]: m for m in spec["per_layer"]}
    for name in READERS:
        assert "workloads" not in per_layer[name]
    for w in spec["workloads"]:
        cell = bench.resolve(spec, w["name"])
        assert set(READERS) <= {m["name"] for m in cell.per_layer}
