"""The trace->metric reduction on a trace built by hand, and the FLOP and
byte functions against hand counts."""
import pytest

import bench
import breakdown
import devtrace
from devtrace import Op, Trace

MS = 1e6  # ns


def metric(name):
    return bench.load_module(bench.HERE / "metrics" / f"{name}.py")


def arch_of(config):
    ref = bench.load_module(bench.HERE / "references" / "dense_lm.py")
    return ref.Arch.from_config(bench.load_json(
        bench.HERE / "configs" / f"{config}.json"))


def test_flops_per_token_hand_counts():
    flops = metric("step_mfu").flops_per_token
    # qwen2-1.5b-l4: per layer 2*1536*12*128 + 2*1536*2*128 + 3*1536*8960
    # = 46,792,704 matrix parameters, x4 layers, + the 1536 x 18,992 head
    # = 216,342,528; x6 = 1,298,055,168; attention 12*4*12*128*512
    # = 37,748,736; 1.336 GFLOP per token
    assert flops(arch_of("qwen2-1.5b-l4"), 512) == 1_335_803_904
    # chatglm3-6b-l1: 2*4096*32*128 + 2*4096*2*128 + 3*4096*13696
    # = 203,948,032, + 4096 * 8,128 = 237,240,320; x6 = 1,423,441,920;
    # attention 12*1*32*128*512 = 25,165,824
    assert flops(arch_of("chatglm3-6b-l1"), 512) == 1_448_607_744


@pytest.mark.parametrize("k,copies", [(1, 10), (2, 12)])
def test_receive_bytes_are_the_f32_copies_it_streams(k, copies):
    P = 245_536_256
    assert metric("receive_roofline").bytes_per_call(P, 2, k) == \
        4 * P * copies


def small_trace():
    """Device 0 over a 100 ms window: two worker programs (30 ms each),
    a stack (2 ms), a receive module whose kernel runs 10 ms at k=2 and
    8 ms at k=1, and a 20 ms idle gap covered by a host span."""
    rows = 1000
    grad_op = ("flat_master_update_batch_2d.1 = f32[%d,%d,128] "
               "custom-call(f32[%d,%d,128])")
    ops = [
        Op(0, "fusion.1", "jit__lambda", 0 * MS, 30 * MS),
        Op(0, "concatenate.1", "jit_stack", 30 * MS, 32 * MS),
        Op(0, "flat_master_update_batch_2d.1", "jit_fused", 32 * MS, 42 * MS,
           grad_op % (1, rows, 2, rows)),
        Op(0, "fusion.1", "jit__lambda", 42 * MS, 72 * MS),
        Op(0, "flat_master_update_batch_2d.1", "jit_fused", 72 * MS,
           80 * MS,
           grad_op % (1, rows, 1, rows)),
        Op(0, "fusion.9", "jit_fused", 75 * MS, 78 * MS),
    ]
    spans = [("bench.window_open", -5 * MS, 0.0),
             ("bench.next_batch", 81 * MS, 99 * MS),
             ("bench.window_close", 100 * MS, 100.5 * MS)]
    return Trace(ops=ops, spans=spans, devices=1), rows


def ctx_for(trace, rows):
    lo, hi = trace.window("bench.window_open", "bench.window_close")
    return {"trace": trace, "trace_window": (lo, hi), "chips": 1,
            "grads": 2, "rows": rows, "params": rows * 128,
            "traffic": {"workers": 2, "seq": 512},
            "peaks": {"hbm_bytes_per_s": 1e12, "bf16_flops_per_s": 1e14}}


def test_trace_window_and_interval_arithmetic():
    t, rows = small_trace()
    lo, hi = t.window("bench.window_open", "bench.window_close")
    assert (lo, hi) == (0.0, 100 * MS)
    busy = [(o.start, o.end) for o in t.ops]
    assert devtrace.covered(busy) == 80 * MS
    assert devtrace.gaps(busy, lo, hi) == [(80 * MS, 100 * MS)]
    assert devtrace.busy_share(t, lo, hi, 1) == 80 * MS
    # a second chip idle all window halves the average
    assert devtrace.busy_share(t, lo, hi, 2) == 40 * MS


def test_metric_readers_on_the_small_trace():
    t, rows = small_trace()
    ctx = ctx_for(t, rows)
    assert metric("device_idle_share").read(ctx) == pytest.approx(20.0)
    assert metric("worker_grad_ms").read(ctx) == pytest.approx(30.0)
    assert metric("wire_stack_ms").read(ctx) == pytest.approx(1.0)
    # 12 + 10 copies of rows*128 f32 in 18 ms of kernel time
    need = 4 * rows * 128 * (12 + 10)
    assert metric("receive_roofline").read(ctx) == pytest.approx(
        100 * need / 18e-3 / 1e12)


def test_readers_leave_out_what_they_cannot_read():
    t, rows = small_trace()
    ctx = ctx_for(t, rows)
    ctx["trace"] = Trace(ops=[], spans=t.spans, devices=1)
    for name in ("worker_grad_ms", "wire_stack_ms", "receive_roofline"):
        assert metric(name).read(ctx) is None
    assert metric("receive_roofline").read({"trace": None}) is None


def test_breakdown_names_the_idle_gap_by_its_host_span():
    t, rows = small_trace()
    busy_s, window_s, brk = breakdown.summarize(ctx_for(t, rows))
    assert busy_s == pytest.approx(0.080)
    assert window_s == pytest.approx(0.100)
    assert brk["device_ops"][0] == ["jit__lambda:fusion.1",
                                    pytest.approx(0.060)]
    assert brk["idle_gaps"] == [["bench.next_batch", pytest.approx(0.020)]]
