"""Tier-2 driver smoke: the benchmark runner's --quick profile must keep
working (drivers rot silently otherwise) and every run must append one
entry to the repo-root BENCH_kernels.json trajectory."""
import json
import os

import pytest

from benchmarks import run as bench_run


def test_quick_profile_covers_every_suite():
    """Each suite has a quick argv, and every quick argv disables the
    results/ artifact (--out "") so smoke runs never clobber recorded
    paper-scale results."""
    for name in bench_run.SUITES:
        argv = bench_run.QUICK.get(name)
        assert argv is not None, f"no --quick profile for {name}"
        assert argv[argv.index("--out") + 1] == "", \
            f"--quick {name} would write a results/ artifact"


def _argv_values(argv, flag):
    i = argv.index(flag) + 1
    out = []
    while i < len(argv) and not argv[i].startswith("--"):
        out.append(argv[i])
        i += 1
    return out


def test_quick_cluster_exercises_shard_sweep():
    """The cluster smoke must sweep at least two shard counts so the
    row-sharded master's capacity claim stays in the CI trajectory."""
    shards = [int(s) for s in _argv_values(bench_run.QUICK["cluster"],
                                           "--shards")]
    assert len(shards) >= 2 and 1 in shards


def test_quick_cluster_exercises_procs_sweep():
    """The cluster smoke must also run the process-backend capacity
    sweep (it reuses --shards): the QUICK argv must not pass
    --skip-procs, so the procs claims stay in the CI trajectory."""
    argv = bench_run.QUICK["cluster"]
    assert "--skip-procs" not in argv
    assert len(_argv_values(argv, "--shards")) >= 2


def test_quick_cluster_covers_sent_family():
    """The cluster smoke must sweep at least one sent-snapshot member
    (dc-asgd / dana-dc / ga-asgd): bench_cluster asserts the documented
    eligibility matrix and measures the algorithm's flat path, so a
    kernel-eligibility regression for the newly eligible family fails
    CI instead of silently falling back to the tree path."""
    algos = _argv_values(bench_run.QUICK["cluster"], "--algos")
    assert set(algos) & {"dc-asgd", "dana-dc", "ga-asgd"}


def test_quick_cluster_covers_memtier_sweep():
    """The cluster smoke must sweep the memory-tier section across BOTH
    routing regimes: N = 8 (dense full-slab tiles survive — the routed
    path must not regress) and one N past the tiling knee (the
    scalar-prefetch kernel's 2u-stream win), so the PR-7 claims —
    prefetch_over_full_slab_x, prefetch_not_slower_at_n8,
    slab_traffic_scales_with_u, skewed_pull_saving_x — stay in the CI
    trajectory."""
    ns = [int(s) for s in _argv_values(bench_run.QUICK["cluster"],
                                       "--memtier-n")]
    assert 8 in ns and max(ns) >= 48


def test_quick_cluster_covers_pipeline_section():
    """The cluster smoke must run the hot-path pipeline section: the
    QUICK argv must not pass --skip-pipeline, so the stacked-wire,
    pull-ahead, and staleness-shift claims stay in the CI trajectory."""
    assert "--skip-pipeline" not in bench_run.QUICK["cluster"]


def test_quick_cluster_covers_dana_hetero():
    """The cluster smoke must sweep dana-hetero: its rate-weighted send
    is the PR-5 weighted-slab reduction path (receive batch + send
    kernel + rate lane), and bench_cluster's eligibility assertion plus
    the send sweep keep it pinned in CI."""
    algos = _argv_values(bench_run.QUICK["cluster"], "--algos")
    assert "dana-hetero" in algos


def test_quick_convergence_covers_real_lm_both_backends():
    """The convergence smoke must run the real-LM accuracy-at-scale
    sweep on BOTH live backends with >= 2 cluster sizes and >= 2
    algorithms (one of them the staleness-aware sa-asgd), so the
    lm_loss_decreases / lm_both_backends claims stay non-degenerate in
    the CI trajectory."""
    argv = bench_run.QUICK["convergence"]
    assert set(_argv_values(argv, "--lm-backends")) == {"thread",
                                                        "process"}
    workers = [int(w) for w in _argv_values(argv, "--lm-workers")]
    assert len(set(workers)) >= 2
    algos = _argv_values(argv, "--lm-algos")
    assert len(set(algos)) >= 2 and "sa-asgd" in algos


def test_quick_convergence_covers_pack_overhead():
    """The convergence smoke must keep the fused backward->wire pack
    micro-bench on (pack-reps > 0): its bit-exactness and speedup
    claims are the PR-10 hot-path regression guard."""
    argv = bench_run.QUICK["convergence"]
    assert int(_argv_values(argv, "--pack-reps")[0]) > 0


def test_bench_scaling_out_empty_writes_nothing(tmp_path, monkeypatch):
    """bench_scaling must treat --out "" as 'no artifact', not fall
    through to its default path (the --quick contract)."""
    from benchmarks import bench_scaling
    monkeypatch.chdir(tmp_path)
    bench_scaling.main(["--grads", "40", "--workers", "2",
                        "--algos", "dana-zero", "--out", ""])
    assert not (tmp_path / "results").exists()


def test_run_quick_kernels_and_cluster_appends_trajectory(tmp_path,
                                                          monkeypatch):
    """End-to-end: the driver executes the kernel + cluster suites on the
    --quick profile and appends exactly one trajectory entry."""
    traj = tmp_path / "BENCH_kernels.json"
    monkeypatch.setattr(bench_run, "TRAJECTORY", str(traj))
    out = bench_run.main(["--quick", "--only", "kernels", "cluster",
                          "heterogeneous"])
    assert all(s["ok"] for s in out.values()), out
    assert out["kernels"]["claims"]["fused_correct"]
    assert out["kernels"]["claims"]["batched_correct"]
    # the sharded capacity sweep rides in the cluster suite's claims
    sweep = out["cluster"]["claims"]["shard_sweep_updates_per_s"]
    assert set(sweep) == {"1", "2"} and all(v > 0 for v in sweep.values())
    # ...and so does the process-backend sweep, side by side with its
    # ratio against the threaded numbers at matching S
    procs = out["cluster"]["claims"]["procs_sweep_updates_per_s"]
    assert set(procs) == {"1", "2"} and all(v > 0 for v in procs.values())
    ratio = out["cluster"]["claims"]["procs_over_threaded_x_by_s"]
    assert set(ratio) == {"1", "2"} and all(v > 0 for v in ratio.values())
    # the PR-7 memory-tier claims: present and non-degenerate (the
    # routed dispatch must not lose to the full-slab kernel at N = 8;
    # the prefetch kernel must win where the dense tiles shrink; slab
    # traffic must scale with unique senders; hot-row pulls must save)
    cl = out["cluster"]["claims"]
    assert cl["prefetch_not_slower_at_n8"]
    assert cl["prefetch_over_full_slab_x"] > 1.0
    assert cl["slab_traffic_scales_with_u"]
    assert cl["skewed_pull_saving_x"] > 1.0
    # the PR-9 hot-path pipeline claims: present and non-degenerate —
    # finite positive speedup ratios, and the pull-ahead staleness dial
    # at depth 1 shifts the pinned single-worker lag by ~+1 (exactly
    # (G-1)/G over G messages; the unit tests pin the exact series)
    assert cl["stacked_over_tuple_x"] > 0.0
    assert cl["pullahead_over_sync_x"] > 0.0
    assert 0.5 < cl["staleness_shift_depth1"] <= 1.0
    trail = json.loads(traj.read_text())
    assert isinstance(trail, list) and len(trail) == 1
    entry = trail[0]
    assert entry["profile"] == "quick"
    assert entry["failures"] == []
    assert set(entry["suites"]) == {"kernels", "cluster", "heterogeneous"}
    # append-style: a second run extends, never overwrites
    bench_run.main(["--quick", "--only", "kernels"])
    assert len(json.loads(traj.read_text())) == 2


def test_trajectory_append_recovers_from_corruption(tmp_path):
    p = tmp_path / "BENCH_kernels.json"
    p.write_text("{not json")
    bench_run._append_trajectory({"probe": 1}, path=str(p))
    trail = json.loads(p.read_text())
    assert trail == [{"probe": 1}]


def test_tracing_disabled_guard_within_noise_of_hot_path():
    """Observability overhead guard: with tracing DISABLED, the guarded
    call sites must cost a negligible fraction of the measured hot path.

    The tracer's disabled-path contract is one module-attribute read and
    a branch per call site.  We measure that guard cost directly (delta
    over an empty loop, best of 3), scale it by the number of guarded
    sites a message crosses, and require it to stay under 10% of the
    measured per-message wall cost of a real free-mode cluster run — a
    RELATIVE threshold, so the test doesn't flake on slow CI hosts but
    does fail if the guard regresses into allocation, locking, or a time
    syscall."""
    import time

    import jax

    from repro.cluster import ClusterConfig, run_cluster
    from repro.core import GammaModel, HyperParams, make_algorithm
    from repro.data.synthetic import ClassificationTask
    from repro.models.toy import make_classifier_fns
    from repro.obs import trace

    assert not trace.enabled

    N = 200_000

    def best_of(fn, reps=3):
        return min(fn() for _ in range(reps))

    def empty_loop():
        t0 = time.perf_counter()
        for _ in range(N):
            pass
        return time.perf_counter() - t0

    def guarded_loop():
        t0 = time.perf_counter()
        for _ in range(N):
            if trace.enabled:  # pragma: no cover - must not be taken
                trace.complete("x", "test", 0.0, 0.0)
        return time.perf_counter() - t0

    per_guard = max(best_of(guarded_loop) - best_of(empty_loop), 0.0) / N
    # one message crosses 5 guarded sites, each reading the flag once:
    # the worker's batch + grad, its rpc, the mailbox drain, the serve
    # loop's apply and the master's stack
    per_msg_guard = 5 * per_guard

    # reference: real per-message wall cost, measured (warm-up run first
    # so jit compilation stays out of the measurement)
    task = ClassificationTask(dim=8, num_classes=4, batch_size=8, seed=3)
    init, grad_fn, make_eval = make_classifier_fns([8, 16, 4])
    params0 = init(jax.random.PRNGKey(0))
    eval_fn = make_eval(task.eval_batch(32))
    grads = 240

    def run_once():
        algo = make_algorithm("dana-zero", HyperParams(lr=0.05,
                                                       momentum=0.9))
        cfg = ClusterConfig(num_workers=4, total_grads=grads,
                            eval_every=10_000, mode="free", coalesce=4,
                            exec_model=GammaModel(seed=5))
        t0 = time.perf_counter()
        run_cluster(algo, grad_fn, params0, task.batch, cfg, eval_fn)
        return time.perf_counter() - t0

    run_once()                             # warm-up (compilation)
    per_msg_cost = best_of(run_once, reps=2) / grads

    ratio = per_msg_guard / per_msg_cost
    assert ratio < 0.10, (
        f"disabled-tracing guard costs {per_msg_guard * 1e9:.0f} ns/msg "
        f"({ratio:.1%} of the {per_msg_cost * 1e6:.1f} us/msg hot path); "
        f"the disabled path must stay near-free")
