"""The flat_update kernels compile for a TPU v5e at the chip smoke's real
shapes (qwen2-1.5b widths, 4 layers, 1/8 vocabulary: R = 1,918,464 rows,
N = 2 workers, k = 2 messages), without a chip attached.

Interpret mode runs none of the TPU lowering's checks (block alignment,
dynamic lane indexing, unsupported primitives), so these compiles are
what keeps the main path loadable on the chip between chip runs.  The
topology is described inside a module fixture, never while a module is
imported: only one process at a time may load the TPU compiler's
library.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import chip_smoke
from repro.core.flat import FlatSpec
from repro.kernels.flat_update.kernel import (
    BLOCK_ROWS, _pick_block_rows, flat_master_update_batch_2d,
    flat_master_update_batch_gap, flat_master_update_batch_prefetch)
from repro.kernels.flat_update.send import _send_view_pallas
from repro.models.api import ModelGradFn

N = chip_smoke.WORKERS
K = chip_smoke.COALESCE


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no logs in /tmp
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip; keep it out of the cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def rows():
    grad_fn = ModelGradFn(chip_smoke.MODEL, reduced=False,
                          overrides=chip_smoke.OVERRIDES)
    shapes = jax.eval_shape(grad_fn.init, jax.random.PRNGKey(0))
    return FlatSpec.from_tree(shapes).rows


def _compile(fn, chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _receive_shapes(r, weighted):
    f32, i32 = jnp.float32, jnp.int32
    shapes = [((r, 128), f32), ((N, r, 128), f32), ((r, 128), f32),
              ((K, r, 128), f32), ((K,), i32)] + [((K,), f32)] * 5
    if weighted:
        shapes.append(((K, N), f32))
    return shapes


def test_smoke_rows(rows):
    # 245,536,256 parameters fill 1,918,252 rows of 128 lanes, padded to
    # whole 256-row tiles
    assert rows == 1_918_464
    assert _pick_block_rows(rows, N) == BLOCK_ROWS
    assert _pick_block_rows(rows, K + 2) == BLOCK_ROWS


@pytest.mark.parametrize("hat_mode", ["v0", "weighted"])
@pytest.mark.parametrize("kernel", [flat_master_update_batch_2d,
                                    flat_master_update_batch_prefetch],
                         ids=["dense", "prefetch"])
def test_receive_kernel_compiles(chip, rows, kernel, hat_mode):
    weighted = hat_mode == "weighted"

    def fn(theta, v, v0, g, ids, lrs, lrs_next, gammas, cgs, vscales,
           w=None):
        return kernel(theta, v, v0, None, None, g, ids, lrs, lrs_next,
                      gammas, cgs, vscales, nesterov=False,
                      hat_mode=hat_mode, weights=w)

    _compile(fn, chip, *_receive_shapes(rows, weighted))


@pytest.mark.parametrize("k", [1, K])
def test_fused_receive_reads_one_gradient_in_place(chip, rows, k,
                                                   monkeypatch):
    """The master's fused receive compiled for the chip: at k = 1 the
    kernel's (1, R, 128) gradient operand is a bitcast of the gradient
    parameter, so no state-sized copy or fusion precedes the kernel; at
    k > 1 the one concatenation happens inside the program."""
    import re

    import repro.kernels.flat_update.ops as ops
    from repro.cluster.master import fused_flat_program
    from repro.core import HyperParams, make_algorithm
    from repro.kernels.flat_update import FlatAlgorithm

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)   # no interpret mode
    grad_fn = ModelGradFn(chip_smoke.MODEL, reduced=False,
                          overrides=chip_smoke.OVERRIDES)
    fa = FlatAlgorithm(make_algorithm("dana-zero", HyperParams(
        lr=0.05, momentum=0.9)), use_pallas=True)
    params = jax.eval_shape(grad_fn.init, jax.random.PRNGKey(0))
    flat = jax.eval_shape(lambda p: fa.init(p, N), params)
    assert fa.spec.rows == rows

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    text = fused_flat_program(fa, k, False).lower(
        jax.tree.map(lambda x: sds(x.shape, x.dtype), flat),
        sds((k,), jnp.int32), sds((k,), jnp.float32),
        tuple(sds((rows, 128), jnp.float32) for _ in range(k)),
        None).compile().as_text()
    call = [ln for ln in text.splitlines()
            if "custom-call(" in ln and "tpu_custom_call" in ln]
    assert len(call) == 1
    # the kernel's gradient operand stays f32[k,R,128]
    assert re.findall(r"f32\[(\d+),\d+,128\]",
                      call[0][call[0].index("custom-call("):])[-1] == str(k)
    made = [ln.strip() for ln in text.splitlines()
            if re.search(rf"= f32\[(\d+,)?{rows},128\]\S* "
                         rf"(copy|fusion|concatenate)\(", ln)]
    if k == 1:
        assert made == []
        grad = re.search(r"(%\S+) = f32\[\d+,128\]\S* parameter\(\d+\).*"
                         r'op_name="g_flat\[0\]"', text).group(1)
        assert re.search(rf"= f32\[1,{rows},128\]\S* bitcast\({grad}\)",
                         text)
    else:
        assert len(made) == 1 and "receive/concatenate" in made[0]


@pytest.mark.parametrize("n", [1, N])
def test_send_kernel_compiles(chip, rows, n):
    fn = functools.partial(_send_view_pallas, u2=None, eps=1e-8,
                           interpret=False)
    f32 = jnp.float32
    _compile(lambda theta, slab, w, c: fn(theta, slab, w, c), chip,
             ((rows, 128), f32), ((n, rows, 128), f32), ((n,), f32),
             ((), f32))


@pytest.mark.parametrize("prefetch", [False, True])
def test_gap_kernel_compiles(chip, rows, prefetch):
    f32, i32 = jnp.float32, jnp.int32

    def fn(theta, v, sent, avg, g, ids, lrs, gammas, cgs, vscales):
        return flat_master_update_batch_gap(
            theta, v, sent, avg, g, ids, lrs, gammas, cgs, vscales,
            gap_ema=0.99, n_elems=rows * 128, prefetch=prefetch)

    _compile(fn, chip, ((rows, 128), f32), ((N, rows, 128), f32),
             ((N, rows, 128), f32), ((), f32), ((K, rows, 128), f32),
             ((K,), i32), *[((K,), f32)] * 4)


@pytest.mark.parametrize("r", [296, 1000, 1_968_752, 1_918_256])
def test_row_tiles_are_multiples_of_8(r):
    for window in (1, 2, 4, 64):
        block = _pick_block_rows(r, window)
        assert r % block == 0
        assert block % 8 == 0 or block == r


@pytest.mark.parametrize("n_elems,rows", [
    (1, 8), (128 * 256, 256),                # one tile: rows padded to 8
    (128 * 256 + 1, 512), (128 * 1045, 1280), (245_536_256, 1_918_464)])
def test_flat_spec_pads_tall_states_to_full_tiles(n_elems, rows):
    spec = FlatSpec(None, [(n_elems,)], ["float32"])
    assert spec.rows == rows
    for window in (1, 2, 4, 10, 32):
        assert _pick_block_rows(rows, window) == min(rows, BLOCK_ROWS)


def test_row_tile_refuses_unaligned_rows():
    assert _pick_block_rows(100, 2) == 100          # one full-height tile
    with pytest.raises(ValueError, match="multiple of 8"):
        _pick_block_rows(1004, 2)
    assert np.all([_pick_block_rows(r, 2) % 8 == 0
                   for r in range(264, 2048, 8)])
