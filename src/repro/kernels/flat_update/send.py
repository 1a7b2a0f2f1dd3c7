"""Weighted-slab reduction: the flat send path's view construction.

Every look-ahead send in the family is the same shape over flat rows:

    view = theta - c * sum_j w[j] * slab[j]          [/ (sqrt(u2) + eps)]

with a (N, R, 128) slab, an (N,) weight vector, and a scalar coefficient
c = lr(t) [* gamma] [* tau] [* vscale] (``SendSpec`` in ``ops.py`` says
which factors an algorithm uses; ``Algorithm._send_scale`` composes the
same product in the same order on the tree path):

  dana-zero / dana-dc   slab = v0[None],  w = [1]      c = lr*gamma*vs
  dana-nadam            slab = m0[None],  w = [1]      c = lr*b1, adaptive
  lwp                   slab = v[None],   w = [1]      c = lr*tau*vs
  dana-hetero           slab = v (all N), w = r_j/r_i  c = lr*gamma*vs
  asgd / theta-senders  no reduction at all (w = 0): view IS theta

The reduction is per row, so a row-range shard runs the identical kernel
on its slice (``view[r0:r1] == flat_send_view(theta[r0:r1],
slab[:, r0:r1], ...)`` bit-for-bit — property-tested), which is how the
sharded master's sends reduce per row range.

Lowering: one Pallas grid over row tiles on TPU (c and the N weights
ride in SMEM; a tile's N slab rows accumulate one by one), the jnp
reference elsewhere.  The
reference mirrors the tree path's ``tensordot`` + axpy expression
bit-for-bit (that is the production jnp pairing, pinned by the
flat == tree equivalence tests).  The Pallas lowering agrees with the
jitted reference to 1-ULP fma tolerance — two different XLA graphs
contract fused multiply-adds differently — plus reduction-order drift
on the N-way rate-weighted mix.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .kernel import LANES, _pick_block_rows, _smem


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def flat_send_view_ref(theta, slab, w, c, u2=None, eps: float = 1e-8):
    """The jnp oracle — the tree path's expression on flat rows."""
    wsum = jnp.tensordot(w, slab, axes=1)
    if u2 is not None:
        return theta - (c * wsum) / (jnp.sqrt(u2) + eps)
    return (-c) * wsum + theta


def _make_kernel(adaptive: bool, eps: float, n: int):
    def kernel(*refs):
        it = iter(refs)
        scal_ref, theta_ref, slab_ref = next(it), next(it), next(it)
        u2_ref = next(it) if adaptive else None
        out_ref = next(it)
        c = scal_ref[0]                               # SMEM: c, w_0..w_N-1
        wsum = scal_ref[1] * slab_ref[0]
        for m in range(1, n):
            wsum = wsum + scal_ref[1 + m] * slab_ref[m]
        if adaptive:
            out_ref[...] = theta_ref[...] \
                - (c * wsum) / (jnp.sqrt(u2_ref[...]) + eps)
        else:
            out_ref[...] = (-c) * wsum + theta_ref[...]
    return kernel


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def _send_view_pallas(theta, slab, w, c, u2, *, eps: float,
                      interpret: bool):
    r, lanes = theta.shape
    n = slab.shape[0]
    assert lanes == LANES, lanes
    block_r = _pick_block_rows(r, n)
    grid = (r // block_r,)
    scal = jnp.concatenate([jnp.reshape(c, (1,)), w])    # (1 + N,) SMEM

    flat_spec = pl.BlockSpec((block_r, LANES), lambda ri: (ri, 0))
    in_specs = [_smem(),
                flat_spec,
                pl.BlockSpec((n, block_r, LANES), lambda ri: (0, ri, 0))]
    inputs = [scal, theta, slab]
    adaptive = u2 is not None
    if adaptive:
        in_specs.append(flat_spec)
        inputs.append(u2)
    return pl.pallas_call(
        _make_kernel(adaptive, eps, n),
        grid=grid,
        in_specs=in_specs,
        out_specs=flat_spec,
        out_shape=jax.ShapeDtypeStruct((r, LANES), jnp.float32),
        interpret=interpret,
    )(*inputs)


def flat_send_view(theta, slab, w, c, u2=None, *, eps: float = 1e-8,
                   use_pallas: bool | None = None):
    """view = theta - c * sum_j w[j]*slab[j] [/ (sqrt(u2)+eps)].

    theta (R, 128); slab (N, R, 128); w (N,); c scalar.  Pallas on TPU
    (interpret mode when forced elsewhere), jnp reference otherwise.
    """
    if use_pallas is None:
        use_pallas = _on_tpu()
    if use_pallas:
        return _send_view_pallas(theta, slab, jnp.asarray(w, jnp.float32),
                                 jnp.asarray(c, jnp.float32), u2, eps=eps,
                                 interpret=not _on_tpu())
    return flat_send_view_ref(theta, slab, w, c, u2=u2, eps=eps)
