"""Pallas TPU kernel: fused DANA-Zero master round.

The parameter-server hot loop (paper Sec. C.1: "above 20 workers, the
master becomes a bottleneck") is a pure HBM-bandwidth problem: per worker
message the master touches theta, v_i, v0 and produces four outputs.  XLA
un-fused this is ~10 HBM round trips; fused it is 4 reads + 4 writes.

Tiling: parameters are viewed as (R, 128) rows; each grid step processes a
(BLOCK_ROWS, 128) VMEM tile of all four streams.  Elementwise VPU work,
lane dimension 128-aligned.  Scalars (lr, gamma) ride in as (1, 1) tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_ROWS = 256
LANES = 128


def _kernel(scal_ref, theta_ref, vi_ref, v0_ref, g_ref,
            theta_out, vi_out, v0_out, hat_out):
    lr = scal_ref[0, 0]
    gamma = scal_ref[0, 1]
    theta = theta_ref[...]
    vi = vi_ref[...]
    v0 = v0_ref[...]
    g = g_ref[...]
    v_new = gamma * vi + g
    v0_new = v0 - vi + v_new
    theta_new = theta - lr * v_new
    vi_out[...] = v_new
    v0_out[...] = v0_new
    theta_out[...] = theta_new
    hat_out[...] = theta_new - lr * gamma * v0_new


@functools.partial(jax.jit, static_argnames=("interpret",))
def dana_master_update_2d(theta, v_i, v0, g, lr, gamma, *, interpret=False):
    """theta/v_i/v0/g: (R, 128) float arrays; lr/gamma scalars."""
    r, lanes = theta.shape
    # NOTE: these used to be one chained assert whose `and`/`or` precedence
    # silently skipped the lane check whenever r <= BLOCK_ROWS.
    assert lanes == LANES, f"lane dim must be {LANES}, got {lanes}"
    assert (r % BLOCK_ROWS == 0) or (r <= BLOCK_ROWS), \
        f"rows must divide {BLOCK_ROWS} or fit one block, got {r}"
    block_r = min(BLOCK_ROWS, r)
    grid = (r // block_r,)
    scal = jnp.stack([jnp.asarray(lr, theta.dtype),
                      jnp.asarray(gamma, theta.dtype)]).reshape(1, 2)
    spec = pl.BlockSpec((block_r, LANES), lambda i: (i, 0))
    out_shape = [jax.ShapeDtypeStruct(theta.shape, theta.dtype)] * 4
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((1, 2), lambda i: (0, 0)),
                  spec, spec, spec, spec],
        out_specs=[spec, spec, spec, spec],
        out_shape=out_shape,
        interpret=interpret,
    )(scal, theta, v_i, v0, g)
