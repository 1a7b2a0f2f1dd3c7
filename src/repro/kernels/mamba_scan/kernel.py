"""Pallas TPU kernel: Mamba-1 selective scan (falcon-mamba).

Grid (B, D/dt, S/sc): the SSM state tile (dt, N) persists in a VMEM
scratch across the (sequential, minor) sequence-chunk dimension.  Per
timestep the kernel forms abar = exp(delta_t * A) on the (dt, N) tile,
updates the state, and contracts against C_t — a (dt,N)x(N,) reduction on
the VPU.  Channel tiles are lane-aligned; N (the SSM state, 16) rides in
the sublane dimension of the scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128


def _kernel(x_ref, d_ref, b_ref, c_ref, a_ref, h0_ref, y_ref, last_ref):
    s_idx = pl.program_id(2)

    @pl.when(s_idx == 0)
    def _init():
        last_ref[...] = h0_ref[...]

    sc = x_ref.shape[0]
    a = a_ref[...]                                   # (dt, N)
    h = last_ref[...]                                # (dt, N)

    def body(t, h):
        d_t = d_ref[t, :]                            # (dt,)
        x_t = x_ref[t, :]
        b_t = b_ref[t, :]                            # (N,)
        c_t = c_ref[t, :]
        abar = jnp.exp(d_t[:, None] * a)             # (dt, N)
        h = abar * h + (d_t * x_t)[:, None] * b_t[None, :]
        y_ref[t, :] = jnp.sum(h * c_t[None, :], axis=1)
        return h

    h = jax.lax.fori_loop(0, sc, body, h)
    last_ref[...] = h


@functools.partial(jax.jit,
                   static_argnames=("seq_chunk", "chan_tile", "interpret"))
def mamba_scan_pallas(x, delta, b, c, a, h0, *, seq_chunk=64,
                      chan_tile=LANES, interpret=False):
    bsz, s, d = x.shape
    n = a.shape[1]
    seq_chunk = min(seq_chunk, s)
    chan_tile = min(chan_tile, d)
    assert s % seq_chunk == 0 and d % chan_tile == 0, (s, d)
    grid = (bsz, d // chan_tile, s // seq_chunk)

    xd_spec = pl.BlockSpec((1, seq_chunk, chan_tile),
                           lambda bi, di, si: (bi, si, di))
    bc_spec = pl.BlockSpec((1, seq_chunk, n), lambda bi, di, si: (bi, si, 0))
    a_spec = pl.BlockSpec((chan_tile, n), lambda bi, di, si: (di, 0))
    h_spec = pl.BlockSpec((1, chan_tile, n), lambda bi, di, si: (bi, di, 0))

    def kern(x_ref, d_ref, b_ref, c_ref, a_ref, h0_ref, y_ref, last_ref):
        _kernel(x_ref.at[0], d_ref.at[0], b_ref.at[0], c_ref.at[0],
                a_ref, h0_ref.at[0], y_ref.at[0], last_ref.at[0])

    y, last = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[xd_spec, xd_spec, bc_spec, bc_spec, a_spec, h_spec],
        out_specs=[xd_spec, h_spec],
        out_shape=[jax.ShapeDtypeStruct((bsz, s, d), x.dtype),
                   jax.ShapeDtypeStruct((bsz, d, n), x.dtype)],
        interpret=interpret,
    )(x, delta, b, c, a, h0)
    return y, last
