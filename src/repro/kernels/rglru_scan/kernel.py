"""Pallas TPU kernel: RG-LRU linear recurrence (RecurrentGemma).

Grid (B, D/dt, S/sc) — TPU grids iterate row-major and sequentially, so
for a fixed (batch, channel-tile) the sequence chunks arrive in order and
the running state lives in a VMEM scratch tile that persists across the
minor grid dimension.  Inside a chunk, a fori_loop runs the recurrence
h <- a*h + x one timestep at a time on (1, dt) VPU rows; the channel tile
dt is lane-aligned (multiple of 128).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128


def _kernel(a_ref, x_ref, h0_ref, out_ref, last_ref, *, seq_chunks):
    s_idx = pl.program_id(2)

    @pl.when(s_idx == 0)
    def _init():
        last_ref[...] = h0_ref[...]

    sc = a_ref.shape[0]
    h = last_ref[...]                             # (1, dt)

    def body(t, h):
        h = a_ref[t, :][None, :] * h + x_ref[t, :][None, :]
        out_ref[t, :] = h[0, :]
        return h

    h = jax.lax.fori_loop(0, sc, body, h)
    last_ref[...] = h


@functools.partial(jax.jit,
                   static_argnames=("seq_chunk", "chan_tile", "interpret"))
def rglru_scan_pallas(a, x, h0, *, seq_chunk=128, chan_tile=LANES,
                      interpret=False):
    """a, x: (B, S, D); h0: (B, D) -> (h_all, h_last)."""
    b, s, d = a.shape
    seq_chunk = min(seq_chunk, s)
    chan_tile = min(chan_tile, d)
    assert s % seq_chunk == 0 and d % chan_tile == 0, (s, d)
    grid = (b, d // chan_tile, s // seq_chunk)
    seq_chunks = s // seq_chunk

    tile = pl.BlockSpec((1, seq_chunk, chan_tile),
                        lambda bi, di, si: (bi, si, di))
    h0_spec = pl.BlockSpec((1, chan_tile), lambda bi, di, si: (bi, di))

    def kern(a_ref, x_ref, h0_ref, out_ref, last_ref):
        _kernel(a_ref.at[0], x_ref.at[0], h0_ref, out_ref.at[0],
                last_ref, seq_chunks=seq_chunks)

    out, last = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[tile, tile, h0_spec],
        out_specs=[tile, h0_spec],
        out_shape=[jax.ShapeDtypeStruct((b, s, d), a.dtype),
                   jax.ShapeDtypeStruct((b, d), a.dtype)],
        interpret=interpret,
    )(a, x, h0)
    return out, last
