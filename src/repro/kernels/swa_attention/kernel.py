"""Pallas TPU kernel: causal sliding-window flash attention.

Grid (B*H, S/qb): one (qb, hd) query tile per step.  The kv band covering
[q_start - window, q_end] is visited with a fori_loop of
window//kb + ceil(qb/kb) + 1 dynamic (kb, hd) loads from the full K/V rows
held per (batch, head) — the flash running-softmax (m, l, acc) lives in
registers/VMEM.  Only band blocks are read: the kernel does O(S * window)
work instead of O(S^2) — this is the structural win over a dense-masked
MXU attention for the 32k prefill shapes.

MXU alignment: qb and kb are multiples of 128 (scores tile (qb, kb)), and
hd is the natural 128/256 head dim of the assigned configs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, window, q_block, kv_block,
            seq_len):
    qi = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32)                  # (qb, hd)
    hd = q.shape[-1]
    q = q * (1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32)))
    qpos = qi * q_block + jax.lax.broadcasted_iota(jnp.int32,
                                                   (q_block, 1), 0)

    n_band = window // kv_block + (q_block + kv_block - 1) // kv_block + 1
    first = jnp.maximum(qi * q_block // kv_block - (n_band - 1), 0)
    last = qi * q_block // kv_block                      # causal upper block

    m0 = jnp.full((q_block, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((q_block, 1), jnp.float32)
    a0 = jnp.zeros((q_block, hd), jnp.float32)

    def body(j, carry):
        m, l, acc = carry
        kj = first + j
        valid_block = kj <= last

        def visit(carry):
            m, l, acc = carry
            k = k_ref[pl.ds(kj * kv_block, kv_block), :].astype(jnp.float32)
            v = v_ref[pl.ds(kj * kv_block, kv_block), :].astype(jnp.float32)
            kpos = kj * kv_block + jax.lax.broadcasted_iota(
                jnp.int32, (1, kv_block), 1)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)      # (qb, kb)
            mask = (kpos <= qpos) & (qpos - kpos < window)
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=1, keepdims=True)
            acc_new = acc * corr + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return m_new, l_new, acc_new

        return jax.lax.cond(valid_block, visit, lambda c: c, (m, l, acc))

    m, l, acc = jax.lax.fori_loop(0, n_band, body, (m0, l0, a0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("window", "q_block", "kv_block",
                                    "interpret"))
def swa_attention_pallas(q, k, v, *, window, q_block=128, kv_block=128,
                         interpret=False):
    """q,k,v: (B, S, H, hd), same H (GQA pre-expanded by ops.py)."""
    b, s, h, hd = q.shape
    q_block = min(q_block, s)
    kv_block = min(kv_block, s)
    assert s % q_block == 0 and s % kv_block == 0

    # (B,S,H,hd) -> (B*H, S, hd)
    def fold(x):
        return jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, s, hd)

    qf, kf, vf = fold(q), fold(k), fold(v)
    grid = (b * h, s // q_block)
    q_spec = pl.BlockSpec((1, q_block, hd), lambda bh, qi: (bh, qi, 0))
    kv_spec = pl.BlockSpec((1, s, hd), lambda bh, qi: (bh, 0, 0))

    def kern(q_ref, k_ref, v_ref, o_ref):
        _kernel(q_ref.at[0], k_ref.at[0], v_ref.at[0], o_ref.at[0],
                window=window, q_block=q_block, kv_block=kv_block,
                seq_len=s)

    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, s, hd), q.dtype),
        interpret=interpret,
    )(qf, kf, vf)
    return jnp.transpose(out.reshape(b, h, s, hd), (0, 2, 1, 3))
