"""Recurrent sequence mixers: Mamba-1 selective SSM and RG-LRU (Griffin /
RecurrentGemma), with chunked scans for training and O(1)-state decode.

TPU adaptation (DESIGN.md Sec. 6): the recurrences are evaluated in
sequence chunks — within a chunk the scan is unrolled into dense tensor ops
that feed the VPU/MXU; across chunks a small carried state crosses
``lax.scan`` iterations.  The Pallas kernels in ``repro.kernels`` implement
the same chunking with explicit VMEM tiling; these jnp versions are the
oracles and the CPU path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import (clean_pspec, current_mesh, dense_init,
                     kernels_enabled, with_logical_constraint)


def _pallas_interpret(interp):
    return (jax.default_backend() != "tpu") if interp is None else interp


def _shard_mapped(fn, args, arg_axes, out_axes):
    """Run a Pallas kernel per-shard under the current mesh (the kernel
    body cannot be GSPMD-partitioned); single-device: call directly."""
    mesh = current_mesh()
    if mesh is None:
        return fn(*args)
    in_specs = tuple(clean_pspec(a, *ax) for a, ax in zip(args, arg_axes))
    out_specs = tuple(out_axes)
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(*args)


# ---------------------------------------------------------------------------
# causal depthwise conv1d (width w), used by both mixers
# ---------------------------------------------------------------------------
def causal_conv1d(x, w, b=None, state=None):
    """x: (B,S,D); w: (W,D) depthwise taps; state: (B,W-1,D) trailing
    context from the previous chunk (None = zeros: sequence start).
    Returns (y, new_state)."""
    bsz, s, d = x.shape
    width = w.shape[0]
    if state is None:
        state = jnp.zeros((bsz, width - 1, d), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)          # (B, S+W-1, D)
    y = jnp.zeros_like(x)
    for i in range(width):
        y = y + xp[:, i:i + s, :] * w[i].astype(x.dtype)
    if b is not None:
        y = y + b.astype(x.dtype)
    new_state = xp[:, -(width - 1):, :] if width > 1 else state
    return y, new_state


# ---------------------------------------------------------------------------
# Mamba-1 (falcon-mamba): selective SSM
# ---------------------------------------------------------------------------
def init_mamba(key, d_model, d_inner, d_state, conv_width=4, dt_rank=None):
    dt_rank = dt_rank or max(1, d_model // 16)
    ks = jax.random.split(key, 6)
    return {
        "in_proj": dense_init(ks[0], (d_model, 2 * d_inner)),
        "conv_w": dense_init(ks[1], (conv_width, d_inner), in_axes=(0,)),
        "conv_b": jnp.zeros((d_inner,)),
        "x_proj": dense_init(ks[2], (d_inner, dt_rank + 2 * d_state)),
        "dt_proj": dense_init(ks[3], (dt_rank, d_inner)),
        "dt_bias": jnp.log(jnp.expm1(
            jnp.clip(jax.random.uniform(ks[4], (d_inner,)) * 0.1, 1e-3))),
        "A_log": jnp.log(jnp.broadcast_to(
            jnp.arange(1, d_state + 1, dtype=jnp.float32),
            (d_inner, d_state)).copy()),
        "D": jnp.ones((d_inner,)),
        "out_proj": dense_init(ks[5], (d_inner, d_model)),
    }


def _mamba_scan_chunk(a, bx, h0):
    """Linear recurrence h_t = a_t * h_{t-1} + bx_t within one chunk via an
    associative scan.  a, bx: (B, L, D, N); h0: (B, D, N)."""
    def comb(c1, c2):
        a1, x1 = c1
        a2, x2 = c2
        return a1 * a2, x2 + a2 * x1
    a_s, x_s = jax.lax.associative_scan(comb, (a, bx), axis=1)
    h = x_s + a_s * h0[:, None]
    return h, h[:, -1]


def apply_mamba(params, x, state=None, chunk=128):
    """x: (B,S,d_model).  state: dict(conv, ssm) or None.  Returns
    (y, new_state)."""
    dt_ = x.dtype
    bsz, s, _ = x.shape
    d_inner = params["dt_proj"].shape[1]
    n = params["A_log"].shape[1]
    xz = jnp.einsum("bsd,de->bse", x, params["in_proj"].astype(dt_))
    xin, z = jnp.split(xz, 2, axis=-1)
    xin = with_logical_constraint(xin, "batch", None, "d_inner")
    conv_state = None if state is None else state["conv"]
    xc, conv_state = causal_conv1d(xin, params["conv_w"], params["conv_b"],
                                   conv_state)
    xc = jax.nn.silu(xc)

    dt_rank = params["dt_proj"].shape[0]
    proj = jnp.einsum("bsd,dr->bsr", xc, params["x_proj"].astype(dt_))
    dt_raw, b_, c_ = jnp.split(proj, [dt_rank, dt_rank + n], axis=-1)
    delta = jax.nn.softplus(
        jnp.einsum("bsr,rd->bsd", dt_raw, params["dt_proj"].astype(dt_))
        .astype(jnp.float32) + params["dt_bias"])              # (B,S,Di)
    a_mat = -jnp.exp(params["A_log"])                          # (Di,N)

    ssm0 = (jnp.zeros((bsz, d_inner, n), jnp.float32) if state is None
            else state["ssm"])

    use_kernel, interp = kernels_enabled()
    if use_kernel:
        # Pallas selective-scan kernel (serve path), per-shard under the
        # mesh: batch over data axes, d_inner over "model" — the
        # recurrence is elementwise across channels.
        from ..kernels.mamba_scan.ops import mamba_scan

        def run(xk, dk, bk, ck, ak, hk):
            return mamba_scan(xk, dk, bk, ck, ak, hk, use_pallas=True,
                              interpret=_pallas_interpret(interp))

        y_f, ssm_last = _shard_mapped(
            run,
            (xc.astype(jnp.float32), delta,
             b_.astype(jnp.float32), c_.astype(jnp.float32), a_mat, ssm0),
            (("batch", None, "d_inner"), ("batch", None, "d_inner"),
             ("batch", None, None), ("batch", None, None),
             ("d_inner", None), ("batch", "d_inner", None)),
            (clean_pspec(xc, "batch", None, "d_inner"),
             clean_pspec(ssm0, "batch", "d_inner", None)))
        y = y_f.reshape(bsz, s, d_inner).astype(dt_)
    else:
        s_chunks = max(s // chunk, 1)
        chunk = s // s_chunks
        xs = xc.reshape(bsz, s_chunks, chunk, d_inner)
        ds = delta.reshape(bsz, s_chunks, chunk, d_inner)
        bs = b_.reshape(bsz, s_chunks, chunk, n).astype(jnp.float32)
        cs = c_.reshape(bsz, s_chunks, chunk, n).astype(jnp.float32)

        def body(h, inp):
            xcb, db, bb, cb = inp                             # per chunk
            a = jnp.exp(db[..., None] * a_mat)                # (B,L,Di,N)
            bx = (db * xcb.astype(jnp.float32))[..., None] \
                * bb[:, :, None, :]
            h_all, h_last = _mamba_scan_chunk(a, bx, h)
            y = jnp.einsum("bldn,bln->bld", h_all, cb)
            return h_last, y

        ssm_last, ys = jax.lax.scan(
            body, ssm0,
            (jnp.moveaxis(xs, 1, 0), jnp.moveaxis(ds, 1, 0),
             jnp.moveaxis(bs, 1, 0), jnp.moveaxis(cs, 1, 0)))
        y = jnp.moveaxis(ys, 0, 1).reshape(bsz, s, d_inner).astype(dt_)
    y = y + xc * params["D"].astype(dt_)
    y = y * jax.nn.silu(z)
    out = jnp.einsum("bse,ed->bsd", y, params["out_proj"].astype(dt_))
    new_state = {"conv": conv_state, "ssm": ssm_last}
    return out, new_state


def init_mamba_state(batch, d_inner, d_state, conv_width, dtype=jnp.bfloat16):
    return {
        "conv": jnp.zeros((batch, conv_width - 1, d_inner), dtype),
        "ssm": jnp.zeros((batch, d_inner, d_state), jnp.float32),
    }


# ---------------------------------------------------------------------------
# RG-LRU (RecurrentGemma / Griffin) recurrent block
# ---------------------------------------------------------------------------
def init_rglru(key, d_model, d_inner, num_heads, conv_width=4):
    """Griffin recurrent block: x-branch (conv1d -> RG-LRU), gate branch
    (GeLU), merged and projected out.  Gates are block-diagonal with
    ``num_heads`` blocks as in the paper."""
    ks = jax.random.split(key, 6)
    bd = d_inner // num_heads
    c = 8.0
    return {
        "in_x": dense_init(ks[0], (d_model, d_inner)),
        "in_gate": dense_init(ks[1], (d_model, d_inner)),
        "conv_w": dense_init(ks[2], (conv_width, d_inner), in_axes=(0,)),
        "conv_b": jnp.zeros((d_inner,)),
        # block-diagonal recurrence/input gates: (H, bd, bd)
        "w_a": dense_init(ks[3], (num_heads, bd, bd), in_axes=(1,)),
        "b_a": jnp.zeros((num_heads, bd)),
        "w_i": dense_init(ks[4], (num_heads, bd, bd), in_axes=(1,)),
        "b_i": jnp.zeros((num_heads, bd)),
        # Lambda parameter: a = sigmoid(lam)^(c*r); init near 0.9..0.999
        "lam": jnp.log(jnp.exp(jnp.linspace(2.0, 6.0, d_inner)) - 1.0),
        "out": dense_init(ks[5], (d_inner, d_model)),
    }


def _rglru_scan_chunk(a, gx, h0):
    def comb(c1, c2):
        a1, x1 = c1
        a2, x2 = c2
        return a1 * a2, x2 + a2 * x1
    a_s, x_s = jax.lax.associative_scan(comb, (a, gx), axis=1)
    h = x_s + a_s * h0[:, None]
    return h, h[:, -1]


def apply_rglru(params, x, state=None, chunk=128, c_const=8.0):
    """x: (B,S,d_model); state: dict(conv, h) or None -> (y, new_state)."""
    dt_ = x.dtype
    bsz, s, _ = x.shape
    d_inner = params["in_x"].shape[1]
    nh, bd, _ = params["w_a"].shape

    gate = jax.nn.gelu(
        jnp.einsum("bsd,de->bse", x, params["in_gate"].astype(dt_)))
    xin = jnp.einsum("bsd,de->bse", x, params["in_x"].astype(dt_))
    xin = with_logical_constraint(xin, "batch", None, "d_inner")
    conv_state = None if state is None else state["conv"]
    xc, conv_state = causal_conv1d(xin, params["conv_w"], params["conv_b"],
                                   conv_state)

    xh = xc.reshape(bsz, s, nh, bd)
    r = jax.nn.sigmoid(jnp.einsum("bshd,hde->bshe", xh,
                                  params["w_a"].astype(dt_))
                       + params["b_a"].astype(dt_)).astype(jnp.float32)
    i = jax.nn.sigmoid(jnp.einsum("bshd,hde->bshe", xh,
                                  params["w_i"].astype(dt_))
                       + params["b_i"].astype(dt_)).astype(jnp.float32)
    r = r.reshape(bsz, s, d_inner)
    i = i.reshape(bsz, s, d_inner)
    log_a_base = jax.nn.log_sigmoid(params["lam"])             # (Di,) < 0
    log_a = c_const * r * log_a_base                           # (B,S,Di)
    a = jnp.exp(log_a)
    beta = jnp.sqrt(jnp.clip(1.0 - jnp.exp(2.0 * log_a), 1e-12))
    gx = beta * i * xc.astype(jnp.float32)

    h0 = (jnp.zeros((bsz, d_inner), jnp.float32) if state is None
          else state["h"])
    use_kernel, interp = kernels_enabled()
    if use_kernel:
        # Pallas RG-LRU scan kernel (serve path), per-shard on the mesh
        from ..kernels.rglru_scan.ops import rglru_scan

        def run(ak, xk, hk):
            return rglru_scan(ak, xk, hk, use_pallas=True,
                              interpret=_pallas_interpret(interp))

        h_all, h_last = _shard_mapped(
            run, (a, gx, h0),
            (("batch", None, "d_inner"), ("batch", None, "d_inner"),
             ("batch", "d_inner")),
            (clean_pspec(a, "batch", None, "d_inner"),
             clean_pspec(h0, "batch", "d_inner")))
        h = h_all.astype(dt_)
    else:
        s_chunks = max(s // chunk, 1)
        chunk = s // s_chunks

        def body(h, inp):
            ab, gxb = inp
            h_all, h_last = _rglru_scan_chunk(ab, gxb, h)
            return h_last, h_all

        a_c = jnp.moveaxis(a.reshape(bsz, s_chunks, chunk, d_inner), 1, 0)
        g_c = jnp.moveaxis(gx.reshape(bsz, s_chunks, chunk, d_inner), 1, 0)
        h_last, hs = jax.lax.scan(body, h0, (a_c, g_c))
        h = jnp.moveaxis(hs, 0, 1).reshape(bsz, s, d_inner).astype(dt_)
    y = h * gate
    out = jnp.einsum("bse,ed->bsd", y, params["out"].astype(dt_))
    return out, {"conv": conv_state, "h": h_last}


def init_rglru_state(batch, d_inner, conv_width, dtype=jnp.bfloat16):
    return {
        "conv": jnp.zeros((batch, conv_width - 1, d_inner), dtype),
        "h": jnp.zeros((batch, d_inner), jnp.float32),
    }
