"""Plain reference of a dense decoder-only LM, in the parameter layout the
program trains.

Pre-norm blocks: RMSNorm with a ``1 + scale`` gain, grouped-query
attention with QKV bias and rotary positions on the first ``rotary``
dims of each head (rotate-half pairs), causal softmax over the whole
sequence at once, a SwiGLU MLP, a final RMSNorm and an untied output
projection; the loss is the mean next-token cross entropy.

Every matrix product runs in float32 at ``Precision.HIGHEST``.  With
``prec="fp8"`` both operands of every product are first rounded to
float8 e4m3: the precision one step below the configuration's bfloat16,
used only as the control that a sound comparison must reject.

The layout (and only the layout) mirrors the program's parameter tree:
``{"embed", "final_norm", "lm_head", "prologue": [], "unit": [block]}``,
with every block leaf stacked over the layers.  Nothing is imported from
the program.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Arch:
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    num_layers: int
    rotary_dims: int
    rope_theta: float
    norm_eps: float
    qkv_bias: bool = True

    @classmethod
    def from_config(cls, conf: dict) -> "Arch":
        return cls(**conf["arch"])


def init_params(key, arch: Arch):
    """Seeded weights: N(0, 0.02^2) embeddings, N(0, 1/fan_in) matrices,
    zero norms and biases.  Float32, the master state's type."""
    d, h, kv, hd, f, v, n = (arch.d_model, arch.num_heads,
                             arch.num_kv_heads, arch.head_dim, arch.d_ff,
                             arch.vocab_size, arch.num_layers)
    ks = jax.random.split(key, 9)

    def normal(k, shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) * (fan_in ** -0.5)

    block = {
        "ln1": jnp.zeros((n, d)),
        "attn": {
            "wq": normal(ks[0], (n, d, h, hd), d),
            "wk": normal(ks[1], (n, d, kv, hd), d),
            "wv": normal(ks[2], (n, d, kv, hd), d),
            "wo": normal(ks[3], (n, h, hd, d), h * hd),
        },
        "ln2": jnp.zeros((n, d)),
        "mlp": {
            "w_gate": normal(ks[4], (n, d, f), d),
            "w_up": normal(ks[5], (n, d, f), d),
            "w_down": normal(ks[6], (n, f, d), f),
        },
    }
    if arch.qkv_bias:
        block["attn"].update(bq=jnp.zeros((n, h, hd)),
                             bk=jnp.zeros((n, kv, hd)),
                             bv=jnp.zeros((n, kv, hd)))
    return {
        "embed": jax.random.normal(ks[7], (v, d), jnp.float32) * 0.02,
        "final_norm": jnp.zeros((d,)),
        "lm_head": normal(ks[8], (d, v), d),
        "prologue": [],
        "unit": [block],
    }


def _mm(eq, a, b, prec):
    if prec == "fp8":
        a = a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        b = b.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return jnp.einsum(eq, a, b, precision=HI,
                      preferred_element_type=jnp.float32)


def _rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def _rotary(x, positions, dims, theta):
    """Rotate-half rotary embedding on the first ``dims`` of the head."""
    half = dims // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[:, :, None, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2, rest = x[..., :half], x[..., half:dims], x[..., dims:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def _block(p, x, arch: Arch, prec):
    b, s, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    a = p["attn"]
    h = _rms_norm(x, p["ln1"], arch.norm_eps)
    q = _mm("bsd,dhk->bshk", h, a["wq"], prec)
    k = _mm("bsd,dhk->bshk", h, a["wk"], prec)
    v = _mm("bsd,dhk->bshk", h, a["wv"], prec)
    if arch.qkv_bias:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = _rotary(q, pos, arch.rotary_dims, arch.rope_theta)
    k = _rotary(k, pos, arch.rotary_dims, arch.rope_theta)
    group = arch.num_heads // arch.num_kv_heads
    k = jnp.repeat(k, group, axis=2)          # query head j reads kv j // g
    v = jnp.repeat(v, group, axis=2)
    scores = _mm("bqhk,bthk->bhqt", q, k, prec) / jnp.sqrt(
        jnp.float32(arch.head_dim))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    att = _mm("bhqt,bthk->bqhk", probs, v, prec)
    x = x + _mm("bshk,hkd->bsd", att, a["wo"], prec)
    h = _rms_norm(x, p["ln2"], arch.norm_eps)
    m = p["mlp"]
    gate = _mm("bsd,df->bsf", h, m["w_gate"], prec)
    up = _mm("bsd,df->bsf", h, m["w_up"], prec)
    return x + _mm("bsf,fd->bsd", jax.nn.silu(gate) * up, m["w_down"], prec)


def loss(params, tokens, arch: Arch, prec: str = "f32"):
    """Mean next-token cross entropy of ``tokens`` (B, S)."""
    x = params["embed"][tokens]
    unit = params["unit"][0]
    for layer in range(arch.num_layers):
        x = _block(jax.tree.map(lambda l: l[layer], unit), x, arch, prec)
    x = _rms_norm(x, params["final_norm"], arch.norm_eps)
    logits = _mm("bsd,dv->bsv", x, params["lm_head"], prec)[:, :-1]
    labels = tokens[:, 1:]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)

