"""Batched serving driver: prefill a batch of prompts, decode greedily.

Uses the same prefill/decode steps the dry-run lowers for the production
mesh, on a host mesh here.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
      --batch 4 --prompt-len 32 --gen 16
"""
import os
import sys

if "--devices" in sys.argv:                      # before any jax import
    _n = sys.argv[sys.argv.index("--devices") + 1]
    os.environ["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={_n} "
                               + os.environ.get("XLA_FLAGS", ""))

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config
from ..models.api import build_model
from ..models.attention import CacheSpec
from .cache import enable_compile_cache
from .mesh import make_host_mesh
from .steps import build_decode_step, build_prefill_step


def generate(model, params, prompts, gen_len: int, mesh,
             window: int | None = None):
    """Greedy batched generation; returns (tokens (B, gen), stats)."""
    cfg = model.cfg
    b, s = prompts.shape
    capacity = s + gen_len if window is None else min(window, s + gen_len)
    spec = CacheSpec(capacity=capacity, window=window)

    @jax.jit
    def prefill_fn(params, batch):
        return model.prefill(params, batch, spec)

    @jax.jit
    def decode_fn(params, tok, cache):
        return model.decode_step(params, tok, cache, spec)

    batch = {"tokens": prompts}
    if cfg.modality == "vision":
        batch["embeds"] = jnp.zeros((b, cfg.modality_tokens, cfg.d_model),
                                    jnp.bfloat16)
    if cfg.rope == "mrope":
        total = s + (cfg.modality_tokens if cfg.modality == "vision" else 0)
        pos = jnp.broadcast_to(jnp.arange(total)[None, None],
                               (3, b, total)).astype(jnp.int32)
        batch["positions"] = pos
    if cfg.is_encdec:
        enc = min(cfg.max_encoder_len, s)
        batch["enc_embeds"] = jnp.zeros((b, enc, cfg.d_model), jnp.bfloat16)

    t0 = time.time()
    logits, cache = prefill_fn(params, batch)
    jax.block_until_ready(logits)
    t_prefill = time.time() - t0

    tok = jnp.argmax(logits[:, -1, :], -1).astype(jnp.int32)[:, None]
    out = [tok]
    t0 = time.time()
    for _ in range(gen_len - 1):
        logits, cache = decode_fn(params, tok, cache)
        tok = jnp.argmax(logits[:, -1, :], -1).astype(jnp.int32)[:, None]
        out.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.time() - t0
    toks = jnp.concatenate(out, axis=1)
    return toks, {
        "prefill_s": t_prefill,
        "decode_s": t_decode,
        "prefill_tok_per_s": b * s / max(t_prefill, 1e-9),
        "decode_tok_per_s": b * max(gen_len - 1, 1) / max(t_decode, 1e-9),
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--reduced", action="store_true", default=False)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--window", type=int, default=None)
    ap.add_argument("--devices", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    mesh = make_host_mesh()
    params = model.init(jax.random.PRNGKey(args.seed))
    params = jax.tree.map(
        lambda l: l.astype(jnp.bfloat16)
        if l.dtype == jnp.float32 else l, params)

    rng = np.random.default_rng(args.seed)
    prompts = jnp.asarray(rng.integers(
        0, cfg.vocab_size, size=(args.batch, args.prompt_len)), jnp.int32)

    with mesh:
        toks, stats = generate(model, params, prompts, args.gen, mesh,
                               window=args.window)
    print(f"arch={cfg.name} batch={args.batch} prompt={args.prompt_len} "
          f"gen={args.gen}")
    for k, v in stats.items():
        print(f"  {k}: {v:.3f}")
    print("first sequences:", np.asarray(toks[:2]).tolist())
    return stats


if __name__ == "__main__":
    main()
