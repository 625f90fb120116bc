"""Batched serving driver: prefill + decode loop with KV caches.

Runs the same ``prefill``/``serve_step`` the dry-run lowers. On CPU use
``--reduced``; on a TPU mesh the full configs apply with the sharding rules
from ``repro.distributed.sharding`` (decode caches sequence-sharded over the
model axis for long contexts).

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --reduced \
      --batch 4 --prompt-len 16 --gen 32
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import api


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    rng = np.random.default_rng(args.seed)
    B, P = args.batch, args.prompt_len
    params = api.init_model(jax.random.key(args.seed), cfg)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (B, P)), jnp.int32)}
    if cfg.family == "vlm":
        batch["patches"] = jnp.asarray(
            rng.normal(size=(B, cfg.n_patches, cfg.d_model)), jnp.float32)
    if cfg.family == "encdec":
        batch["frames"] = jnp.asarray(
            rng.normal(size=(B, cfg.enc_frames, cfg.d_model)), jnp.float32)

    offset = cfg.n_patches if cfg.family == "vlm" else 0
    total = offset + P + args.gen + 8

    t0 = time.time()
    logits, caches = jax.jit(
        lambda p, b: api.prefill(p, b, cfg))(params, batch)
    logits.block_until_ready()
    t_prefill = time.time() - t0

    # grow caches to decode length
    full = api.make_caches(cfg, B, total)

    def copy_prefix(z, c):
        if z.shape == c.shape:
            return c
        axis = [i for i, (a, b) in enumerate(zip(z.shape, c.shape)) if a != b][0]
        pad = [(0, z.shape[i] - c.shape[i]) if i == axis else (0, 0)
               for i in range(z.ndim)]
        return jnp.pad(c, pad)

    caches = jax.tree.map(copy_prefix, full, caches)

    step_fn = jax.jit(lambda p, c, t, pos: api.decode_step(
        p, c, {"token": t, "pos": pos}, cfg))
    key = jax.random.key(args.seed)
    token = jnp.argmax(logits[:, -1, :cfg.vocab], axis=-1).astype(jnp.int32)
    out_tokens = [np.asarray(token)]
    t0 = time.time()
    for i in range(args.gen):
        pos = jnp.full((B,), offset + P + i, jnp.int32)
        logits_t, caches = step_fn(params, caches, token, pos)
        key, sub = jax.random.split(key)
        if args.temperature > 0:
            token = jax.random.categorical(
                sub, logits_t[:, :cfg.vocab] / args.temperature).astype(jnp.int32)
        else:
            token = jnp.argmax(logits_t[:, :cfg.vocab], -1).astype(jnp.int32)
        out_tokens.append(np.asarray(token))
    jax.block_until_ready(token)
    t_decode = time.time() - t0
    toks = np.stack(out_tokens, 1)
    print(f"prefill: {t_prefill*1e3:.1f} ms for {B}x{P}; "
          f"decode: {t_decode/args.gen*1e3:.2f} ms/token "
          f"({B*args.gen/t_decode:.1f} tok/s)")
    print("sample tokens[0]:", toks[0][:16].tolist())


if __name__ == "__main__":
    main()
