"""Kernel micro-benchmarks. Off a TPU the kernels run in the Pallas
interpreter and the numbers are correctness-path timings, NOT TPU
performance; on a TPU backend they are compiled (the backend decides)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref


def _time(fn, *args, iters=3, **kw):
    fn(*args, **kw)  # compile/warm
    t0 = time.time()
    for _ in range(iters):
        out = fn(*args, **kw)
    jax.block_until_ready(out)
    return (time.time() - t0) / iters * 1e6


def main(report):
    rng = np.random.default_rng(0)
    # olaf_combine: 8 slots x 16-update burst x 64k gradient
    Q, U, D = 8, 16, 65536
    slots = jnp.asarray(rng.normal(size=(Q, D)), jnp.float32)
    counts = jnp.ones((Q,), jnp.int32)
    updates = jnp.asarray(rng.normal(size=(U, D)), jnp.float32)
    clusters = jnp.asarray(rng.integers(0, Q, (U,)), jnp.int32)
    gate = jnp.ones((U,), jnp.int32)
    us = _time(ops.olaf_combine, slots, counts, updates, clusters, gate)
    bytes_touched = (U * D + 2 * Q * D) * 4
    report("olaf_combine_8x16x64k", us,
           f"{bytes_touched/2**20:.0f} MiB touched; HBM-bound target "
           f"{bytes_touched/819e9*1e6:.1f} us on v5e")

    # large-burst combine: U=256 with no per-update unroll (MXU segment-sum)
    U2 = 256
    updates2 = jnp.asarray(rng.normal(size=(U2, D)), jnp.float32)
    clusters2 = jnp.asarray(rng.integers(0, Q, (U2,)), jnp.int32)
    gate2 = jnp.ones((U2,), jnp.int32)
    us = _time(ops.olaf_combine, slots, counts, updates2, clusters2, gate2)
    bytes_touched = (U2 * D + 2 * Q * D) * 4
    report("olaf_combine_8x256x64k", us,
           f"{bytes_touched/2**20:.0f} MiB touched; HBM-bound target "
           f"{bytes_touched/819e9*1e6:.1f} us on v5e")

    # multi-queue combine: 3 switches (SW1/SW2/SW3) in one kernel launch
    S = 3
    mslots = jnp.asarray(rng.normal(size=(S, Q, D)), jnp.float32)
    mcounts = jnp.ones((S, Q), jnp.int32)
    mupdates = jnp.asarray(rng.normal(size=(S, U, D)), jnp.float32)
    mclusters = jnp.asarray(rng.integers(0, Q, (S, U)), jnp.int32)
    mgate = jnp.ones((S, U), jnp.int32)
    us = _time(ops.olaf_combine_multi, mslots, mcounts, mupdates, mclusters,
               mgate)
    bytes_touched = S * (U * D + 2 * Q * D) * 4
    report("olaf_combine_multi_3x8x16x64k", us,
           f"{bytes_touched/2**20:.0f} MiB touched; HBM-bound target "
           f"{bytes_touched/819e9*1e6:.1f} us on v5e")

    # flash attention 1k x 64
    q = jnp.asarray(rng.normal(size=(4, 1024, 64)), jnp.bfloat16)
    from repro.kernels.flash_attention import flash_attention_pallas
    us = _time(flash_attention_pallas, q, q, q, causal=True, block_q=256,
               block_k=256, interpret=jax.default_backend() != "tpu")
    flops = 4 * 1024 * 1024 * 64 * 2 * 2 / 2  # causal half
    report("flash_attn_4x1k_d64", us,
           f"{flops/1e9:.1f} GFLOP; MXU target {flops/197e12*1e6:.1f} us on v5e")

    # decode attention vs 32k cache
    B, S, KV, rep, Dh = 2, 32768, 2, 4, 128
    qd = jnp.asarray(rng.normal(size=(B, KV, rep, Dh)), jnp.bfloat16)
    kc = jnp.asarray(rng.normal(size=(B, S, KV, Dh)), jnp.bfloat16)
    pos = jnp.full((B,), S - 1, jnp.int32)
    us = _time(ops.decode_attention, qd, kc, kc, pos, block_s=2048)
    cache_bytes = 2 * B * S * KV * Dh * 2
    report("decode_attn_32k_cache", us,
           f"{cache_bytes/2**20:.0f} MiB cache/step; HBM-bound target "
           f"{cache_bytes/819e9*1e6:.1f} us on v5e")
    return {}
