"""Pallas TPU kernel: single-token decode attention against a KV cache.

Decode attention is memory-bound: every step streams the whole (S, KV, Dh)
cache from HBM through VMEM once, so the kernel is organized around that
stream — grid = (batch·kv_heads, cache_blocks) with the online-softmax state
(m, l, acc) for the `rep` query heads of this kv group held in VMEM scratch.
The per-block compute is a (rep, Dh) x (Dh, bk) matmul — tiny, by design;
the roofline term that matters is cache bytes / HBM bandwidth.

The current token position arrives as a scalar-prefetch operand so masking
(and early block skipping via ``pl.when``) happens before the DMA is wasted.
GQA is handled natively: q is laid out (B·KV, rep, Dh) so the cache is read
once per kv head, not per query head.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_S = 512
NEG_INF = -1e30


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                   *, block_s: int, n_blocks: int, n_kv: int, scale: float):
    bkv = pl.program_id(0)
    j = pl.program_id(1)
    b = bkv // n_kv
    pos = pos_ref[b]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j * block_s <= pos)  # skip blocks entirely past the position
    def _compute():
        q = q_ref[0, :, :].astype(jnp.float32)  # (rep, Dh)
        k = k_ref[0, :, :].astype(jnp.float32)  # (bs, Dh)
        v = v_ref[0, :, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = j * block_s + jax.lax.broadcasted_iota(
            jnp.int32, (q.shape[0], block_s), 1)
        mask = kpos <= pos
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == n_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, :, :] = (acc_scr[...] / l).astype(o_ref.dtype)


def decode_attention_pallas(q: jnp.ndarray, k_cache: jnp.ndarray,
                            v_cache: jnp.ndarray, pos: jnp.ndarray, *,
                            block_s: int = DEFAULT_BLOCK_S,
                            interpret: bool) -> jnp.ndarray:
    """q: (B, KV, rep, Dh); k/v_cache: (B, S, KV, Dh); pos: (B,) int32.

    Returns (B, KV, rep, Dh). Cache entries at positions > pos are masked.
    """
    B, KV, rep, Dh = q.shape
    S = k_cache.shape[1]
    block_s = min(block_s, S)
    assert S % block_s == 0
    n_blocks = S // block_s
    # fold (B, KV) into the grid's batch dim; cache transposed to expose
    # (B*KV, S, Dh) contiguous streaming
    qf = q.reshape(B * KV, rep, Dh)
    kf = jnp.moveaxis(k_cache, 2, 1).reshape(B * KV, S, Dh)
    vf = jnp.moveaxis(v_cache, 2, 1).reshape(B * KV, S, Dh)

    kernel = functools.partial(_decode_kernel, block_s=block_s,
                               n_blocks=n_blocks, n_kv=KV,
                               scale=1.0 / np.sqrt(Dh))
    out = pl.pallas_call(
        kernel,
        grid=(B * KV, n_blocks),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # pos (scalar reads)
            pl.BlockSpec((1, rep, Dh), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, block_s, Dh), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_s, Dh), lambda b, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, rep, Dh), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * KV, rep, Dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, Dh), jnp.float32),
        ],
        interpret=interpret,
    )(pos, qf, kf, vf)
    return out.reshape(B, KV, rep, Dh)
