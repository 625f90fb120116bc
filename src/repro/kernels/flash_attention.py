"""Pallas TPU kernel: flash attention (online softmax over KV blocks).

TPU-native tiling: grid = (batch·heads, q_blocks, kv_blocks) with the
kv-block dimension innermost, so the (m, l, acc) running state lives in VMEM
scratch across the kv sweep while q/k/v stream HBM -> VMEM one (block_q,
head_dim) / (block_k, head_dim) tile at a time. Block shapes default to
(512, 512) with head_dim padded to a lane multiple — MXU-aligned (multiples
of 128) on the contraction dims.

Causality is handled with in-block masking plus `pl.when` block skipping:
fully-future kv blocks contribute nothing and their matmuls are predicated
off. The jnp oracle is `repro.models.layers.chunked_attention` (same block
recurrence); `ref.py` re-exports it for the kernel test sweep.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  causal: bool, window: int, q_offset: int, scale: float,
                  block_q: int, block_k: int, n_kv_blocks: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_lo = qi * block_q + q_offset
    k_lo = kj * block_k
    # block is live unless entirely in the future (causal) or out of window
    live = True
    if causal:
        live = k_lo <= q_lo + block_q - 1
    if window:
        live = jnp.logical_and(live, k_lo + block_k - 1 > q_lo - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, :, :].astype(jnp.float32)  # (bq, d)
        k = k_ref[0, :, :].astype(jnp.float32)  # (bk, d)
        v = v_ref[0, :, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window:
            mask = jnp.logical_and(mask, kpos > qpos - window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(kj == n_kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, :, :] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                           causal: bool = True, window: int = 0,
                           q_offset: int = 0,
                           block_q: int = DEFAULT_BLOCK_Q,
                           block_k: int = DEFAULT_BLOCK_K,
                           interpret: bool) -> jnp.ndarray:
    """q: (BH, Sq, Dh); k/v: (BH, Sk, Dh) — heads pre-folded into batch.

    Returns (BH, Sq, Dh). The ops.py wrapper handles the (B,S,H,D) <->
    (BH,S,D) layout and GQA expansion.
    """
    BH, Sq, Dh = q.shape
    Sk = k.shape[1]
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0
    n_kv = Sk // block_k
    grid = (BH, Sq // block_q, n_kv)
    kernel = functools.partial(
        _flash_kernel, causal=causal, window=window, q_offset=q_offset,
        scale=1.0 / np.sqrt(Dh), block_q=block_q, block_k=block_k,
        n_kv_blocks=n_kv)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, Dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, Dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, Dh), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, Dh), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, Dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # m: running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # l: running denom
            pltpu.VMEM((block_q, Dh), jnp.float32),  # acc: running numerator
        ],
        interpret=interpret,
    )(q, k, v)
