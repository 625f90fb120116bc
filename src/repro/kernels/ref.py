"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def olaf_combine_ref(slots, counts, updates, clusters, gate):
    """Running-mean segment combine (Algorithm 1 applied to a burst).

    slots (Q,D), counts (Q,), updates (U,D), clusters (U,), gate (U,)
    -> (new_slots (Q,D), new_counts (Q,)). A leading S axis batches
    independent queues (mirrors the kernel's multi-queue grid axis).
    """
    if slots.ndim == 3:
        return jax.vmap(olaf_combine_ref)(slots, counts, updates, clusters, gate)
    Q = slots.shape[0]
    onehot = (jax.nn.one_hot(clusters, Q, dtype=updates.dtype)
              * gate.astype(updates.dtype)[:, None])  # (U,Q)
    sums = jnp.einsum("uq,ud->qd", onehot, updates.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)
    hits = onehot.sum(axis=0)  # (Q,)
    acc = slots.astype(jnp.float32) * counts.astype(jnp.float32)[:, None] + sums
    denom = jnp.maximum(counts.astype(jnp.float32) + hits, 1.0)
    new_counts = counts.astype(jnp.int32) + hits.astype(jnp.int32)
    return (acc / denom[:, None]).astype(slots.dtype), new_counts


def flash_attention_ref(q, k, v, *, causal=True, window=0, q_offset=0):
    """Dense-softmax reference. q/k/v: (BH, S, Dh)."""
    Dh = q.shape[-1]
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / np.sqrt(Dh)
    Sq, Sk = q.shape[1], k.shape[1]
    qpos = jnp.arange(Sq)[:, None] + q_offset
    kpos = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isfinite(s).any(-1, keepdims=True), p, 0.0)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)).astype(q.dtype)


def decode_attention_ref(q, k_cache, v_cache, pos):
    """q: (B,KV,rep,Dh); caches (B,S,KV,Dh); pos (B,)."""
    Dh = q.shape[-1]
    s = jnp.einsum("bkrd,bskd->bkrs", q.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) / np.sqrt(Dh)
    S = k_cache.shape[1]
    mask = jnp.arange(S)[None, :] <= pos[:, None]
    s = jnp.where(mask[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkrs,bskd->bkrd", p, v_cache.astype(jnp.float32))
    return out.astype(q.dtype)
