"""Minimal functional parameter/module helpers (no flax dependency).

Params are plain nested dicts of arrays; layer stacks store params with a
leading ``L`` axis so the forward pass can `lax.scan` over layers (keeps the
HLO small — essential for 35-88 layer models and single-core XLA compiles).
Sharding is attached *outside* the model by path-pattern rules
(``repro.distributed.sharding``), so model code stays mesh-agnostic.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]


def dtype_of(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16}[name]


def normal(key, shape, std, dtype):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def dense_init(key, in_dim: int, out_shape, dtype, std: Optional[float] = None):
    """Weight of shape (in_dim, *out_shape), fan-in scaled."""
    if std is None:
        std = 1.0 / np.sqrt(in_dim)
    shape = (in_dim,) + tuple(np.atleast_1d(out_shape).tolist())
    return normal(key, shape, std, dtype)


def stack_layer_params(init_fn: Callable[[jax.Array], Params], key,
                       n_layers: int) -> Params:
    """vmap a single-layer init over layer keys -> params stacked on axis 0."""
    keys = jax.random.split(key, n_layers)
    return jax.vmap(init_fn)(keys)


def _remat_wrap(body: Callable, cfg) -> Callable:
    """Apply the configured rematerialization policy to a layer body."""
    if not getattr(cfg, "remat", False):
        return body
    policy = getattr(cfg, "remat_policy", "full")
    if policy == "dots":
        # save matmul outputs; recompute only cheap elementwise chains —
        # cuts the backward re-forward (~33% of train flops) at the cost of
        # storing per-layer matmul activations
        return jax.checkpoint(
            body, prevent_cse=False,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    if policy == "none":
        return body
    return jax.checkpoint(body, prevent_cse=False)


def scan_layers(body: Callable, carry, stacked_params: Params, *,
                remat: bool = False, unroll: int = 1):
    """`lax.scan` over the leading layer axis of ``stacked_params``.

    ``body(carry, layer_params) -> (carry, out)``. With ``remat`` the body is
    rematerialized (per-layer activation checkpointing). The saved carry is
    pinned behind an optimization barrier: without it XLA hoists the
    bf16->f32 conversion of the *entire* saved-residual stack out of the
    backward loop, tripling activation memory (observed on the 512-device
    dry-run).
    """
    if not remat:
        return jax.lax.scan(body, carry, stacked_params, unroll=unroll)

    def pinned(c, xs):
        c = jax.lax.optimization_barrier(c)
        return body(c, xs)

    fn = jax.checkpoint(pinned, prevent_cse=False)
    return jax.lax.scan(fn, carry, stacked_params, unroll=unroll)


def count_params(params: Params) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))


def tree_paths(params: Params, prefix: str = "") -> Dict[str, Any]:
    """Flatten params to {'a/b/c': leaf} path map (sharding rule matching)."""
    out: Dict[str, Any] = {}
    if isinstance(params, dict):
        for k, v in params.items():
            out.update(tree_paths(v, f"{prefix}/{k}" if prefix else k))
    else:
        out[prefix] = params
    return out


# float32 values in one (8, 128) TPU tile
_TILE = 8 * 128


def pack_rows(trees: Sequence[Params]) -> jax.Array:
    """The ``(len(trees), dim)`` float32 rows of same-shaped trees: row ``i``
    holds every leaf of ``trees[i]`` raveled and cast to float32, in
    ``tree_paths`` order, the order :func:`unflatten_like` reads.

    Leaf by leaf, the rows' whole (8, 128) tiles are built as lane-dense
    ``(n / 128, 128)`` blocks behind an optimization barrier; a TPU holds
    such a block as the bytes of the contiguous row segment. Without the
    barrier XLA folds the ravel and the rows' layout into one reshape,
    which it lowers on a TPU as a relayout loop of several full-length
    passes. The rest of a leaf, less than a tile, goes as it is."""
    parts = []
    for leaves in zip(*(tree_paths(t).values() for t in trees)):
        rows = [jnp.ravel(v) for v in leaves]
        n = rows[0].size
        whole = n - n % _TILE
        if whole:
            parts.append(jnp.stack([jax.lax.optimization_barrier(
                r[:whole].astype(jnp.float32).reshape(-1, 128)).reshape(whole)
                for r in rows]))
        if whole < n:
            parts.append(jnp.stack([r[whole:].astype(jnp.float32)
                                    for r in rows]))
    return jnp.concatenate(parts, axis=1)


def unflatten_like(flat, like: Params) -> Params:
    """Slices the flat row ``flat`` back into a nested dict shaped and
    typed like ``like``, reading the leaves in ``tree_paths`` order, the
    order :func:`pack_rows` writes them."""
    out, off = {}, 0
    for k, v in tree_paths(like).items():
        n = int(np.prod(v.shape))
        out[k] = flat[off:off + n].reshape(v.shape).astype(v.dtype)
        off += n
    # rebuild nested dict
    root = {}
    for path, leaf in out.items():
        d = root
        parts = path.split("/")
        for p in parts[:-1]:
            d = d.setdefault(p, {})
        d[parts[-1]] = leaf
    return root


def cast_tree(params: Params, dtype) -> Params:
    return jax.tree.map(lambda x: x.astype(dtype)
                        if jnp.issubdtype(x.dtype, jnp.floating) else x, params)


def run_periods(body: Callable, carry, stacked_params: Params, *, cfg):
    """Dispatch between scan (default) and python-unrolled period loops.

    The unrolled path (``cfg.scan_layers=False``) exists for the roofline
    cost probes: XLA's cost_analysis counts a `while` body once regardless
    of trip count, so exact per-period FLOPs/bytes come from compiling 1-
    and 2-period unrolled variants and differencing.
    """
    if getattr(cfg, "scan_layers", True):
        fn = _remat_wrap(body, cfg)
        return jax.lax.scan(fn, carry, stacked_params)
    fn = _remat_wrap(body, cfg)
    n = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    ys_list = []
    for i in range(n):
        pp = jax.tree.map(lambda x: x[i], stacked_params)
        carry, y = fn(carry, pp)
        ys_list.append(y)
    if ys_list and ys_list[0] is not None:
        ys = jax.tree.map(lambda *xs: jnp.stack(xs), *ys_list)
    else:
        ys = None
    return carry, ys
