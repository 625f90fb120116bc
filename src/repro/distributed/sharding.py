"""Logical-axis sharding rules (MaxText-style) for params, inputs, caches.

Strategy on the production mesh (``data``=16, ``model``=16, optional
``pod``=2):

  * batch            -> ("pod","data")   (pure DP across pods: params are
                        replicated over ``pod``; gradients all-reduce across
                        pods once per step — the hierarchical, pod-local-
                        combining layout matching OLAF's multi-hop topology)
  * params           -> FSDP over ``data`` on the d_model/input dim and TP
                        over ``model`` on one output dim (heads / ff / vocab /
                        experts), with divisibility-checked fallbacks: heads
                        that don't divide the axis fall back to head_dim
                        sharding; experts that don't divide fall back to
                        per-expert ff sharding (grok: 8 experts on a 16-way
                        axis -> TP inside experts)
  * KV caches        -> batch over ``data``; kv-heads over ``model`` when
                        divisible, else the *sequence* dim over ``model``
                        (sequence-parallel decode for long contexts)

Rules match on parameter path suffixes; every dim carries an ordered list of
candidate mesh axes and the resolver picks the first feasible assignment
(axis unused so far in this tensor + divisibility).
"""
from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeCfg
from repro.models.module import tree_paths

# dim annotation -> ordered candidate mesh-axis names
FSDP = ("data",)
TP = ("model",)
TP_THEN_FSDP = ("model", "data")
NONE: Tuple[str, ...] = ()

# (path regex, per-dim candidates, priority order of dims for resolution)
# Dims are listed for the *unstacked* tensor; a leading scan/layer axis is
# detected by ndim mismatch and gets no sharding.
_PARAM_RULES: List[Tuple[str, Tuple[Tuple[str, ...], ...], Tuple[int, ...]]] = [
    (r"embedding/embed$",        (TP, FSDP),           (0, 1)),
    (r"embedding/unembed$",      (FSDP, TP),           (1, 0)),
    (r"patch_proj$",             (FSDP, TP),           (1, 0)),
    # attention: heads (padded to divisibility) shard on model; KV-head
    # weights stay replicated over model (expanded at compute); in
    # "replicated" attention mode the TP candidates are stripped below.
    (r"attn/wq$",                (FSDP, TP, NONE),     (1, 0)),
    (r"attn/wk$",                (FSDP, TP, NONE),     (1, 0)),
    (r"attn/wv$",                (FSDP, TP, NONE),     (1, 0)),
    (r"attn/wo$",                (TP, NONE, FSDP),     (0, 2)),
    (r"mlp/wg$",                 (FSDP, TP),           (1, 0)),
    (r"mlp/wu$",                 (FSDP, TP),           (1, 0)),
    (r"mlp/wd$",                 (TP, FSDP),           (0, 1)),
    (r"moe/router$",             (FSDP, NONE),         (0,)),
    (r"moe/wg$",                 (TP, FSDP, TP),       (0, 2, 1)),  # experts, else ff
    (r"moe/wu$",                 (TP, FSDP, TP),       (0, 2, 1)),
    (r"moe/wd$",                 (TP, TP, FSDP),       (0, 1, 2)),
    (r"moe/dense/w[gud]$",       (FSDP, TP),           (1, 0)),
    (r"ssm/w[zx]$",              (FSDP, TP),           (1, 0)),
    (r"ssm/w(B|C|dt)$",          (FSDP, NONE),         (0,)),
    (r"ssm/wo$",                 (TP, FSDP),           (0, 1)),
    (r"ssm/conv_[wb]$",          None,                 ()),  # replicate
    (r"ssm/(A_log|dt_bias|D|norm_scale)$", None,       ()),
    (r"rec/w_(gate|rec)_branch$", (FSDP, TP),          (1, 0)),
    (r"rec/w_[ax]$",             (FSDP, TP),           (1, 0)),
    (r"rec/conv_[wb]$",          None,                 ()),
    (r"rec/lam$",                None,                 ()),
    (r"rec/wo$",                 (TP, FSDP),           (0, 1)),
    (r"(ln1|ln2|ln_x|final_norm|enc_final|dec_final|norm)/", None, ()),
    (r"(scale|bias)$",           None,                 ()),
]

_ATTN_PAT = re.compile(r"(attn)/w[qkvo]$")


def params_pspecs_cfg(param_tree, mesh: Mesh, cfg: Optional[ArchConfig]) -> Any:
    """Like :func:`params_pspecs` but strips TP candidates from attention
    weights when ``cfg.attn_mode == "replicated"`` (tiny-head archs where the
    attention compute is replicated over the model axis)."""
    specs = params_pspecs(param_tree, mesh)
    if cfg is None or cfg.attn_mode != "replicated":
        return specs
    flat_params = tree_paths(param_tree)
    flat_specs = tree_paths_like(specs, flat_params)
    out = {}
    for path, spec in flat_specs.items():
        if _ATTN_PAT.search(path):
            # keep only "data" (FSDP) entries
            out[path] = P(*[a if a == "data" else None for a in
                            (list(spec) + [None] * 8)[:len(flat_params[path].shape)]])
        else:
            out[path] = spec
    return _unflatten_like(param_tree, out)


def tree_paths_like(spec_tree, flat_params: Dict[str, Any]) -> Dict[str, P]:
    flat = {}

    def rec(t, prefix=""):
        if isinstance(t, dict):
            for k, v in t.items():
                rec(v, f"{prefix}/{k}" if prefix else k)
        else:
            flat[prefix] = t

    rec(spec_tree)
    return flat


def _resolve_spec(shape: Sequence[int], dims: Optional[Tuple[Tuple[str, ...], ...]],
                  priority: Tuple[int, ...], mesh: Mesh,
                  lead_pad: int) -> P:
    """Assign at most one mesh axis per tensor-axis honoring divisibility."""
    if dims is None:
        return P()
    spec: List[Optional[str]] = [None] * len(shape)
    used: set = set()
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    for di in priority:
        idx = di + lead_pad
        if idx >= len(shape):
            continue
        for cand in dims[di]:
            if cand in used or cand not in axis_sizes:
                continue
            if shape[idx] % axis_sizes[cand] == 0 and shape[idx] > 0:
                spec[idx] = cand
                used.add(cand)
                break
    return P(*spec)


def params_pspecs(param_tree, mesh: Mesh) -> Any:
    """Map a params pytree (arrays or ShapeDtypeStructs) -> PartitionSpecs."""
    flat = tree_paths(param_tree)
    specs: Dict[str, P] = {}
    for path, leaf in flat.items():
        shape = leaf.shape
        matched = False
        for pat, dims, prio in _PARAM_RULES:
            if re.search(pat, path):
                if dims is None:
                    specs[path] = P()
                else:
                    lead = len(shape) - len(dims)
                    specs[path] = _resolve_spec(shape, dims, prio, mesh, lead)
                matched = True
                break
        if not matched:
            specs[path] = P()  # conservative: replicate
    return _unflatten_like(param_tree, specs)


def _unflatten_like(tree, flat_specs: Dict[str, P], prefix: str = ""):
    if isinstance(tree, dict):
        return {k: _unflatten_like(v, flat_specs,
                                   f"{prefix}/{k}" if prefix else k)
                for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        out = [_unflatten_like(v, flat_specs, f"{prefix}/{i}")
               for i, v in enumerate(tree)]
        return type(tree)(out)
    return flat_specs[prefix]


# ---------------------------------------------------------------------------
# Input / cache specs
# ---------------------------------------------------------------------------
def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _shardable(size: int, mesh: Mesh, axes: Tuple[str, ...]) -> bool:
    n = int(np.prod([dict(zip(mesh.axis_names, mesh.devices.shape))[a]
                     for a in axes]))
    return size % n == 0 and size >= n


def data_pspecs(specs: Dict[str, Any], mesh: Mesh, cfg: ArchConfig) -> Dict[str, Any]:
    """Shardings for a train/prefill/decode input dict (see api.input_specs)."""
    ba = batch_axes(mesh)
    out: Dict[str, Any] = {}
    for name, leaf in specs.items():
        if name == "caches":
            out[name] = cache_pspecs(leaf, mesh, cfg)
            continue
        shape = leaf.shape
        b_ok = _shardable(shape[0], mesh, ba)
        b_spec = ba if b_ok else (("data",) if _shardable(shape[0], mesh, ("data",))
                                  else None)
        out[name] = P(b_spec, *([None] * (len(shape) - 1)))
    return out


def cache_pspecs(cache_tree, mesh: Mesh, cfg: ArchConfig) -> Any:
    """KV caches: batch->data(+pod), kv-heads->model if divisible else seq->model.
    Recurrent states: batch->data, channels/headdim->model if divisible."""
    ba = batch_axes(mesh)
    msize = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)

    def leaf_spec(path: str, leaf) -> P:
        shape = leaf.shape
        # stacked layer axis present for scanned caches ((L, B, ...)) — detect
        # via path prefix "layers/" (transformer) or self/cross (encdec)
        lead = 1 if (path.startswith("layers/") or path.split("/")[-1].startswith(
            ("self_", "cross_"))) else 0
        spec: List[Optional[str]] = [None] * len(shape)
        b_idx = lead
        if _shardable(shape[b_idx], mesh, ba):
            spec[b_idx] = ba
        elif _shardable(shape[b_idx], mesh, ("data",)):
            spec[b_idx] = "data"
        name = path.split("/")[-1]
        if name in ("k", "v", "self_k", "self_v", "cross_k", "cross_v"):
            kv_idx, s_idx = lead + 2, lead + 1
            if shape[kv_idx] % msize == 0:
                spec[kv_idx] = "model"
            elif shape[s_idx] % msize == 0:
                spec[s_idx] = "model"  # sequence-parallel cache
        elif name == "state":  # SSD state (B,H,P,N)
            for idx in (lead + 1, lead + 2):
                if shape[idx] % msize == 0:
                    spec[idx] = "model"
                    break
        elif name == "h":  # RG-LRU state (B, w)
            if shape[lead + 1] % msize == 0:
                spec[lead + 1] = "model"
        elif name == "conv":  # (B, K-1, C)
            if shape[lead + 2] % msize == 0:
                spec[lead + 2] = "model"
        return P(*spec)

    flat = tree_paths(cache_tree)
    return _unflatten_like(cache_tree, {p: leaf_spec(p, l) for p, l in flat.items()})


def out_pspecs_for(kind: str, mesh: Mesh, cfg: ArchConfig, in_specs, data_specs):
    """out_shardings: train -> replicated loss + param-sharded grads handled
    by caller; prefill/decode -> logits sharded on vocab, caches like inputs."""
    raise NotImplementedError  # assembled in launch.dryrun per step type


def to_named(tree, mesh: Mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Multi-switch (S-axis) sharding for the OLAF data plane.
#
# The fused kernels batch independent queues on a leading S axis (one per
# switch — SW1/SW2/SW3 in the §8.3 topology). On a single device the axis
# folds into the Pallas grid (one launch covers every switch); with several
# devices the axis is split over a dedicated "switch" mesh with shard_map,
# so each device runs its slice of the same single launch.
# ---------------------------------------------------------------------------
def switch_mesh(n_switches) -> Mesh:
    """1-D mesh on axis ``"switch"`` sized to the largest divisor of
    ``n_switches`` that the available devices support (1 on this CPU
    container, up to ``n_switches`` on a pod slice). Accepts either the
    switch count or a compiled ``repro.core.topology.TopologySpec`` (any
    spec DAG shards by its switch axis), so arbitrary topologies — not
    just the 3-switch §8.3 fan-in — split over the device mesh."""
    n_switches = int(getattr(n_switches, "num_switches", n_switches))
    devs = jax.devices()
    n = 1
    for d in range(min(n_switches, len(devs)), 0, -1):
        if n_switches % d == 0:
            n = d
            break
    return Mesh(np.asarray(devs[:n]).reshape(n), ("switch",))


def _pow2_at_most(n: int) -> int:
    return 1 << max(int(n), 1).bit_length() - 1


def vecsim_mesh(n_switches=None, *, n_clusters: Optional[int] = None,
                worker_shards: int = 1) -> Mesh:
    """2-D ``("switch", "worker")`` mesh for the sharded vectorized
    simulator (``repro.core.vecsim.run_vecsim(..., mesh=...)``): per-switch
    scan state partitions over ``"switch"``, worker generation / txctl /
    AoM state over ``"worker"``. Shard counts are powers of two, which
    always divide vecsim's power-of-two padded axes: the worker axis gets
    at most ``worker_shards`` devices (capped by ``n_clusters`` so the
    AoM rows still split), the switch axis the largest power of two that
    fits the remaining devices and the switch count. Accepts a count or a
    ``TopologySpec`` for ``n_switches``."""
    n_switches = int(getattr(n_switches, "num_switches", n_switches or 1))
    devs = jax.devices()
    nw = _pow2_at_most(min(worker_shards, len(devs)))
    if n_clusters is not None:
        nw = min(nw, _pow2_at_most(n_clusters))
    ns = _pow2_at_most(min(n_switches, len(devs) // nw))
    return Mesh(np.asarray(devs[:ns * nw]).reshape(ns, nw),
                ("switch", "worker"))


def _shard_switch_axis(fn, mesh: Mesh, n_in: int, n_out: int):
    """shard_map ``fn`` (every operand/result leading-S) over ``"switch"``."""
    spec = P("switch")
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec,) * n_in,
                         out_specs=(spec,) * n_out if n_out > 1 else spec,
                         check_vma=False)


def olaf_combine_sharded(slots, counts, updates, clusters, gate, *,
                         mesh: Optional[Mesh] = None, **kw):
    """``ops.olaf_combine_multi`` with the S axis split over the switch mesh.

    Falls back to the single-launch folded-grid path when the mesh has one
    device, so callers can use this unconditionally.
    """
    from repro.kernels import ops
    if mesh is None:
        mesh = switch_mesh(slots.shape[0])
    fn = lambda *a: ops.olaf_combine_multi(*a, **kw)  # noqa: E731
    if mesh.devices.size <= 1:
        return fn(slots, counts, updates, clusters, gate)
    return _shard_switch_axis(fn, mesh, 5, 2)(
        slots, counts, updates, clusters, gate)


def olaf_step_sharded(states, clusters, workers, gen_times, rewards,
                      payloads, reward_threshold=float("inf"), send=None,
                      capacities=None, *, k: int,
                      mesh: Optional[Mesh] = None, **kw):
    """``ops.olaf_step_multi`` with the S axis split over the switch mesh:
    the full enqueue→drain cycle for every switch in one sharded launch.

    ``capacities`` is an optional ``(S,)`` per-switch logical slot vector
    (``TopologySpec.queue_slots``): switches with heterogeneous queue
    sizes ride one padded ``(S, Qmax)`` state, and the vector shards with
    its switch."""
    import jax.numpy as jnp

    from repro.kernels import ops
    if mesh is None:
        mesh = switch_mesh(states.cluster.shape[0])
    if send is None:
        send = jnp.ones(clusters.shape, bool)
    thr = jnp.broadcast_to(jnp.asarray(reward_threshold, jnp.float32),
                           (clusters.shape[0], 1))
    cap = jnp.broadcast_to(
        jnp.asarray(states.cluster.shape[1] if capacities is None
                    else capacities, jnp.int32), (clusters.shape[0],))

    def fn(st, c, w, t, r, p, th, sn, cp):
        return ops.olaf_step_multi(st, c, w, t, r, p, th[0, 0], sn, cp,
                                   k=k, **kw)

    if mesh.devices.size <= 1:
        return fn(states, clusters, workers, gen_times, rewards, payloads,
                  thr, send, cap)
    spec = P("switch")
    state_specs = jax.tree.map(lambda _: spec, states)
    out_specs = (state_specs,
                 dict(valid=spec, n_valid=spec, cluster=spec, worker=spec,
                      gen_time=spec, reward=spec, agg_count=spec,
                      payload=spec))
    return jax.shard_map(fn, mesh=mesh,
                         in_specs=(state_specs,) + (spec,) * 8,
                         out_specs=out_specs, check_vma=False)(
        states, clusters, workers, gen_times, rewards, payloads, thr, send,
        cap)
