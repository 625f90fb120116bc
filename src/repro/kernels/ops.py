"""Jit'd public wrappers around the Pallas kernels.

Whether a kernel is compiled is read from the backend at trace time: on a
TPU every kernel compiles with Mosaic; on any other backend the kernel
bodies run through the Pallas interpreter. No flag or environment variable
changes that. The raw ``*_pallas`` functions keep an explicit
``interpret`` argument, so a test can compile them for a described TPU
while the CPU is the backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.olaf_queue import (JaxQueueState, expire_inactive_drains,
                                   jax_enqueue_burst_ex, jax_olaf_step)
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.olaf_combine import olaf_combine_pallas, olaf_enqueue_pallas
from repro.kernels.olaf_step import olaf_step_pallas


def _interpret() -> bool:
    """Pallas kernels are compiled on a TPU and interpreted elsewhere."""
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("tile_q", "tile_d"))
def olaf_combine(slots, counts, updates, clusters, gate, *, tile_q: int = 8,
                 tile_d: int = 512):
    """Combine a burst of updates into cluster slots (running mean).

    slots (Q,D), counts (Q,) int32, updates (U,D), clusters (U,) int32,
    gate (U,) int32/bool -> (new_slots (Q,D), new_counts (Q,)).

    A leading S axis on every operand batches S independent queues (the
    SW1/SW2/SW3 multi-switch combine) in one kernel launch; see also
    :func:`olaf_combine_multi` for the explicitly-batched signature. Both
    slots and counts come fused out of a single Pallas kernel — the counts
    are not recomputed host-side.
    """
    gate = gate.astype(jnp.int32)
    return olaf_combine_pallas(slots, counts, updates, clusters, gate,
                               tile_q=tile_q, tile_d=tile_d,
                               interpret=_interpret())


def olaf_combine_multi(slots, counts, updates, clusters, gate, *,
                       tile_q: int = 8, tile_d: int = 512):
    """Multi-queue combine: every operand carries a leading S (switch) axis.

    slots (S,Q,D), counts (S,Q), updates (S,U,D), clusters/gate (S,U)
    -> (new_slots (S,Q,D), new_counts (S,Q)). Equivalent to
    ``jax.vmap(olaf_combine)`` but runs as one kernel launch with the switch
    axis folded into the Pallas grid.
    """
    return olaf_combine(slots, counts, updates, clusters, gate,
                        tile_q=tile_q, tile_d=tile_d)


def olaf_combine_window(slots, counts, updates, clusters, gate, reset_slots,
                        *, tile_q: int = 8, tile_d: int = 512):
    """Window-batched gate entry for the hybrid control-plane replay.

    Lands one whole transmission window — ``updates`` (S, U, D) staged as a
    single block, ``clusters``/``gate`` (S, U) and ``reset_slots`` (S, Q)
    arriving as host (numpy) window buffers, one device put each — in one
    :func:`olaf_combine_multi` launch. ``gate`` carries each entry's
    aggregation weight with non-contributing entries already zeroed (the
    ``burst_contribution_mask`` telescoped-mean rule), and ``reset_slots``
    masks the slots whose payload restarts from this window: their running
    count re-enters the combine at zero.
    """
    counts_in = jnp.where(jnp.asarray(reset_slots), 0, counts)
    return olaf_combine_multi(slots, counts_in, updates,
                              jnp.asarray(clusters), jnp.asarray(gate),
                              tile_q=tile_q, tile_d=tile_d)


@functools.partial(jax.jit, static_argnames=("tile_q", "tile_d"))
def olaf_forward(slots, counts, updates, clusters, gate, reset_slots,
                 drain_sw, drain_slot, drain_hop=None, *, tile_q: int = 8,
                 tile_d: int = 512):
    """Window combine + device-resident forwarding pass, one dispatch.

    First lands the pending transmission window (exactly
    :func:`olaf_combine_window`; skipped when ``updates`` is empty — a
    drain-only boundary), then routes the departing rows out of the
    ``(S, Q, D)`` slot buffer with a next-hop one-hot gather/scatter:
    ``drain_sw``/``drain_slot`` ``(K,)`` name the departing (switch, slot)
    pairs; their rows are gathered from the *post-combine* buffer and the
    slots cleared. Returns ``(new_slots, new_counts, drained (K, D))``, or
    ``(…, drained, hops (K,))`` when ``drain_hop`` is given.

    The drained rows stay device-resident: the hybrid control plane
    resolves each row's next hop (the routing decision recorded in the
    queue-event trace — primary, failure reroute, PS delivery, or link
    drop) and threads it through as ``drain_hop`` ``(K,)`` int32
    (destination switch index, −1 = PS egress, −2 = dropped by the fault
    model). The hop vector rides the dispatch and returns as a device
    array aligned with ``drained``, so a transit hop (SW1→SW3-style
    forwarding, or any spec DAG edge) never round-trips payload bytes
    through the host, and a batched multi-drain consumer can scatter rows
    by hop entirely on device.
    """
    if updates.shape[1] > 0:
        slots, counts = olaf_combine_window(
            slots, counts, updates, clusters, gate, reset_slots,
            tile_q=tile_q, tile_d=tile_d)
    S, Q, _ = slots.shape
    drain_sw = jnp.asarray(drain_sw, jnp.int32)
    drain_slot = jnp.asarray(drain_slot, jnp.int32)
    # O(K·D) indexed gather + clear — the departing rows, not the buffer
    drained = slots[drain_sw, drain_slot]  # (K, D)
    popped = jnp.zeros((S, Q), bool).at[drain_sw, drain_slot].set(True)
    new_slots = jnp.where(popped[..., None], 0.0, slots)
    new_counts = jnp.where(popped, 0, counts)
    if drain_hop is None:
        return new_slots, new_counts, drained
    # a dropped row (hop == −2) is zeroed in place: the payload dies on
    # device with its slot; the caller never copies it anywhere
    hops = jnp.asarray(drain_hop, jnp.int32)
    drained = jnp.where((hops >= -1)[:, None], drained, 0.0)
    return new_slots, new_counts, drained, hops


@functools.partial(jax.jit, static_argnames=("tile_q", "tile_d"))
def olaf_enqueue(state: JaxQueueState, clusters, workers, gen_times, rewards,
                 payloads, reward_threshold=jnp.inf, capacity=None,
                 screen=None, *, tile_q: int = 8, tile_d: int = 512) -> JaxQueueState:
    """Fused single-launch burst enqueue (Algorithm 1 for U updates).

    Drop-in replacement for ``repro.core.olaf_queue.jax_enqueue_burst`` (the
    oracle it is tested against): the ``_burst_resolve`` scalar scan runs
    inside the kernel from SMEM per-update scalars and the payload
    telescoped-mean runs on the VPU over the same (Q-tile × D-tile) grid as
    ``olaf_combine`` — one kernel launch for the whole burst instead of a
    scan + einsum + blend pipeline. ``screen`` optionally withholds rows
    flagged by the ingress integrity gate (``jax_screen_mask``).
    """
    new_payload, mi, mf = olaf_enqueue_pallas(
        state.cluster, state.worker, state.seq, state.gen_time, state.reward,
        state.agg_count, state.replaceable, state.next_seq, state.n_dropped,
        state.n_agg, state.n_repl, state.payload,
        clusters, workers, gen_times, rewards, payloads, reward_threshold,
        capacity, state.n_screened, screen, tile_q=tile_q, tile_d=tile_d,
        interpret=_interpret())
    return JaxQueueState(
        cluster=mi[0], worker=mi[1], seq=mi[2], gen_time=mf[0], reward=mf[1],
        agg_count=mi[3], replaceable=mi[4].astype(bool), payload=new_payload,
        next_seq=mi[5, 0], n_dropped=mi[6, 0], n_agg=mi[7, 0],
        n_repl=mi[8, 0], n_screened=mi[9, 0])


def _olaf_step_unpack(new_payload, drained, mi, mf, di, df):
    """Raw kernel outputs -> (JaxQueueState, drain out dict).

    Works for both the single-queue (no batch axis) and the multi-queue
    (leading S axis) layouts; ``mi``/``mf``/``di``/``df`` carry the packing
    documented in :func:`repro.kernels.olaf_step._olaf_step_kernel`.
    """
    lead = mi.ndim == 3  # (S, 10, Q) vs (10, Q)
    row = (lambda a, r: a[:, r]) if lead else (lambda a, r: a[r])
    ctr = (lambda a, r: a[:, r, 0]) if lead else (lambda a, r: a[r, 0])
    valid = row(di, 3).astype(bool)
    state = JaxQueueState(
        cluster=row(mi, 0), worker=row(mi, 1), seq=row(mi, 2),
        gen_time=row(mf, 0), reward=row(mf, 1), agg_count=row(mi, 3),
        replaceable=row(mi, 4).astype(bool),
        payload=new_payload, next_seq=ctr(mi, 5), n_dropped=ctr(mi, 6),
        n_agg=ctr(mi, 7), n_repl=ctr(mi, 8), n_screened=ctr(mi, 9))
    out = dict(valid=valid, n_valid=valid.sum(axis=-1),
               cluster=row(di, 0), worker=row(di, 1),
               gen_time=row(df, 0), reward=row(df, 1),
               agg_count=row(di, 2), payload=drained)
    return state, out


@functools.partial(jax.jit, static_argnames=(
    "k", "tile_q", "tile_d", "impl"), donate_argnums=0)
def olaf_step(state: JaxQueueState, clusters, workers, gen_times, rewards,
              payloads, reward_threshold=jnp.inf, send=None, capacity=None,
              active_workers=None, screen=None, *, k: int, tile_q: int = 8,
              tile_d: int | None = None, impl: str = "auto"):
    """Fused full-cycle data-plane step: burst enqueue → drain-k, one launch.

    Drop-in replacement for the composed ``jax_enqueue_burst →
    jax_dequeue_burst`` pipeline (the oracle it is tested against in
    tests/test_olaf_step.py); returns the same ``(new_state, out)`` pair.
    ``send`` optionally gates each burst row (worker-side transmission
    control); ``capacity`` caps the logical slot count below the padded
    buffer size (per-switch ``TopologySpec.queue_slots``). The queue state
    is donated: treat the passed-in state as consumed.

    ``impl`` selects the execution path: ``"pallas"`` is the single-launch
    kernel (the TPU fast path — resolve, drain select and payload movement
    share one grid, whose D-tile ``tile_d=None`` sizes from the shapes and
    a VMEM budget); ``"xla"`` is the same cycle as one fused XLA
    executable (the fast path where the interpreter would run the kernel
    body, i.e. off a TPU); ``"auto"`` picks ``"pallas"`` on a TPU backend
    and ``"xla"`` on any other. An empty burst always takes ``"xla"``.

    ``active_workers`` (bool (W,)) treats drained rows of crashed workers
    as expired — slot freed, row masked invalid so it is never applied
    (node-churn gating). Applied as a post-drain mask on both execution
    paths, keeping the Pallas kernel body unchanged; see
    :func:`repro.core.olaf_queue.expire_inactive_drains`.

    ``screen`` (bool (U,)) is the ingress payload-integrity gate: flagged
    rows are withheld before the queue exactly like transmission-control
    deferrals, except they bump the state's ``n_screened`` counter.
    """
    if impl == "auto":
        # an empty burst (drain-only final flush) has no (U, Dt) tile to
        # grid over — always take the XLA path for it
        impl = "xla" if (_interpret() or clusters.shape[0] == 0) \
            else "pallas"
    if impl == "xla":
        return jax_olaf_step(state, clusters, workers, gen_times, rewards,
                             payloads, k, reward_threshold, send, capacity,
                             active_workers, screen)
    outs = olaf_step_pallas(
        state.cluster, state.worker, state.seq, state.gen_time, state.reward,
        state.agg_count, state.replaceable, state.next_seq, state.n_dropped,
        state.n_agg, state.n_repl, state.payload,
        clusters, workers, gen_times, rewards, payloads, k, reward_threshold,
        send, capacity, state.n_screened, screen, tile_q=tile_q,
        tile_d=tile_d, interpret=_interpret())
    state, out = _olaf_step_unpack(*outs)
    if active_workers is not None:
        out = expire_inactive_drains(out, active_workers)
    return state, out


@functools.partial(jax.jit, static_argnames=(
    "k", "tile_q", "tile_d", "impl"), donate_argnums=0)
def olaf_step_multi(states: JaxQueueState, clusters, workers, gen_times,
                    rewards, payloads, reward_threshold=jnp.inf, send=None,
                    capacity=None, screen=None, *, k: int, tile_q: int = 8,
                    tile_d: int | None = None, impl: str = "auto"):
    """Multi-queue fused cycle: every operand carries a leading S axis.

    ``states`` is a JaxQueueState of (S, Q)/(S, Q, D)/(S,) arrays; burst
    operands are (S, U)/(S, U, D). Equivalent to ``jax.vmap(olaf_step)``
    but the Pallas path runs one kernel launch with the switch axis folded
    into the grid (the SW1/SW2/SW3 multi-switch cycle); see
    ``repro.distributed.sharding.olaf_step_sharded`` for the shard_map
    variant that splits S over a device mesh.
    """
    if impl == "auto":
        impl = "xla" if (_interpret() or clusters.shape[1] == 0) \
            else "pallas"
    if impl == "xla":
        if send is None:
            send = jnp.ones(clusters.shape, bool)
        if screen is None:
            screen = jnp.zeros(clusters.shape, bool)
        thr = jnp.broadcast_to(jnp.asarray(reward_threshold, jnp.float32),
                               (clusters.shape[0],))
        cap = jnp.broadcast_to(
            jnp.asarray(states.cluster.shape[1] if capacity is None
                        else capacity, jnp.int32), (clusters.shape[0],))
        return jax.vmap(
            lambda st, c, w, t, r, p, th, sn, cp, scr: jax_olaf_step(
                st, c, w, t, r, p, k, th, sn, cp, None, scr)
        )(states, clusters, workers, gen_times, rewards, payloads, thr, send,
          cap, screen)
    outs = olaf_step_pallas(
        states.cluster, states.worker, states.seq, states.gen_time,
        states.reward, states.agg_count, states.replaceable, states.next_seq,
        states.n_dropped, states.n_agg, states.n_repl, states.payload,
        clusters, workers, gen_times, rewards, payloads, k, reward_threshold,
        send, capacity, states.n_screened, screen, tile_q=tile_q,
        tile_d=tile_d, interpret=_interpret())
    return _olaf_step_unpack(*outs)


def olaf_burst_multi(states: JaxQueueState, clusters, workers, gen_times,
                     rewards, payloads, reward_threshold=jnp.inf, send=None,
                     capacity=None, in_counts=None, in_replaceable=None):
    """Multi-queue enqueue-only burst with per-slot event reporting.

    Every operand carries a leading S (switch) axis: ``states`` is a
    JaxQueueState of (S, Q)/(S, Q, D)/(S,) arrays; burst operands are
    (S, U)/(S, U, D); ``reward_threshold``/``capacity`` are (S,).
    Returns ``(new_states, slots (S, U), events (S, U))`` with the
    Algorithm 1 outcome codes of :func:`jax_enqueue_burst_ex` — the entry
    the vectorized network simulator (:mod:`repro.core.vecsim`) routes its
    per-step arrival bursts through. Unlike :func:`olaf_step_multi` this
    does not drain: dequeue is driven separately by link service.
    """
    S = clusters.shape[0]
    thr = jnp.broadcast_to(
        jnp.asarray(reward_threshold, jnp.float32), (S,))
    if send is None:
        send = jnp.ones(clusters.shape, bool)
    if capacity is None:
        capacity = jnp.full((S,), states.cluster.shape[1], jnp.int32)
    else:
        capacity = jnp.broadcast_to(jnp.asarray(capacity, jnp.int32), (S,))
    if in_counts is None:
        in_counts = jnp.ones(clusters.shape, jnp.int32)
    if in_replaceable is None:
        in_replaceable = jnp.ones(clusters.shape, bool)
    return jax.vmap(
        lambda st, c, w, t, r, p, th, sn, cp, ic, ir: jax_enqueue_burst_ex(
            st, c, w, t, r, p, reward_threshold=th, send=sn, capacity=cp,
            in_counts=ic, in_replaceable=ir)
    )(states, clusters, workers, gen_times, rewards, payloads,
      thr, send, capacity, in_counts, in_replaceable)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "q_offset", "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    q_offset: int = 0, block_q: int = 512, block_k: int = 512):
    """Flash attention in the model's (B, S, H, Dh) layout (kv pre-expanded)."""
    B, Sq, H, Dh = q.shape
    qf = jnp.moveaxis(q, 2, 1).reshape(B * H, Sq, Dh)
    kf = jnp.moveaxis(k, 2, 1).reshape(B * H, k.shape[1], Dh)
    vf = jnp.moveaxis(v, 2, 1).reshape(B * H, v.shape[1], Dh)
    out = flash_attention_pallas(qf, kf, vf, causal=causal, window=window,
                                 q_offset=q_offset, block_q=block_q,
                                 block_k=block_k, interpret=_interpret())
    return jnp.moveaxis(out.reshape(B, H, Sq, Dh), 1, 2)


@functools.partial(jax.jit, static_argnames=("block_s",))
def decode_attention(q, k_cache, v_cache, pos, *, block_s: int = 512):
    """GQA decode attention. q: (B,KV,rep,Dh); caches (B,S,KV,Dh); pos (B,)."""
    return decode_attention_pallas(q, k_cache, v_cache, pos, block_s=block_s,
                                   interpret=_interpret())
