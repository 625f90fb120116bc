"""Pallas TPU kernel: OLAF opportunistic update combining (the paper's
data-plane aggregation hot-spot, re-thought for the TPU memory hierarchy).

The P4/Verilog pipeline combines one update at a time at line rate. On TPU
the equivalent operating point is a *batched* combine: a burst of U incoming
updates (an incast, §3) is merged into the Q cluster-keyed queue slots in a
single VMEM-resident pass:

    new_slot[q] = (slot[q]·count[q] + Σ_{u: cluster[u]=q ∧ gate[u]} upd[u])
                  / (count[q] + n[q])

i.e. a masked segment-sum over the update batch followed by a running-mean
renormalization — the same arithmetic as Algorithm 1 applied to a burst
(gating decisions are data-dependent scalars and stay in the JAX wrapper).

The masked segment-sum is expressed as a one-hot (Qt, U) × (U, Dt) matmul so
it runs on the MXU — there is no per-update unroll, so U scales to hundreds
of updates with a constant trace size. The matmul runs at
``Precision.HIGHEST``: the one-hot weights are small integers, so every
product is exact and the sum is an f32 sum, never a bf16 pass. Tiling: grid
over (queues × Q-tiles × D-tiles); per step the kernel holds one (U, Dt)
update tile and one (Qt, Dt) slot tile in VMEM, beside the (1, U)
``clusters``/``gate`` rows and the (Qt, 1) ``counts`` column. The kernel is
HBM-bandwidth bound by design (it must touch every incoming byte exactly
once, like the line-rate queue); the matmul FLOPs (2·Q·U·D) are far below
the MXU roofline at these shapes. Updated slot counts are produced by the
same kernel launch, and a leading S axis batches independent queues
(SW1/SW2/SW3-style multi-switch combines) in one launch.

Tiles follow the TPU's (8, 128) layout: a Q-tile is a multiple of 8 rows or
the whole queue, a D-tile a multiple of 128 lanes or the whole row. A D that
no tile divides leaves a partial last D-block; every column is computed on
its own, so the block's out-of-range lanes never reach a stored value.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_TILE_D = 512
DEFAULT_TILE_Q = 8
_HIGHEST = jax.lax.Precision.HIGHEST


def _combine_kernel(cluster_ref, gate_ref, count_ref, updates_ref, slots_ref,
                    out_ref, counts_out_ref, *, tile_q: int):
    """One (queue s, Q-tile i, D-tile j) grid step.

    cluster_ref: (1, 1, U) int32 — cluster id per update
    gate_ref:    (1, 1, U) int32 — aggregation weight per update: 0 drops
                 it, 1 is a plain (un-aggregated) update, w > 1 means the
                 update is itself the mean of w raw updates (a combined
                 packet arriving from an upstream switch) and contributes
                 with weight w — so multi-hop combining stays an exact
                 weighted mean of the raw gradients
    count_ref:   (1, Qt, 1) int32 — current agg_count per slot
    updates_ref: (1, U, Dt) tile of incoming payloads
    slots_ref:   (1, Qt, Dt) tile of the current slot payloads
    out_ref:     (1, Qt, Dt) tile of the combined slot payloads
    counts_out_ref: (1, Qt, 1) int32 — written once per Q-tile (at j == 0)
    """
    i = pl.program_id(1)
    U = updates_ref.shape[1]
    counts = count_ref[0]  # (Qt, 1)

    # weighted one-hot membership (Qt, U): each entry is the update's
    # aggregation weight, not just 1
    qids = i * tile_q + jax.lax.broadcasted_iota(jnp.int32, (tile_q, U), 0)
    onehot = jnp.where(cluster_ref[0] == qids, gate_ref[0],
                       0).astype(jnp.float32)
    hits = onehot.sum(axis=1, keepdims=True).astype(jnp.int32)  # (Qt, 1)

    acc = slots_ref[0].astype(jnp.float32) * counts.astype(jnp.float32)
    # masked segment-sum as an MXU matmul: (Qt, U) x (U, Dt)
    acc += jnp.dot(onehot, updates_ref[0].astype(jnp.float32),
                   preferred_element_type=jnp.float32, precision=_HIGHEST)
    denom = jnp.maximum(counts + hits, 1).astype(jnp.float32)
    out_ref[0] = (acc / denom).astype(out_ref.dtype)

    @pl.when(pl.program_id(2) == 0)
    def _():
        counts_out_ref[0] = counts + hits


def _pick_tile_q(Q: int, tile_q: int) -> int:
    """Largest multiple of 8 that divides ``Q`` and is at most ``tile_q``;
    the whole queue when there is none (a block spanning the full axis is
    always a legal TPU tile)."""
    for t in range(min(tile_q, Q) // 8 * 8, 0, -8):
        if Q % t == 0:
            return t
    return Q


def _pick_tile_d(D: int, tile_d: int) -> int:
    """A multiple of 128 lanes, at most ``tile_d`` (at least 128), or the
    whole row when it is no wider than that."""
    tile_d = max(128, tile_d // 128 * 128)
    return D if D <= tile_d else tile_d


def olaf_combine_pallas(slots: jnp.ndarray, counts: jnp.ndarray,
                        updates: jnp.ndarray, clusters: jnp.ndarray,
                        gate: jnp.ndarray, *, tile_q: int = DEFAULT_TILE_Q,
                        tile_d: int = DEFAULT_TILE_D, interpret: bool):
    """Fused burst combine; returns ``(new_slots, new_counts)``.

    Rank-2: slots (Q, D), counts (Q,), updates (U, D), clusters/gate (U,).
    Rank-3 (multi-queue): a leading S axis on every operand batches S
    independent queues (one per switch) in a single kernel launch.
    ``interpret=True`` runs the kernel body through the Pallas interpreter
    (any backend); ``False`` compiles it with Mosaic for a TPU.
    """
    squeeze = slots.ndim == 2
    if squeeze:
        slots, counts = slots[None], counts[None]
        updates, clusters, gate = updates[None], clusters[None], gate[None]
    S, Q, D = slots.shape
    U = updates.shape[1]
    tile_q = _pick_tile_q(Q, tile_q)
    tile_d = _pick_tile_d(D, tile_d)

    grid = (S, Q // tile_q, pl.cdiv(D, tile_d))
    kernel = functools.partial(_combine_kernel, tile_q=tile_q)
    row = pl.BlockSpec((1, 1, U), lambda s, i, j: (s, 0, 0))
    col = pl.BlockSpec((1, tile_q, 1), lambda s, i, j: (s, i, 0))
    new_slots, new_counts = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            row, row, col,
            pl.BlockSpec((1, U, tile_d), lambda s, i, j: (s, 0, j)),
            pl.BlockSpec((1, tile_q, tile_d), lambda s, i, j: (s, i, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, tile_q, tile_d), lambda s, i, j: (s, i, j)),
            col,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((S, Q, D), slots.dtype),
            jax.ShapeDtypeStruct((S, Q, 1), jnp.int32),
        ],
        interpret=interpret,
    )(clusters.astype(jnp.int32)[:, None, :], gate.astype(jnp.int32)[:, None, :],
      counts.astype(jnp.int32)[..., None], updates, slots)
    new_counts = new_counts[..., 0]
    if squeeze:
        new_slots, new_counts = new_slots[0], new_counts[0]
    return new_slots, new_counts


# ===========================================================================
# Fused enqueue kernel: Algorithm 1's gating *and* payload movement in one
# launch (the device analogue of the switch pipeline's single pass).
# ===========================================================================
# Per-update burst events — mirror repro.core.olaf_queue._EV_*.
_EV_DROP = 0
_EV_AGG = 1
_EV_RESET = 2


def alg1_resolve(cl0, wk0, sq0, gt0, rw0, cnt0, rp0, nseq0, nd0, na0, nr0,
                 ns0, thr, U, read_update, cap):
    """In-kernel Algorithm 1 scalar resolve over a U-update burst.

    The same sequential walk as ``olaf_queue._burst_resolve``, written to
    lower on the TPU VPU: a ``fori_loop`` over U carrying only (1, Q)
    metadata rows, with masked sums in place of dynamic gathers and
    min-index in place of argmax. Shared by the fused ``olaf_enqueue`` and
    the full-cycle ``olaf_step`` kernels (``repro.kernels.olaf_step``),
    which differ only in where the burst scalars come from and what runs
    after the resolve.

    ``read_update(u) -> (cluster, worker, gen_time, reward, send, screen)``
    reads one update's scalars (typically from SMEM scalar-prefetch refs);
    ``send`` is the transmission-control gate — a masked-out update is
    deferred: no queue writes, no counter changes, event ``_EV_DROP``.
    ``screen`` is the ingress payload-integrity gate (§ payload hardening):
    a sent-but-screened update is withheld exactly like a deferred one,
    except it bumps the ``n_screened`` counter so the trainer can see the
    rejected fraction.

    Returns ``(cl, wk, sq, gt, rw, cnt, rp, nseq, nd, na, nr, ns, slots_v,
    events_v, contributes, last_reset)``: the post-burst (1, Q) metadata
    rows and counters, the per-update (1, U) slot/event assignment, and
    the telescoped-mean bookkeeping consumed by the payload pass — a
    (1, U) contributes row and a (Q, 1) last-reset column, the layouts
    :func:`_burst_payload_tile` broadcasts against.

    ``cap`` (scalar) is the queue's *logical* slot count: slots at index
    >= cap never host an append, so one padded (Qmax,) buffer batches
    switches with heterogeneous per-switch slot vectors
    (``TopologySpec.queue_slots``) in a single launch.
    """
    Q = cl0.shape[1]
    qidx = jax.lax.broadcasted_iota(jnp.int32, (1, Q), 1)
    uidx = jax.lax.broadcasted_iota(jnp.int32, (1, U), 1)
    valid_slot = qidx < cap

    def body(u, carry):
        (cl, wk, sq, gt, rw, cnt, rp, nseq, nd, na, nr, ns,
         slots_v, events_v) = carry
        c, w, t, r, snd, scr = read_update(u)
        act = snd & ~scr  # screened sends are withheld before the queue
        occupied = cl >= 0
        same = occupied & (cl == c)
        hit = jnp.any(same)
        # scalar extraction from the (at most one) matching slot — a
        # masked sum instead of a dynamic gather
        w_worker = jnp.sum(jnp.where(same, wk, 0))
        w_seq = jnp.sum(jnp.where(same, sq, 0))
        w_cnt = jnp.sum(jnp.where(same, cnt, 0))
        w_repl = jnp.any(same & (rp != 0))
        w_reward = jnp.sum(jnp.where(same, rw, 0.0))
        w_gt = jnp.sum(jnp.where(same, gt, 0.0))

        swr = act & hit & w_repl & (w_worker == w)
        rdiff = r - w_reward
        do_rr = act & hit & ~swr & (rdiff > thr)
        do_rd = act & hit & ~swr & (rdiff < -thr)
        do_agg = act & hit & ~swr & ~do_rr & ~do_rd
        full = jnp.all(occupied | ~valid_slot)
        do_append = act & ~hit & ~full
        do_dropf = act & ~hit & full

        # min-index in place of argmax (lowers without gather support)
        slot_hit = jnp.min(jnp.where(same, qidx, Q))
        slot_append = jnp.min(jnp.where(~occupied & valid_slot, qidx, Q))
        slot = jnp.minimum(jnp.where(hit, slot_hit, slot_append), Q - 1)
        write = swr | do_rr | do_agg | do_append
        onehot = (qidx == slot) & write

        def put(old, new):
            return jnp.where(onehot, new, old)

        event = jnp.where(do_agg, _EV_AGG,
                          jnp.where(write, _EV_RESET, _EV_DROP))
        return (
            put(cl, c),
            put(wk, w),
            put(sq, jnp.where(hit, w_seq, nseq)),
            put(gt, jnp.where(do_agg, jnp.maximum(t, w_gt), t)),
            put(rw, jnp.where(do_agg, jnp.maximum(r, w_reward), r)),
            put(cnt, jnp.where(do_agg, w_cnt + 1, 1)),
            put(rp, (swr | do_append).astype(jnp.int32)),
            nseq + do_append.astype(jnp.int32),
            nd + (do_dropf | do_rd).astype(jnp.int32),
            na + do_agg.astype(jnp.int32),
            nr + (swr | do_rr).astype(jnp.int32),
            ns + (snd & scr).astype(jnp.int32),
            jnp.where(uidx == u, slot, slots_v),
            jnp.where(uidx == u, event.astype(jnp.int32), events_v),
        )

    carry0 = (cl0, wk0, sq0, gt0, rw0, cnt0, rp0, nseq0, nd0, na0, nr0, ns0,
              jnp.zeros((1, U), jnp.int32), jnp.zeros((1, U), jnp.int32))
    (cl, wk, sq, gt, rw, cnt, rp, nseq, nd, na, nr, ns,
     slots_v, events_v) = jax.lax.fori_loop(0, U, body, carry0)

    # telescoped-mean bookkeeping: which updates survive into the slot.
    # Built in the (Q, U) orientation, so the per-slot result comes out a
    # column and the per-update one a row without a transpose
    onehot_qu = jax.lax.broadcasted_iota(jnp.int32, (Q, U), 0) == slots_v
    is_reset = events_v == _EV_RESET
    is_agg = events_v == _EV_AGG
    last_reset = jnp.max(jnp.where(is_reset & onehot_qu, uidx, -1),
                         axis=1, keepdims=True)  # (Q, 1)
    lr_u = jnp.sum(jnp.where(onehot_qu, last_reset, 0), axis=0,
                   keepdims=True)  # (1, U)
    contributes = ((is_agg & (uidx > lr_u))
                   | (is_reset & (uidx == lr_u)))
    return (cl, wk, sq, gt, rw, cnt, rp, nseq, nd, na, nr, ns,
            slots_v, events_v, contributes, last_reset)


def _tile_rows(ref, i, tile_q: int):
    """Rows ``[i*tile_q, (i+1)*tile_q)`` of a (Q, 1) per-slot column."""
    if ref.shape[0] == tile_q:
        return ref[...]
    return ref[pl.ds(pl.multiple_of(i * tile_q, 8), tile_q), :]


def _burst_payload_tile(i, tile_q: int, slots_v, contrib, last_reset,
                        counts, updates, old):
    """Telescoped-mean burst combine of one (Qt, Dt) slot tile.

    ``slots_v``/``contrib`` (1, U) are the resolved per-update slot and
    survival flag, ``last_reset``/``counts`` (Qt, 1) the tile's last reset
    index and pre-burst agg_count. One one-hot (Qt, U) × (U, Dt)
    segment-sum on the MXU (exact: 0/1 weights at ``HIGHEST``) plus one
    blend — ``jax_enqueue_burst``'s payload arithmetic.
    """
    U = updates.shape[0]
    qids = i * tile_q + jax.lax.broadcasted_iota(jnp.int32, (tile_q, U), 0)
    seg = jnp.where((slots_v == qids) & (contrib != 0), 1.0,
                    0.0).astype(jnp.float32)  # (Qt, U)
    sums = jnp.dot(seg, updates.astype(jnp.float32),
                   preferred_element_type=jnp.float32, precision=_HIGHEST)
    n_contrib = seg.sum(axis=1, keepdims=True)
    base_n = jnp.where(last_reset < 0, counts, 0).astype(jnp.float32)
    touched = (last_reset >= 0) | (n_contrib > 0)
    denom = jnp.maximum(base_n + n_contrib, 1.0)
    old = old.astype(jnp.float32)
    return jnp.where(touched, (old * base_n + sums) / denom, old)


def _enqueue_kernel(qc_ref, ui_ref, uf_ref, qi_ref, qf_ref, cnt_ref,
                    updates_ref, slotpay_ref,
                    out_ref, meta_i_ref, meta_f_ref,
                    slots_scr, contrib_scr, lastreset_scr, *, tile_q: int):
    """One (D-tile j, Q-tile i) grid step of the fused burst enqueue.

    Scalar-prefetch SMEM operands:
      qc_ref: (1, 6) int32 — [next_seq, n_dropped, n_agg, n_repl, capacity,
                 n_screened] (capacity = the logical slot count; Q when not
                 capped)
      ui_ref: (3, U) int32 — burst [clusters, workers, screen]
      uf_ref: (3, U) f32   — burst [gen_times, rewards, reward_threshold row]
    VMEM blocks: qi (5, Q) int32 queue [cluster, worker, seq, agg_count,
    replaceable]; qf (2, Q) f32 queue [gen_time, reward]; cnt (Qt, 1) the
    tile's agg_count column; updates (U, Dt); slotpay (Qt, Dt).
    Outputs: new payload tile (Qt, Dt); meta_i (10, Q) int32 (rows 0-4 the
    qi columns, rows 5-9 the counters broadcast across Q); meta_f (2, Q)
    f32.
    VMEM scratch: per-update slot / contributes (1, U) and per-slot
    last-reset index (Q, 1), written once at the first grid step and reused
    by every later (j, i) step — TPU grid steps run sequentially on one
    core, so scratch persists across the whole grid. The grid iterates
    D-tiles outermost (Q-tiles innermost), the shared order of the
    ``olaf_step`` full-cycle kernel, whose drained-row accumulator needs
    every Q-tile of one D-tile visited consecutively.

    The scalar resolve is the shared :func:`alg1_resolve` walk; the payload
    movement is :func:`_burst_payload_tile`.
    """
    j, i = pl.program_id(0), pl.program_id(1)
    Q = qi_ref.shape[1]
    U = ui_ref.shape[1]

    @pl.when((i == 0) & (j == 0))
    def _resolve():
        def read_update(u):
            return (ui_ref[0, u], ui_ref[1, u], uf_ref[0, u], uf_ref[1, u],
                    jnp.bool_(True), ui_ref[2, u] != 0)

        (cl, wk, sq, gt, rw, cnt, rp, nseq, nd, na, nr, ns,
         slots_v, _, contributes, last_reset) = alg1_resolve(
            qi_ref[0:1, :], qi_ref[1:2, :], qi_ref[2:3, :], qf_ref[0:1, :],
            qf_ref[1:2, :], qi_ref[3:4, :], qi_ref[4:5, :],
            qc_ref[0, 0], qc_ref[0, 1], qc_ref[0, 2], qc_ref[0, 3],
            qc_ref[0, 5], uf_ref[2, 0], U, read_update, cap=qc_ref[0, 4])

        slots_scr[...] = slots_v
        contrib_scr[...] = contributes.astype(jnp.int32)
        lastreset_scr[...] = last_reset

        zero = jnp.zeros((1, Q), jnp.int32)
        for r, v in enumerate((cl, wk, sq, cnt, rp, zero + nseq, zero + nd,
                               zero + na, zero + nr, zero + ns)):
            meta_i_ref[r:r + 1, :] = v
        meta_f_ref[0:1, :] = gt
        meta_f_ref[1:2, :] = rw

    # ---- payload pass (every grid step, MXU) ----------------------------
    out_ref[...] = _burst_payload_tile(
        i, tile_q, slots_scr[...], contrib_scr[...],
        _tile_rows(lastreset_scr, i, tile_q), cnt_ref[...], updates_ref[...],
        slotpay_ref[...]).astype(out_ref.dtype)


def olaf_enqueue_pallas(cluster, worker, seq, gen_time, reward, agg_count,
                        replaceable, next_seq, n_dropped, n_agg, n_repl,
                        payload, clusters, workers, gen_times, rewards,
                        payloads, reward_threshold=float("inf"),
                        capacity=None, n_screened=0, screen=None, *,
                        tile_q: int = DEFAULT_TILE_Q,
                        tile_d: int = DEFAULT_TILE_D, interpret: bool):
    """Single-launch fused burst enqueue over raw queue-state arrays.

    Returns ``(new_payload (Q, D), meta_i (10, Q) int32, meta_f (2, Q)
    f32)`` — see :func:`_enqueue_kernel` for the packing. The
    JaxQueueState-typed wrapper lives in ``repro.kernels.ops.olaf_enqueue``.
    """
    Q, D = payload.shape
    U = clusters.shape[0]
    tile_q = _pick_tile_q(Q, tile_q)
    tile_d = _pick_tile_d(D, tile_d)
    i32, f32 = jnp.int32, jnp.float32
    if capacity is None:
        capacity = Q
    if screen is None:
        screen = jnp.zeros((U,), i32)
    qi = jnp.stack([cluster.astype(i32), worker.astype(i32), seq.astype(i32),
                    agg_count.astype(i32), replaceable.astype(i32)])
    qf = jnp.stack([gen_time.astype(f32), reward.astype(f32)])
    qc = jnp.stack([jnp.asarray(next_seq, i32), jnp.asarray(n_dropped, i32),
                    jnp.asarray(n_agg, i32), jnp.asarray(n_repl, i32),
                    jnp.asarray(capacity, i32),
                    jnp.asarray(n_screened, i32)])[None]
    ui = jnp.stack([clusters.astype(i32), workers.astype(i32),
                    screen.astype(i32)])
    uf = jnp.stack([gen_times.astype(f32), rewards.astype(f32),
                    jnp.full((U,), reward_threshold, f32)])

    grid = (pl.cdiv(D, tile_d), Q // tile_q)  # D-tiles outer, Q-tiles inner
    kernel = functools.partial(_enqueue_kernel, tile_q=tile_q)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # qc, ui, uf -> SMEM
            grid=grid,
            in_specs=[
                pl.BlockSpec((5, Q), lambda j, i, *p: (0, 0)),
                pl.BlockSpec((2, Q), lambda j, i, *p: (0, 0)),
                pl.BlockSpec((tile_q, 1), lambda j, i, *p: (i, 0)),
                pl.BlockSpec((U, tile_d), lambda j, i, *p: (0, j)),
                pl.BlockSpec((tile_q, tile_d), lambda j, i, *p: (i, j)),
            ],
            out_specs=[
                pl.BlockSpec((tile_q, tile_d), lambda j, i, *p: (i, j)),
                pl.BlockSpec((10, Q), lambda j, i, *p: (0, 0)),
                pl.BlockSpec((2, Q), lambda j, i, *p: (0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((1, U), jnp.int32),  # resolved slot per update
                pltpu.VMEM((1, U), jnp.int32),  # contributes per update
                pltpu.VMEM((Q, 1), jnp.int32),  # last reset per slot
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((Q, D), payload.dtype),
            jax.ShapeDtypeStruct((10, Q), jnp.int32),
            jax.ShapeDtypeStruct((2, Q), jnp.float32),
        ],
        interpret=interpret,
    )(qc, ui, uf, qi, qf, agg_count.astype(i32)[:, None], payloads, payload)
