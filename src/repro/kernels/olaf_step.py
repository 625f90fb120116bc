"""Pallas TPU kernel: the fused full-cycle OLAF data plane (``olaf_step``).

One launch per PS step performs what previously took two kernels plus a
top-k pass:

  1. **burst-enqueue scalar resolve** — Algorithm 1 gating for a U-update
     incast burst, the shared :func:`repro.kernels.olaf_combine.alg1_resolve`
     fori_loop over SMEM per-update scalars and (1, Q) VMEM metadata rows,
     run once at the first grid step. An optional per-update ``send`` gate (worker-side
     transmission control, §5) defers masked-out updates without touching
     the queue.
  2. **drain-k oldest-valid selection** — the k slots with the smallest
     post-enqueue sequence numbers, ties (the empty-slot sentinel) broken by
     slot index, reproducing ``jax.lax.top_k``'s ordering exactly so the
     kernel matches the ``jax_enqueue_burst → jax_dequeue_burst`` oracle
     row for row. A k-step selection loop over the (1, Q) rows, also at the
     first grid step.
  3. **payload combine + gather** — on every (Q-tile × D-tile) grid step:
     the telescoped-mean burst combine (one one-hot (Qt, U) × (U, Dt)
     segment-sum on the MXU plus a blend), then the drained rows gathered
     from the *combined* tiles by a one-hot (K, Qt) × (Qt, Dt) matmul
     accumulated across Q-tiles, and the popped slots zeroed in the new
     payload output.

VMEM scratch carries the resolved slot/contribute assignment and the drain
slot selection across grid steps (TPU grid steps run sequentially on one
core, so scratch written at a switch's first step is visible to all its
later steps). The grid iterates (S, D-tiles, Q-tiles) with Q-tiles
innermost: for a fixed D-tile every Q-tile is visited consecutively, so the
(K, Dt) drained output block stays resident in VMEM while its cross-Q-tile
accumulation runs. Only the burst's per-update scalars and the queue
counters ride in SMEM (scalar prefetch); the per-slot metadata rows live in
VMEM, where the resolve's vector ops run.

A leading S axis batches independent queues (the SW1/SW2/SW3 multi-switch
data plane) in one launch; `repro.distributed.sharding.olaf_step_sharded`
splits that axis over a device mesh with ``shard_map``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.olaf_combine import (_HIGHEST, _burst_payload_tile,
                                        _pick_tile_d, _pick_tile_q,
                                        _tile_rows, alg1_resolve)

_SENTINEL = jnp.iinfo(jnp.int32).max
_NEG_INF = float("-inf")


def _olaf_step_kernel(qc_ref, ui_ref, uf_ref, qi_ref, qf_ref, cnt_ref,
                      updates_ref, slotpay_ref,
                      out_ref, drained_ref, meta_i_ref, meta_f_ref,
                      drain_i_ref, drain_f_ref,
                      slots_scr, contrib_scr, lastreset_scr,
                      dslot_scr, popped_scr, *, tile_q: int, k: int):
    """One (queue s, D-tile j, Q-tile i) grid step of the fused cycle.

    Scalar-prefetch SMEM operands (leading S axis on all of them):
      qc_ref: (S, 1, 6) int32 — [next_seq, n_dropped, n_agg, n_repl,
                 capacity, n_screened] (capacity = the per-switch logical
                 slot count — heterogeneous ``TopologySpec.queue_slots``
                 ride in one padded (S, Qmax) launch; Q when not capped)
      ui_ref: (S, 4, U) int32 — burst [clusters, workers, send, screen]
      uf_ref: (S, 3, U) f32   — burst [gen_times, rewards, threshold row]
    VMEM blocks: qi (1, 5, Q) int32 [cluster, worker, seq, agg_count,
    replaceable]; qf (1, 2, Q) f32 [gen_time, reward]; cnt (1, Qt, 1) the
    tile's pre-burst agg_count column; updates (1, U, Dt); slotpay
    (1, Qt, Dt).
    Outputs:
      out_ref     (1, Qt, Dt) — post-enqueue, post-drain slot payload tile
      drained_ref (1, K, Dt)  — drained rows, accumulated across Q-tiles
      meta_i_ref  (1, 10, Q)  — post-drain metadata (rows 0-4) + counters
                                broadcast across Q (rows 5-9)
      meta_f_ref  (1, 2, Q)   — post-drain [gen_time, reward]
      drain_i_ref (1, 4, K)   — per drained row [cluster, worker,
                                agg_count, valid], read pre-clear
      drain_f_ref (1, 2, K)   — per drained row [gen_time, reward]
    VMEM scratch: enqueue resolve (slot/contributes per update, last reset
    per slot) and drain selection (drained slot per row, -1 when the row
    is invalid; popped flag per slot).
    """
    s, j, i = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    Q = qi_ref.shape[2]
    U = ui_ref.shape[2]

    @pl.when((j == 0) & (i == 0))
    def _resolve_and_select():
        # ---- 1. burst-enqueue scalar resolve (Algorithm 1) --------------
        def read_update(u):
            return (ui_ref[s, 0, u], ui_ref[s, 1, u], uf_ref[s, 0, u],
                    uf_ref[s, 1, u], ui_ref[s, 2, u] != 0,
                    ui_ref[s, 3, u] != 0)

        qi, qf = qi_ref[0], qf_ref[0]
        (cl, wk, sq, gt, rw, cnt, rp, nseq, nd, na, nr, ns,
         slots_v, _, contributes, last_reset) = alg1_resolve(
            qi[0:1], qi[1:2], qi[2:3], qf[0:1], qf[1:2], qi[3:4], qi[4:5],
            qc_ref[s, 0, 0], qc_ref[s, 0, 1], qc_ref[s, 0, 2],
            qc_ref[s, 0, 3], qc_ref[s, 0, 5],
            uf_ref[s, 2, 0], U, read_update, cap=qc_ref[s, 0, 4])

        slots_scr[...] = slots_v
        contrib_scr[...] = contributes.astype(jnp.int32)
        lastreset_scr[...] = last_reset

        # ---- 2. drain-k oldest-valid selection --------------------------
        # k smallest post-enqueue seqs, sentinel ties broken by slot index:
        # the same (value, index) order lax.top_k(-seq) produces, so the
        # drained rows match the two-launch oracle exactly — including the
        # stale metadata invalid rows read from sentinel slots. Each
        # selected row's metadata is read (pre-clear) by a masked sum.
        qidx = jax.lax.broadcasted_iota(jnp.int32, (1, Q), 1)
        kidx = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)
        kcol = jax.lax.broadcasted_iota(jnp.int32, (k, 1), 0)

        def select(t, carry):
            taken, popped, dcol, dsel, dcl, dwk, dcnt, dval, dgt, drw = carry
            seq_m = jnp.where(taken != 0, _SENTINEL, sq)
            m = jnp.min(seq_m)
            slot = jnp.min(jnp.where((taken == 0) & (seq_m == m), qidx, Q))
            at = qidx == slot
            c_at = jnp.sum(jnp.where(at, cl, 0))
            valid = c_at >= 0
            sel = jnp.where(valid, slot, -1)

            def put(row, v):
                return jnp.where(kidx == t, v, row)

            return (jnp.where(at, 1, taken),
                    jnp.where(at & valid, 1, popped),
                    jnp.where(kcol == t, sel, dcol), put(dsel, sel),
                    put(dcl, c_at), put(dwk, jnp.sum(jnp.where(at, wk, 0))),
                    put(dcnt, jnp.sum(jnp.where(at, cnt, 0))),
                    put(dval, valid.astype(jnp.int32)),
                    put(dgt, jnp.sum(jnp.where(at, gt, 0.0))),
                    put(drw, jnp.sum(jnp.where(at, rw, 0.0))))

        zq = jnp.zeros((1, Q), jnp.int32)
        zk = jnp.zeros((1, k), jnp.int32)
        zkf = jnp.zeros((1, k), jnp.float32)
        (_, popped, dcol, dsel, dcl, dwk, dcnt, dval, dgt,
         drw) = jax.lax.fori_loop(
            0, k, select, (zq, zq, jnp.zeros((k, 1), jnp.int32), zk, zk, zk,
                           zk, zk, zkf, zkf))
        dslot_scr[...] = dcol
        popped_scr[...] = jnp.max(
            jnp.where(jax.lax.broadcasted_iota(jnp.int32, (Q, k), 0) == dsel,
                      1, 0), axis=1, keepdims=True)  # (Q, 1)

        for r, v in enumerate((dcl, dwk, dcnt, dval)):
            drain_i_ref[0, r:r + 1, :] = v
        drain_f_ref[0, 0:1, :] = dgt
        drain_f_ref[0, 1:2, :] = drw

        # ---- post-drain metadata (popped slots cleared; gen_time kept,
        # matching jax_dequeue_burst) -------------------------------------
        pop = popped != 0
        for r, v in enumerate((
                jnp.where(pop, -1, cl), jnp.where(pop, -1, wk),
                jnp.where(pop, _SENTINEL, sq), jnp.where(pop, 0, cnt),
                jnp.where(pop, 0, rp), zq + nseq, zq + nd, zq + na,
                zq + nr, zq + ns)):
            meta_i_ref[0, r:r + 1, :] = v
        meta_f_ref[0, 0:1, :] = gt
        meta_f_ref[0, 1:2, :] = jnp.where(pop, _NEG_INF, rw)

    # ---- 3. payload pass (every grid step, MXU) --------------------------
    combined = _burst_payload_tile(
        i, tile_q, slots_scr[...], contrib_scr[...],
        _tile_rows(lastreset_scr, i, tile_q), cnt_ref[0], updates_ref[0],
        slotpay_ref[0])  # post-enqueue, pre-drain tile

    # drained-row gather from the combined tile: each row selects exactly
    # one slot, so the cross-tile accumulation is exact (single-term sums;
    # HIGHEST keeps the one-hot matmul an f32 copy)
    tile_qids = i * tile_q + jax.lax.broadcasted_iota(
        jnp.int32, (k, tile_q), 1)
    onehot_k = jnp.where(dslot_scr[...] == tile_qids, 1.0,
                         0.0).astype(jnp.float32)  # (K, Qt)
    part = jnp.dot(onehot_k, combined, preferred_element_type=jnp.float32,
                   precision=_HIGHEST)  # (K, Dt)
    popped_tile = _tile_rows(popped_scr, i, tile_q) != 0  # (Qt, 1)

    out_ref[0] = jnp.where(popped_tile, 0.0, combined).astype(out_ref.dtype)

    @pl.when(i == 0)
    def _init_drained():
        drained_ref[0] = part.astype(drained_ref.dtype)

    @pl.when(i != 0)
    def _accum_drained():
        drained_ref[0] = drained_ref[0] + part.astype(drained_ref.dtype)


def olaf_step_pallas(cluster, worker, seq, gen_time, reward, agg_count,
                     replaceable, next_seq, n_dropped, n_agg, n_repl,
                     payload, clusters, workers, gen_times, rewards,
                     payloads, k: int, reward_threshold=float("inf"),
                     send=None, capacity=None, n_screened=0, screen=None,
                     *, tile_q: int = 8, tile_d: int = 512,
                     interpret: bool):
    """Single-launch fused enqueue→drain cycle over raw queue-state arrays.

    Rank-2 ``payload (Q, D)`` runs one queue; a leading S axis on every
    operand (``payload (S, Q, D)``, scalars ``(S,)``) batches S independent
    queues in one launch with the switch axis folded into the Pallas grid.
    Returns ``(new_payload, drained_payload (…, K, D), meta_i (…, 10, Q),
    meta_f (…, 2, Q), drain_i (…, 4, K), drain_f (…, 2, K))`` — see
    :func:`_olaf_step_kernel` for the packing. The JaxQueueState-typed
    wrapper lives in ``repro.kernels.ops.olaf_step``. ``interpret=True``
    runs the kernel body through the Pallas interpreter (any backend);
    ``False`` compiles it with Mosaic for a TPU.
    """
    squeeze = payload.ndim == 2
    if squeeze:
        (cluster, worker, seq, gen_time, reward, agg_count, replaceable,
         payload, clusters, workers, gen_times, rewards, payloads) = (
            x[None] for x in (cluster, worker, seq, gen_time, reward,
                              agg_count, replaceable, payload, clusters,
                              workers, gen_times, rewards, payloads))
        next_seq, n_dropped, n_agg, n_repl, n_screened = (
            jnp.asarray(x)[None] for x in (next_seq, n_dropped, n_agg,
                                           n_repl, n_screened))
        if send is not None:
            send = send[None]
        if screen is not None:
            screen = screen[None]
    S, Q, D = payload.shape
    U = clusters.shape[1]
    k = min(int(k), Q)
    tile_q = _pick_tile_q(Q, tile_q)
    tile_d = _pick_tile_d(D, tile_d)
    i32, f32 = jnp.int32, jnp.float32
    if send is None:
        send = jnp.ones((S, U), i32)
    if screen is None:
        screen = jnp.zeros((S, U), i32)
    cap = jnp.broadcast_to(
        jnp.asarray(Q if capacity is None else capacity, i32), (S,))
    nscr = jnp.broadcast_to(jnp.asarray(n_screened, i32), (S,))
    qi = jnp.stack([cluster.astype(i32), worker.astype(i32), seq.astype(i32),
                    agg_count.astype(i32), replaceable.astype(i32)], axis=1)
    qf = jnp.stack([gen_time.astype(f32), reward.astype(f32)], axis=1)
    qc = jnp.stack([jnp.asarray(next_seq, i32), jnp.asarray(n_dropped, i32),
                    jnp.asarray(n_agg, i32), jnp.asarray(n_repl, i32), cap,
                    nscr], axis=1)[:, None, :]
    ui = jnp.stack([clusters.astype(i32), workers.astype(i32),
                    send.astype(i32), screen.astype(i32)], axis=1)
    uf = jnp.stack([gen_times.astype(f32), rewards.astype(f32),
                    jnp.full((S, U), reward_threshold, f32)], axis=1)

    # Q-tiles innermost (see module doc)
    grid = (S, pl.cdiv(D, tile_d), Q // tile_q)
    kernel = functools.partial(_olaf_step_kernel, tile_q=tile_q, k=k)

    def whole(rows, cols):
        return pl.BlockSpec((1, rows, cols), lambda s, j, i, *p: (s, 0, 0))

    outs = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # qc, ui, uf -> SMEM
            grid=grid,
            in_specs=[
                whole(5, Q), whole(2, Q),
                pl.BlockSpec((1, tile_q, 1), lambda s, j, i, *p: (s, i, 0)),
                pl.BlockSpec((1, U, tile_d), lambda s, j, i, *p: (s, 0, j)),
                pl.BlockSpec((1, tile_q, tile_d),
                             lambda s, j, i, *p: (s, i, j)),
            ],
            out_specs=[
                pl.BlockSpec((1, tile_q, tile_d),
                             lambda s, j, i, *p: (s, i, j)),
                pl.BlockSpec((1, k, tile_d), lambda s, j, i, *p: (s, 0, j)),
                whole(10, Q), whole(2, Q), whole(4, k), whole(2, k),
            ],
            scratch_shapes=[
                pltpu.VMEM((1, U), jnp.int32),  # resolved slot per update
                pltpu.VMEM((1, U), jnp.int32),  # contributes per update
                pltpu.VMEM((Q, 1), jnp.int32),  # last reset per slot
                pltpu.VMEM((k, 1), jnp.int32),  # drained slot per row
                pltpu.VMEM((Q, 1), jnp.int32),  # popped flag per slot
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((S, Q, D), payload.dtype),
            jax.ShapeDtypeStruct((S, k, D), payload.dtype),
            jax.ShapeDtypeStruct((S, 10, Q), jnp.int32),
            jax.ShapeDtypeStruct((S, 2, Q), jnp.float32),
            jax.ShapeDtypeStruct((S, 4, k), jnp.int32),
            jax.ShapeDtypeStruct((S, 2, k), jnp.float32),
        ],
        interpret=interpret,
    )(qc, ui, uf, qi, qf, agg_count.astype(i32)[..., None], payloads,
      payload)
    if squeeze:
        outs = [o[0] for o in outs]
    return tuple(outs)
