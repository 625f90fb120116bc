"""OlafQueue — the paper's alternative queue design (§4, Algorithm 1).

Two interchangeable implementations:

  * :class:`PyOlafQueue` / :class:`PyFifoQueue` — event-driven reference
    used by the discrete-event network simulator (``core/netsim.py``) and
    as the oracle for property tests.
  * :func:`jax_enqueue` / :func:`jax_dequeue` over :class:`JaxQueueState`
    — a fully jittable struct-of-arrays version used on-device inside the
    async trainer and mirrored by the Pallas ``olaf_combine`` kernel.

Semantics (paper §4 + §12.1):
  - at most one update per cluster in the queue (plus momentarily a second
    one when the first is *locked*, i.e. head-of-line and in transmission);
  - incoming update whose cluster is present: reward-gated aggregate /
    replace / drop, written back at the waiting update's position;
  - same-worker replacement only while ``replace_flag`` is set (un-aggregated);
  - append at tail if the cluster is absent and the queue is not full;
  - drop only if full and no same-cluster update is waiting.
Dequeue is strictly sequential (FIFO over slot sequence numbers); an
aggregated/replaced update inherits the old update's departure position.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Deque, Dict, List, Optional, Tuple

import numpy as np

from repro.core.aggregation import Action, Update, aggregate, gate, replace


class QueueStats:
    """Counters shared by both queue flavours (Tab. 1 columns)."""

    def __init__(self) -> None:
        self.enqueued = 0
        self.dropped = 0
        self.aggregations = 0
        self.replacements = 0
        self.reward_drops = 0
        self.departed = 0

    def as_dict(self) -> Dict[str, int]:
        return dict(
            enqueued=self.enqueued, dropped=self.dropped,
            aggregations=self.aggregations, replacements=self.replacements,
            reward_drops=self.reward_drops, departed=self.departed,
        )


class PyFifoQueue:
    """Classical tail-drop FIFO — the paper's baseline."""

    def __init__(self, capacity: int) -> None:
        self.capacity = capacity
        self._q: Deque[Update] = deque()
        self.stats = QueueStats()

    def __len__(self) -> int:
        return len(self._q)

    def enqueue(self, upd: Update) -> bool:
        if len(self._q) >= self.capacity:
            self.stats.dropped += 1
            return False
        self._q.append(upd)
        self.stats.enqueued += 1
        return True

    def peek(self) -> Optional[Update]:
        return self._q[0] if self._q else None

    def dequeue(self) -> Optional[Update]:
        if not self._q:
            return None
        self.stats.departed += 1
        return self._q.popleft()


class PyOlafQueue:
    """Reference OlafQueue (Algorithm 1 + §12.1 head-lock corner case).

    Every operation is O(1): the deque holds departure order, and
    ``_by_cluster`` maps each cluster to its *unlocked* waiting update (the
    Olaf invariant guarantees at most one), replacing the per-enqueue linear
    scan. Combines mutate the waiting ``Update`` in place so its identity —
    and hence its deque position — is preserved.
    """

    def __init__(self, capacity: int, reward_threshold: Optional[float] = None) -> None:
        self.capacity = capacity
        self.reward_threshold = reward_threshold
        self._q: Deque[Update] = deque()  # kept sorted by seq (departure order)
        self._by_cluster: Dict[int, Update] = {}  # cluster -> unlocked waiting
        self._seq = 0
        self._locked_seq: Optional[int] = None  # head update in transmission
        self.stats = QueueStats()

    # -- introspection ----------------------------------------------------
    def __len__(self) -> int:
        return len(self._q)

    def clusters(self) -> List[int]:
        return [u.cluster_id for u in self._q]

    def occupancy(self) -> int:
        return len(self._q)

    # -- §12.1: the head update may be locked while serializing ----------
    def lock_head(self) -> None:
        if self._q:
            head = self._q[0]
            self._locked_seq = head.seq
            # a locked head can no longer be combined with
            if self._by_cluster.get(head.cluster_id) is head:
                del self._by_cluster[head.cluster_id]

    @staticmethod
    def _overwrite(waiting: Update, new: Update) -> None:
        """Write ``new``'s fields into ``waiting`` so the object (and its
        deque position / cluster-map entry) survives the combine."""
        waiting.__dict__.update(new.__dict__)

    # -- Algorithm 1 ------------------------------------------------------
    def enqueue(self, upd: Update) -> bool:
        """Returns True iff the update's information is retained in the queue."""
        waiting = self._by_cluster.get(upd.cluster_id)
        if waiting is not None:
            if waiting.replaceable and waiting.worker_id == upd.worker_id:
                # Alg.1 lines 9-10: same-worker, un-aggregated -> replace.
                new = replace(waiting, upd)
                new.replaceable = True  # still a single un-aggregated update
                self._overwrite(waiting, new)
                self.stats.replacements += 1
                return True
            act = gate(upd.reward, waiting.reward, self.reward_threshold)
            if act is Action.DROP:
                self.stats.reward_drops += 1
                self.stats.dropped += 1
                return False
            if act is Action.REPLACE:
                new = replace(waiting, upd)
                new.replaceable = False  # reward-replace counts as a combine event
                self._overwrite(waiting, new)
                self.stats.replacements += 1
                return True
            self._overwrite(waiting, aggregate(waiting, upd))  # Alg.1 lines 12/16
            self.stats.aggregations += 1
            return True
        if len(self._q) >= self.capacity:
            self.stats.dropped += 1  # Alg.1 line 22
            return False
        upd.seq = self._seq  # Alg.1 lines 18-20: append at tail
        self._seq += 1
        self._q.append(upd)
        self._by_cluster[upd.cluster_id] = upd
        self.stats.enqueued += 1
        return True

    def classify_batch(self, updates: List[Update]) -> List[str]:
        """Replay Algorithm 1 for a whole window of updates in one call.

        Returns the per-update stats-delta classification — ``"append"`` /
        ``"agg"`` / ``"replace"`` / ``"drop"`` — resolved from the counter
        deltas of each :meth:`enqueue`, so a window consumer (the hybrid
        control-plane replay) pays one Python call per transmission window
        instead of one per queue event.
        """
        out: List[str] = []
        st = self.stats
        for upd in updates:
            before = (st.aggregations, st.replacements, st.enqueued,
                      st.dropped)
            self.enqueue(upd)
            if st.dropped != before[3]:
                out.append("drop")
            elif st.enqueued != before[2]:
                out.append("append")
            elif st.replacements != before[1]:
                out.append("replace")
            else:
                out.append("agg")
        return out

    def enqueue_batch(self, updates: List[Update]) -> List[bool]:
        """Batched :meth:`enqueue`; True per update whose information is
        retained (anything but a drop)."""
        return [ev != "drop" for ev in self.classify_batch(updates)]

    def peek(self) -> Optional[Update]:
        return self._q[0] if self._q else None

    def dequeue(self) -> Optional[Update]:
        if not self._q:
            return None
        self.stats.departed += 1
        head = self._q.popleft()
        if self._locked_seq is not None and head.seq == self._locked_seq:
            self._locked_seq = None
        if self._by_cluster.get(head.cluster_id) is head:
            del self._by_cluster[head.cluster_id]
        return head


def burst_contribution_mask(slots: List[int], events: List[str]
                            ) -> Tuple[List[bool], Dict[int, int]]:
    """Host-side telescoped-mean contribution rule shared with
    :func:`_burst_resolve`.

    For a window of ``(slot, event)`` assignments with ``event`` in
    ``{"agg", "reset"}``, only the *last* reset per slot and the aggregates
    after it contribute to the slot's combined payload — everything written
    before that reset was overwritten. Returns ``(contributes, last_reset)``
    where ``last_reset`` maps each reset slot to the window index of its
    final reset (the slot restarts from that update).
    """
    last_reset: Dict[int, int] = {}
    for u, (slot, event) in enumerate(zip(slots, events)):
        if event == "reset":
            last_reset[slot] = u
    contributes = []
    for u, (slot, event) in enumerate(zip(slots, events)):
        lr = last_reset.get(slot, -1)
        contributes.append((u > lr) if event == "agg" else (u == lr))
    return contributes, last_reset


# ===========================================================================
# Jittable struct-of-arrays queue (device-resident PS combining buffer).
# ===========================================================================
import jax
import jax.numpy as jnp


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class JaxQueueState:
    """Fixed-capacity OlafQueue state as a pytree of arrays.

    ``payload`` is ``(capacity, dim)``; empty slots have ``cluster == -1``.
    Departure order is the slot with the smallest ``seq``.
    """

    cluster: jnp.ndarray  # int32[Q]
    worker: jnp.ndarray  # int32[Q]
    seq: jnp.ndarray  # int32[Q], INT32_MAX for empty
    gen_time: jnp.ndarray  # float32[Q]
    reward: jnp.ndarray  # float32[Q]
    agg_count: jnp.ndarray  # int32[Q]
    replaceable: jnp.ndarray  # bool[Q]
    payload: jnp.ndarray  # float32[Q, D]
    next_seq: jnp.ndarray  # int32[] monotone counter
    # counters (Tab. 1)
    n_dropped: jnp.ndarray
    n_agg: jnp.ndarray
    n_repl: jnp.ndarray
    # payload-integrity counter: burst rows rejected by the ingress screen
    # (non-finite / norm-gate); defaulted so pre-screening constructions
    # stay valid pytrees
    n_screened: jnp.ndarray = dataclasses.field(
        default_factory=lambda: jnp.zeros((), jnp.int32))


_EMPTY_SEQ = jnp.iinfo(jnp.int32).max


def jax_queue_init(capacity: int, dim: int, dtype=jnp.float32) -> JaxQueueState:
    return JaxQueueState(
        cluster=-jnp.ones((capacity,), jnp.int32),
        worker=-jnp.ones((capacity,), jnp.int32),
        seq=jnp.full((capacity,), _EMPTY_SEQ, jnp.int32),
        gen_time=jnp.zeros((capacity,), jnp.float32),
        reward=jnp.full((capacity,), -jnp.inf, jnp.float32),
        agg_count=jnp.zeros((capacity,), jnp.int32),
        replaceable=jnp.zeros((capacity,), bool),
        payload=jnp.zeros((capacity, dim), dtype),
        next_seq=jnp.zeros((), jnp.int32),
        n_dropped=jnp.zeros((), jnp.int32),
        n_agg=jnp.zeros((), jnp.int32),
        n_repl=jnp.zeros((), jnp.int32),
        n_screened=jnp.zeros((), jnp.int32),
    )


def jax_enqueue(state: JaxQueueState, cluster: jnp.ndarray, worker: jnp.ndarray,
                gen_time: jnp.ndarray, reward: jnp.ndarray, payload: jnp.ndarray,
                reward_threshold: float = jnp.inf,
                capacity=None) -> JaxQueueState:
    """Jittable Algorithm 1 for a single incoming update.

    ``reward_threshold=inf`` disables gating. All branches are computed with
    masks/`jnp.where` so the function is trace-once / fixed-shape.
    ``capacity`` (static int or traced scalar, default the buffer size Q)
    caps the *logical* slot count: slots at index >= capacity are never
    appended into, so one padded ``(Qmax,)`` buffer can host switches with
    heterogeneous per-switch slot vectors (``TopologySpec.queue_slots``).
    """
    Q = state.cluster.shape[0]
    valid_slot = jnp.arange(Q) < (Q if capacity is None else capacity)
    occupied = state.cluster >= 0
    same_cluster = occupied & (state.cluster == cluster)
    hit = jnp.any(same_cluster)
    slot_hit = jnp.argmax(same_cluster)  # valid only when hit

    w_reward = state.reward[slot_hit]
    w_repl = state.replaceable[slot_hit]
    w_worker = state.worker[slot_hit]
    w_cnt = state.agg_count[slot_hit]

    same_worker_replace = hit & w_repl & (w_worker == worker)
    rdiff = reward - w_reward
    do_reward_replace = hit & ~same_worker_replace & (rdiff > reward_threshold)
    do_reward_drop = hit & ~same_worker_replace & (rdiff < -reward_threshold)
    do_aggregate = hit & ~same_worker_replace & ~do_reward_replace & ~do_reward_drop

    full = jnp.all(occupied | ~valid_slot)
    do_append = ~hit & ~full
    do_drop_full = ~hit & full

    # ---- payload combine -------------------------------------------------
    w_payload = state.payload[slot_hit]
    agg_payload = (w_payload * w_cnt.astype(payload.dtype)
                   + payload) / (w_cnt + 1).astype(payload.dtype)

    # ---- slot selection ---------------------------------------------------
    # append slot: first empty *logical* slot (argmax over ~occupied)
    slot_append = jnp.argmax(~occupied & valid_slot)
    slot = jnp.where(hit, slot_hit, slot_append)
    write = same_worker_replace | do_reward_replace | do_aggregate | do_append

    onehot = (jnp.arange(state.cluster.shape[0]) == slot) & write

    def put(old, new):
        return jnp.where(onehot, new, old)

    new_seq_val = jnp.where(hit, state.seq[slot_hit], state.next_seq)
    new_state = JaxQueueState(
        cluster=put(state.cluster, cluster),
        worker=put(state.worker, worker),
        seq=put(state.seq, new_seq_val),
        gen_time=put(state.gen_time, jnp.where(do_aggregate, jnp.maximum(gen_time, state.gen_time[slot_hit]), gen_time)),
        reward=put(state.reward, jnp.where(do_aggregate, jnp.maximum(reward, w_reward), reward)),
        agg_count=put(state.agg_count, jnp.where(do_aggregate, w_cnt + 1, 1)),
        replaceable=put(state.replaceable, same_worker_replace | do_append),
        payload=jnp.where(onehot[:, None], jnp.where(do_aggregate, agg_payload, payload)[None, :], state.payload),
        next_seq=state.next_seq + do_append.astype(jnp.int32),
        n_dropped=state.n_dropped + (do_drop_full | do_reward_drop).astype(jnp.int32),
        n_agg=state.n_agg + do_aggregate.astype(jnp.int32),
        n_repl=state.n_repl + (same_worker_replace | do_reward_replace).astype(jnp.int32),
        n_screened=state.n_screened,
    )
    return new_state


def jax_dequeue(state: JaxQueueState) -> Tuple[JaxQueueState, Dict[str, jnp.ndarray]]:
    """Pop the slot with the smallest sequence number (FIFO order)."""
    slot = jnp.argmin(state.seq)
    valid = state.cluster[slot] >= 0
    out = dict(
        valid=valid,
        cluster=state.cluster[slot],
        worker=state.worker[slot],
        gen_time=state.gen_time[slot],
        reward=state.reward[slot],
        agg_count=state.agg_count[slot],
        payload=state.payload[slot],
    )
    onehot = (jnp.arange(state.cluster.shape[0]) == slot) & valid

    new_state = dataclasses.replace(
        state,
        cluster=jnp.where(onehot, -1, state.cluster),
        worker=jnp.where(onehot, -1, state.worker),
        seq=jnp.where(onehot, _EMPTY_SEQ, state.seq),
        reward=jnp.where(onehot, -jnp.inf, state.reward),
        agg_count=jnp.where(onehot, 0, state.agg_count),
        replaceable=jnp.where(onehot, False, state.replaceable),
        payload=jnp.where(onehot[:, None], 0.0, state.payload),
    )
    return new_state, out


def jax_dequeue_burst(state: JaxQueueState, k: int
                      ) -> Tuple[JaxQueueState, Dict[str, jnp.ndarray]]:
    """Drain-k: pop the ``k`` oldest valid slots in one fixed-shape pass.

    Equivalent to ``k`` repeated :func:`jax_dequeue` calls (the oracle), but
    the payload block is produced by a single one-hot ``(k, Q) × (Q, D)``
    gather matmul instead of ``k`` sequential ``(Q, D)`` re-materializations
    — O(Q·D + k·D) bytes moved instead of O(k·Q·D).

    Returns ``(new_state, out)`` where every ``out`` entry has a leading
    ``k`` axis in FIFO order (row 0 = oldest). ``out['valid']`` is a prefix
    mask: occupied slots sort before empty ones (their ``seq`` is smaller
    than the empty sentinel), so once a row is invalid all later rows are
    too. ``out['n_valid']`` is the number of updates actually popped.
    """
    Q = state.cluster.shape[0]
    k = min(int(k), Q)
    # k smallest seqs == top-k of -seq. Valid slots have unique seq (the
    # monotone next_seq counter) strictly below the empty sentinel, so the
    # valid rows form a FIFO-ordered prefix; sentinel ties are broken by
    # slot index, which is irrelevant because those rows are masked invalid.
    _, slots = jax.lax.top_k(-state.seq, k)
    valid = state.cluster[slots] >= 0
    # one-hot gather (k, Q); invalid rows are zeroed so their payload is 0
    # and they cannot clear a live slot. HIGHEST keeps the matmul an exact
    # f32 copy on a TPU, whose default f32 matmul takes one bf16 pass
    onehot = ((slots[:, None] == jnp.arange(Q, dtype=slots.dtype)[None, :])
              & valid[:, None])
    payload = jnp.einsum("kq,qd->kd", onehot.astype(state.payload.dtype),
                         state.payload, precision=jax.lax.Precision.HIGHEST)
    out = dict(
        valid=valid,
        n_valid=valid.sum(),
        cluster=state.cluster[slots],
        worker=state.worker[slots],
        gen_time=state.gen_time[slots],
        reward=state.reward[slots],
        agg_count=state.agg_count[slots],
        payload=payload,
    )
    popped = jnp.any(onehot, axis=0)  # (Q,)
    new_state = dataclasses.replace(
        state,
        cluster=jnp.where(popped, -1, state.cluster),
        worker=jnp.where(popped, -1, state.worker),
        seq=jnp.where(popped, _EMPTY_SEQ, state.seq),
        reward=jnp.where(popped, -jnp.inf, state.reward),
        agg_count=jnp.where(popped, 0, state.agg_count),
        replaceable=jnp.where(popped, False, state.replaceable),
        payload=jnp.where(popped[:, None], 0.0, state.payload),
    )
    return new_state, out


def jax_enqueue_batch(state: JaxQueueState, clusters, workers, gen_times,
                      rewards, payloads, reward_threshold: float = jnp.inf,
                      capacity=None) -> JaxQueueState:
    """Sequential (scan) batch enqueue — an incast burst hitting the queue.

    Kept as the slow-path oracle for :func:`jax_enqueue_burst`: each scan step
    re-materializes the whole ``(Q, D)`` payload, so an U-update burst moves
    ``O(U · Q · D)`` bytes. Use it to prove equivalence, not in hot loops.
    """

    def body(st, xs):
        c, w, t, r, p = xs
        return jax_enqueue(st, c, w, t, r, p, reward_threshold,
                           capacity), None

    state, _ = jax.lax.scan(body, state, (clusters, workers, gen_times, rewards, payloads))
    return state


# Per-update burst events (scalar resolve output).
_EV_DROP = 0  # full-queue or reward-gated drop: payload discarded
_EV_AGG = 1  # running-mean aggregate into the target slot
_EV_RESET = 2  # slot payload restarts from this update (append / replace)


def _burst_resolve(state: JaxQueueState, clusters, workers, gen_times, rewards,
                   reward_threshold, send=None, capacity=None, screen=None,
                   in_counts=None, in_replaceable=None):
    """Scalar half of the burst: Algorithm 1 decisions for U updates.

    A ``lax.scan`` over the burst carrying only the ``(Q,)`` metadata columns
    — never the ``(Q, D)`` payload — so it costs O(U·Q) scalar ops total.
    Emits the per-update ``(slot, event)`` assignment consumed by the payload
    pass, plus the fully-updated metadata/counters. The payload pass keeps
    only the last reset per slot and the aggs after it —
    :func:`burst_contribution_mask` is the host-side mirror of that rule
    (used by the hybrid window replay).

    ``send`` is an optional (U,) gate from worker-side transmission control
    (§5): a masked-out update is *deferred*, not dropped — it touches neither
    the queue nor the drop counter (the worker keeps training locally and its
    next update subsumes this one).

    ``screen`` is an optional (U,) ingress-screening mask (True = screened
    out as corrupt — non-finite or norm-gate rejection, see
    :func:`jax_screen_mask`): a screened update never touches the queue
    either, but it is counted in ``n_screened`` — and, unlike a deferred
    one, the worker-side txctl machinery treats the missing ACK as a NACK
    and retransmits the clean cached copy.

    ``in_counts`` is an optional (U,) int vector of per-update aggregation
    weights: an incoming row that is itself the running mean of ``k``
    worker updates (a multi-hop forward out of an upstream switch)
    contributes with weight ``k`` to the slot mean and adds ``k`` to the
    slot's ``agg_count``. The default of all-ones reproduces the
    single-hop semantics bitwise.
    """
    if send is None:
        send = jnp.ones(clusters.shape, bool)
    if screen is None:
        screen = jnp.zeros(clusters.shape, bool)
    if in_counts is None:
        in_counts = jnp.ones(clusters.shape, jnp.int32)
    if in_replaceable is None:
        in_replaceable = jnp.ones(clusters.shape, bool)
    Q = state.cluster.shape[0]
    # capacity is a COUNT, not a slot region: one padded (Qmax,) buffer
    # serves heterogeneous per-switch slot counts, and a caller whose
    # effective capacity fluctuates (vecsim reserves one unit for the
    # in-service packet) may leave holes at any index — the full check
    # must match the count-based `len(queue) >= capacity` of the Python
    # reference, not "every slot below capacity occupied"
    cap_count = Q if capacity is None else capacity
    carry = (state.cluster, state.worker, state.seq, state.gen_time,
             state.reward, state.agg_count, state.replaceable, state.next_seq,
             state.n_dropped, state.n_agg, state.n_repl, state.n_screened)

    def body(carry, xs):
        cl, wk, sq, gt, rw, cnt, rp, nseq, nd, na, nr, ns = carry
        c, w, t, r, snd, scr, icnt, irp = xs
        act = snd & ~scr  # sent AND admitted by the ingress screen
        occupied = cl >= 0
        same_cluster = occupied & (cl == c)
        hit = jnp.any(same_cluster)
        slot_hit = jnp.argmax(same_cluster)

        same_worker_replace = act & hit & rp[slot_hit] & (wk[slot_hit] == w)
        rdiff = r - rw[slot_hit]
        do_reward_replace = act & hit & ~same_worker_replace & (rdiff > reward_threshold)
        do_reward_drop = act & hit & ~same_worker_replace & (rdiff < -reward_threshold)
        do_aggregate = act & hit & ~same_worker_replace & ~do_reward_replace & ~do_reward_drop

        full = jnp.sum(occupied) >= cap_count
        do_append = act & ~hit & ~full
        do_drop_full = act & ~hit & full

        slot = jnp.where(hit, slot_hit, jnp.argmax(~occupied))
        write = same_worker_replace | do_reward_replace | do_aggregate | do_append
        onehot = (jnp.arange(cl.shape[0]) == slot) & write

        def put(old, new):
            return jnp.where(onehot, new, old)

        event = jnp.where(do_aggregate, _EV_AGG,
                          jnp.where(write, _EV_RESET, _EV_DROP))
        new_carry = (
            put(cl, c),
            put(wk, w),
            put(sq, jnp.where(hit, sq[slot_hit], nseq)),
            put(gt, jnp.where(do_aggregate, jnp.maximum(t, gt[slot_hit]), t)),
            put(rw, jnp.where(do_aggregate, jnp.maximum(r, rw[slot_hit]), r)),
            put(cnt, jnp.where(do_aggregate, cnt[slot_hit] + icnt, icnt)),
            # replaceable after the write: same-worker replace restores True
            # (still one un-aggregated update); appends inherit the incoming
            # update's own flag (a multi-hop forward that is already a merge
            # arrives un-replaceable); aggregation and reward-replace are
            # combine events and always clear it
            put(rp, same_worker_replace | (do_append & irp)),
            nseq + do_append.astype(jnp.int32),
            nd + (do_drop_full | do_reward_drop).astype(jnp.int32),
            na + do_aggregate.astype(jnp.int32),
            nr + (same_worker_replace | do_reward_replace).astype(jnp.int32),
            ns + (snd & scr).astype(jnp.int32),
        )
        return new_carry, (slot.astype(jnp.int32), event.astype(jnp.int32))

    carry, (slots, events) = jax.lax.scan(
        body, carry, (clusters, workers, gen_times, rewards,
                      send.astype(bool), screen.astype(bool),
                      in_counts.astype(jnp.int32),
                      in_replaceable.astype(bool)))
    return carry, slots, events


def jax_enqueue_burst_ex(state: JaxQueueState, clusters, workers, gen_times,
                         rewards, payloads, reward_threshold: float = jnp.inf,
                         send=None, capacity=None, screen=None, in_counts=None,
                         in_replaceable=None):
    """:func:`jax_enqueue_burst` plus the per-update ``(slots, events)``
    assignment from :func:`_burst_resolve` — the raw Algorithm 1 decisions
    consumers like the vectorized network simulator (``core/vecsim.py``)
    need to derive append/replace/subsumption accounting without a second
    rule set (see :func:`classify_slot_events`). Returns
    ``(new_state, slots, events)``.
    """
    Q = state.cluster.shape[0]
    U = clusters.shape[0]
    if U == 0:  # empty burst (drain-only cycle): nothing to resolve
        empty = jnp.zeros((0,), jnp.int32)
        return state, empty, empty
    if in_counts is None:
        in_counts = jnp.ones(clusters.shape, jnp.int32)
    carry, slots, events = _burst_resolve(
        state, clusters, workers, gen_times, rewards, reward_threshold, send,
        capacity, screen, in_counts, in_replaceable)
    (cl, wk, sq, gt, rw, cnt, rp, nseq, nd, na, nr, ns) = carry

    u_idx = jnp.arange(U, dtype=jnp.int32)
    onehot = slots[:, None] == jnp.arange(Q, dtype=jnp.int32)[None, :]  # (U, Q)
    is_reset = events == _EV_RESET
    is_agg = events == _EV_AGG
    # Last reset per slot: everything written before it was overwritten.
    last_reset = jnp.max(
        jnp.where(is_reset[:, None] & onehot, u_idx[:, None], -1), axis=0)  # (Q,)
    contributes = ((is_agg & (u_idx > last_reset[slots]))
                   | (is_reset & (u_idx == last_reset[slots])))
    # Weight every contribution by its own aggregation count, so a forward
    # that is already the mean of k updates re-enters the slot mean with
    # weight k (all-ones in_counts degenerates to the 0/1 segment matrix).
    seg = ((onehot & contributes[:, None]).astype(jnp.float32)
           * in_counts.astype(jnp.float32)[:, None])  # (U, Q)
    sums = jnp.einsum("uq,ud->qd", seg, payloads.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)  # one-hot matmul

    n_contrib = seg.sum(axis=0)  # (Q,)
    base_n = jnp.where(last_reset < 0, state.agg_count, 0).astype(jnp.float32)
    touched = (last_reset >= 0) | (n_contrib > 0)
    denom = jnp.maximum(base_n + n_contrib, 1.0)
    combined = ((state.payload.astype(jnp.float32) * base_n[:, None] + sums)
                / denom[:, None])
    new_payload = jnp.where(touched[:, None], combined.astype(state.payload.dtype),
                            state.payload)
    new_state = JaxQueueState(
        cluster=cl, worker=wk, seq=sq, gen_time=gt, reward=rw, agg_count=cnt,
        replaceable=rp, payload=new_payload, next_seq=nseq,
        n_dropped=nd, n_agg=na, n_repl=nr, n_screened=ns)
    return new_state, slots, events


def jax_enqueue_burst(state: JaxQueueState, clusters, workers, gen_times,
                      rewards, payloads, reward_threshold: float = jnp.inf,
                      send=None, capacity=None, screen=None) -> JaxQueueState:
    """Fused fast path: resolve a whole U-update incast burst in one pass.

    Semantics match ``jax_enqueue_batch`` (sequential Algorithm 1) exactly on
    all metadata and counters; payloads agree up to float associativity,
    because the chain of per-update running means over a slot telescopes to

        new[q] = (base[q] · base_n[q] + Σ_{u contributing to q} upd[u]) / n[q]

    where ``base`` is the old slot payload if the burst never replaced slot
    ``q``, else the payload of the *last* reset (append/replace) event — so
    the whole payload movement is a single one-hot ``(Q, U) × (U, D)``
    segment-sum (an MXU matmul on TPU) plus one ``(Q, D)`` blend, instead of
    U sequential ``(Q, D)`` re-materializations.
    """
    state, _, _ = jax_enqueue_burst_ex(
        state, clusters, workers, gen_times, rewards, payloads,
        reward_threshold, send, capacity, screen)
    return state


#: Algorithm 1 classification label -> queue event, one place. The hybrid
#: window replay maps ``PyOlafQueue.classify_batch`` labels onto device
#: events through this table; :func:`classify_slot_events` inverts it for
#: consumers that start from the device-side ``(slots, events)`` stream.
EVENT_OF_CLASS = {"append": _EV_RESET, "replace": _EV_RESET,
                  "agg": _EV_AGG, "drop": _EV_DROP}


def classify_slot_events(slots, events, pre_occupied) -> List[str]:
    """Host-side inverse of the Algorithm 1 event stream: recover the
    ``classify_batch`` labels (``append`` / ``replace`` / ``agg`` / ``drop``)
    from the per-update ``(slot, event)`` assignment of
    :func:`_burst_resolve` / :func:`jax_enqueue_burst_ex`.

    ``pre_occupied`` is the (Q,) bool occupancy *before* the burst; the walk
    replays occupancy forward so a RESET into a vacant slot is an append and
    a RESET into an occupied slot is a replace — the single rule shared by
    ``PyOlafQueue.classify_batch`` (stats deltas), ``_SwitchMirror``
    (hybrid replay) and the vectorized simulator's subsumption scan.
    """
    occ = [bool(v) for v in np.asarray(pre_occupied)]
    labels: List[str] = []
    for slot, event in zip(np.asarray(slots), np.asarray(events)):
        slot, event = int(slot), int(event)
        if event == _EV_DROP:
            labels.append("drop")
        elif event == _EV_AGG:
            labels.append("agg")
        else:  # _EV_RESET
            labels.append("replace" if occ[slot] else "append")
            occ[slot] = True
    return labels


def expire_inactive_drains(out: Dict[str, jnp.ndarray], active_workers
                           ) -> Dict[str, jnp.ndarray]:
    """Algorithm 1 node-churn gating: drained rows belonging to crashed
    workers are treated as *expired* — the slot is freed (the drain already
    popped it) but the row is masked invalid, so it is never applied to the
    model and never advances the AoM sawtooth (``jax_aom_update`` freezes
    on ``valid=False``). ``active_workers`` is a bool (W,) membership mask;
    works for both the single-queue (k,) and multi-queue (S, k) layouts."""
    aw = jnp.asarray(active_workers, bool)
    w = jnp.clip(out["worker"], 0, aw.shape[0] - 1)  # invalid rows carry -1
    valid = out["valid"] & aw[w]
    return dict(out, valid=valid, n_valid=valid.sum(axis=-1))


def jax_olaf_step(state: JaxQueueState, clusters, workers, gen_times, rewards,
                 payloads, k: int, reward_threshold: float = jnp.inf,
                 send=None, capacity=None, active_workers=None, screen=None
                 ) -> Tuple[JaxQueueState, Dict[str, jnp.ndarray]]:
    """One full data-plane cycle: burst enqueue then drain-k, in one trace.

    Exactly ``jax_enqueue_burst`` followed by ``jax_dequeue_burst`` — this
    composition is both the XLA fast path of the fused cycle (one dispatch,
    one fused executable) and the oracle the Pallas ``olaf_step`` kernel
    (``repro.kernels.olaf_step``) is proven against. ``send`` optionally
    gates each burst row (worker-side transmission control, §5): a gated-out
    update is deferred and never touches the queue. ``capacity`` caps the
    logical slot count below the padded buffer size (heterogeneous
    per-switch slot vectors, see :func:`jax_enqueue`). ``active_workers``
    (bool (W,)) expires drained rows of crashed workers — see
    :func:`expire_inactive_drains`. ``screen`` (bool (U,), True = screened
    out) rejects corrupt burst rows at the ingress before they can combine
    — see :func:`jax_screen_mask` and ``_burst_resolve``.
    """
    state = jax_enqueue_burst(state, clusters, workers, gen_times, rewards,
                              payloads, reward_threshold, send, capacity,
                              screen)
    state, out = jax_dequeue_burst(state, k)
    if active_workers is not None:
        out = expire_inactive_drains(out, active_workers)
    return state, out


def jax_screen_mask(payloads, med, *, factor: float = 16.0, mask=None):
    """Device-resident ingress screen for one burst of payload rows.

    Per row: reject (``True``) when any coordinate is non-finite, or when
    the row's L2 norm exceeds ``factor ×`` a running robust scale estimate
    of the admitted traffic. The estimate ``med`` (a float32 scalar; start
    at 0.0) is a clipped exponential estimator of the admitted-row norm —
    each admitted row moves it at most ±10%, so a burst of exploding rows
    cannot drag the gate open, and screened rows never update it. A
    ``lax.scan`` over the burst keeps the decision order sequential (row
    ``u`` is judged against the estimate *after* rows ``< u``), matching
    how a switch pipeline would see the traffic.

    ``mask`` (bool (U,), default all-True) limits screening to real
    burst rows: a masked-out row (padding, or a transmission-control
    deferral) is never screened and never moves the scale estimate.

    Returns ``(screen (U,) bool, new_med)``.
    """
    payloads = jnp.asarray(payloads, jnp.float32)
    norms = jnp.sqrt(jnp.sum(
        jnp.where(jnp.isfinite(payloads), payloads, 0.0) ** 2, axis=-1))
    finite = jnp.all(jnp.isfinite(payloads), axis=-1)
    if mask is None:
        mask = jnp.ones(norms.shape, bool)

    def body(m, xs):
        n, fin, act = xs
        big = (m > 0.0) & (n > factor * m)
        scr = act & (~fin | big)
        # admitted rows nudge the scale estimate by at most +-10%; the
        # first admitted row initializes it
        m_new = jnp.where(m == 0.0, n,
                          m + jnp.clip(n - m, -0.1 * m, 0.1 * m))
        m = jnp.where(act & ~scr, m_new, m)
        return m, scr

    med, screen = jax.lax.scan(
        body, jnp.asarray(med, jnp.float32),
        (norms, finite, jnp.asarray(mask, bool)))
    return screen, med


# ---------------------------------------------------------------------------
# Donating jitted entry points for the PS hot loop.
#
# The queue state is donated: XLA reuses the O(Q·D) payload buffer in place
# instead of copying it every call (a no-op on backends without donation,
# e.g. CPU, where jax falls back to a copy). Callers must treat the passed-in
# state as consumed and use only the returned one.
# ---------------------------------------------------------------------------
jax_enqueue_burst_donating = jax.jit(jax_enqueue_burst, donate_argnums=0)
jax_dequeue_burst_donating = jax.jit(jax_dequeue_burst, static_argnums=1,
                                     donate_argnums=0)
