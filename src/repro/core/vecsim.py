"""Vectorized device-resident network simulator (one jitted ``lax.scan``).

The event-driven :mod:`repro.core.netsim` heap is the semantic oracle of
this repo, but it advances one Python callback per event: every scenario
pays a host round-trip per transmission window, and ``BENCH_step.json``
shows the Python heap — not the device kernels — is the throughput
ceiling. This module re-expresses the *same* network model as a
time-stepped, fully vectorized JAX program: per-switch combine queues
(Algorithm 1 via :func:`repro.core.olaf_queue.jax_enqueue_burst_ex`,
reached through the kernels layer as ``ops.olaf_burst_multi``), link
serialization and propagation, §5 transmission control
(:func:`repro.core.txctl.jax_send_probability` / ``jax_txctl_ack`` /
``jax_txctl_send``) and per-cluster AoM accounting
(:func:`repro.core.aom.jax_aom_update_block`) all advance inside ONE
jitted ``lax.scan`` over a precomputed time grid. A whole scenario runs
with zero per-window host round-trips: the staged static arrays (compiled
from :meth:`repro.core.topology.TopologySpec.scan_arrays` plus the
replayed randomness below) are the only host->device transfers.

Time grid and the dt tolerance (documented contract)
----------------------------------------------------
The scan consumes an ascending array of *boundary times* ``ts``; step k
processes every pending event with ``time <= ts[k]`` (and
``time <= horizon`` — the heap never executes events past the horizon).
Exact event *times* are carried in the state (service-finish, transit
arrival = finish + prop, precomputed generation times), so the grid only
batches *processing*; delivery timestamps and AoM integrals are computed
from the exact event times, never quantized to the grid.

The model is EXACTLY equivalent to the event heap whenever each grid cell
``(ts[k-1], ts[k]]`` contains at most one causally-related event per
switch (one completion cannot chain into a same-cell second completion,
an ACK cannot land between two same-cell generations, ...).  Two grid
builders are provided:

* :func:`midpoint_grid` — boundaries placed at the midpoints between
  consecutive *known* event times (collected from an oracle trace via
  :func:`grid_from_trace` / :func:`oracle_event_times`), so every event
  sits strictly inside its own cell with maximal float32 margin. This is
  the exact mode the equivalence suite runs in.
* :func:`uniform_grid` — a fixed ``dt``. Exactness additionally requires
  ``dt`` at most the minimum link service time (a back-to-back
  completion chain needs one step per packet); the builder asserts this
  unless ``allow_coarse=True``.  Under a coarser grid the documented
  tolerance is: same-cell events are processed in phase order
  (completions -> deliveries/ACKs -> arrivals -> service starts) rather
  than event order, chains resolve one cell late, and at most one
  generation per worker executes per cell — counters may then diverge
  from the heap, while every processed event still uses its exact time.

Same-instant ties and the dyadic exactness precondition
--------------------------------------------------------
The heap drains same-time events in *push* (eseq) order. The scan
reproduces that order numerically: every arrival event carries its push
time (``sched`` — a generation event is pushed when the previous
generation fires; a transit arrival when its parent completion is
processed, i.e. at the parent's ``fin``) plus the parent event's own
push time (``sched2``, one level of the heap's recursive tie
resolution), and arrivals sort by ``(time, sched, sched2, ring index)``.
A completion's push time is its service start, so an arrival at the
exact completion instant is processed before the completion iff its own
push time is earlier — the batch-A/try_start/batch-B split in phase 3.

This tie model is *bitwise* faithful whenever event-time arithmetic is
exact, which the equivalence suite guarantees by construction: dyadic
link rates (powers of two in bps), dyadic propagation delays and
generation intervals with zero jitter make every event time a dyadic
rational exactly representable in float32 and float64
(``tests/test_vecsim.py``). Outside that regime two caveats remain:
(1) the heap computes times in float64, where near-ties can differ by a
single ULP of accumulation noise (e.g. ``a + b + c`` vs ``a + (b + c)``)
— unreproducible in the scan's float32 and decided arbitrarily by either
engine, so non-dyadic comparisons must use relative (~1e-5) time keys;
(2) ties recursive beyond depth 2 (two events pushed at the same instant
by parents that were themselves pushed simultaneously) fall back to ring
order. Independently of tie order, float32 rounding of probabilities vs
the heap's float64 can flip a loss draw or reward-gate comparison
sitting exactly on a threshold boundary.

Feature envelope
----------------
:func:`check_vecsim_supported` raises :class:`VecsimUnsupported` (a
``NotImplementedError``) outside the supported envelope: link faults
only (i.i.d. drop + outage windows; no stalls / worker churn / PS faults
/ corruption), no staleness bound, no ingress screening, transmission
control without ACK-timeout retransmission, and no host callbacks.
Within it, fresh-send uid sets are disjoint and ``|uids| == subsumed``
per packet, so ``unique_delivered`` is recovered exactly as the sum of
``subsumed`` over deliveries.

Randomness is replayed, not re-rolled: generation times come from
:func:`repro.core.netsim.generation_schedule` (the heap's jitter stream,
proven exact by the monotone-subsequence argument documented there),
worker gate draws from each controller's own ``default_rng(seed * 7919 +
worker_id)`` stream, and per-link loss draws from the
:func:`repro.core.netsim.link_stream_index` streams — precomputed as
dense uniform tables indexed by per-link draw counters carried in the
scan state.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from collections import defaultdict
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.aggregation import Update
from repro.core.aom import (JaxAoMState, jax_aom_average, jax_aom_init,
                            jax_aom_update_block)
from repro.core.netsim import (NetworkSimulator, SimCfg, SimResult,
                               generation_schedule, link_stream_index)
from repro.core.olaf_queue import (_EMPTY_SEQ, _EV_AGG, _EV_DROP, _EV_RESET,
                                   JaxQueueState, jax_dequeue)
from repro.core.topology import spec_from_switch_cfgs
from repro.core.txctl import (jax_send_probability, jax_txctl_ack,
                              jax_txctl_init, jax_txctl_send)
from repro.kernels import ops

_BIG_I32 = np.int32(1 << 30)


class VecsimUnsupported(NotImplementedError):
    """The scenario uses a feature outside the vectorized model's envelope."""


def check_vecsim_supported(cfg: SimCfg) -> None:
    """Raise :class:`VecsimUnsupported` unless ``cfg`` fits the envelope."""
    problems: List[str] = []
    if cfg.staleness_bound is not None:
        problems.append("staleness_bound (PS admission control)")
    if cfg.ingress_screen:
        problems.append("ingress_screen (payload-integrity screening)")
    f = cfg.faults
    if f is not None:
        for kind in ("stalls", "workers", "ps", "corruption"):
            if getattr(f, kind):
                problems.append(f"faults.{kind}")
    if cfg.tx_control is not None and cfg.tx_control.ack_timeout is not None:
        problems.append("tx_control.ack_timeout (retransmission)")
    for hook in ("payload_fn", "on_deliver", "on_ack", "on_queue_event",
                 "on_ps_restart"):
        if getattr(cfg, hook) is not None:
            problems.append(f"{hook} (host callback)")
    if problems:
        raise VecsimUnsupported(
            "vectorized simulator does not support: " + ", ".join(problems)
            + "; use the event-driven NetworkSimulator for this scenario")


# ---------------------------------------------------------------------------
# Time grids
# ---------------------------------------------------------------------------
def midpoint_grid(times: Sequence[float], horizon: float,
                  *, bucket: int = 128) -> np.ndarray:
    """Boundary grid from known event times: one boundary at the midpoint
    between each pair of consecutive unique times (each event sits strictly
    inside its own cell, with half-gap float32 margin), one final boundary
    past the last event. Times beyond the horizon are pruned — the heap
    never executes them. ``bucket`` pads the step count (repeating the
    final boundary, a provable no-op) so different trials of the
    equivalence suite share one compiled program shape."""
    t = np.unique(np.asarray(list(times), np.float64))
    t = t[(t >= 0.0) & (t <= horizon)]
    if t.size == 0:
        bounds = np.asarray([horizon + 1.0], np.float64)
    else:
        mids = (t[:-1] + t[1:]) / 2.0
        bounds = np.concatenate([mids, [t[-1] + 1.0]])
    bounds = bounds.astype(np.float32)
    if bucket > 1 and bounds.size % bucket:
        pad = bucket - bounds.size % bucket
        bounds = np.concatenate([bounds, np.full(pad, bounds[-1], np.float32)])
    return bounds


def uniform_grid(cfg: SimCfg, dt: float, *, allow_coarse: bool = False,
                 bucket: int = 128) -> np.ndarray:
    """Fixed-step grid covering ``[0, horizon]`` plus a chain-flush tail.

    Exactness requires ``dt`` at most the minimum link service time (a
    back-to-back completion chain resolves one packet per step); asserted
    here unless ``allow_coarse=True`` — the caller then accepts the
    documented coarse-grid tolerance (see module docstring)."""
    min_size = min((w.size_bits for w in cfg.workers), default=1)
    max_rate = max((s.uplink.capacity_bps for s in cfg.switches), default=1.0)
    min_service = min_size / max_rate
    if not allow_coarse and dt > min_service:
        # name the link that sets the bound: the fastest uplink serializes
        # the smallest packet in min_service seconds
        src = next((s for s in cfg.switches
                    if s.uplink.capacity_bps == max_rate), None)
        link = ""
        if src is not None:
            link = (f" — set by link ({src.name} -> {src.next_hop or 'PS'}):"
                    f" {min_size} bits at {max_rate:g} bps serialize in "
                    f"{min_service:g}s")
        raise ValueError(
            f"uniform_grid dt={dt:g} exceeds the minimum link service time "
            f"{min_service:g}s{link}: back-to-back completion chains would "
            f"resolve one grid step late. Pass allow_coarse=True to accept "
            f"the documented coarse-grid tolerance.")
    n = max(1, int(math.ceil(cfg.horizon / dt)))
    ts = dt * np.arange(1, n + 1, dtype=np.float64)
    # flush tail: each extra step drains at most one completion per switch,
    # so queued-up chains (bounded by the slot count) finish resolving
    qmax = max((s.queue_slots for s in cfg.switches), default=1)
    tail = cfg.horizon + dt * np.arange(1, qmax + 4, dtype=np.float64)
    bounds = np.concatenate([ts, tail]).astype(np.float32)
    if bucket > 1 and bounds.size % bucket:
        pad = bucket - bounds.size % bucket
        bounds = np.concatenate([bounds, np.full(pad, bounds[-1], np.float32)])
    return bounds


def grid_from_trace(cfg: SimCfg, events: Sequence[Tuple], *,
                    bucket: int = 128) -> np.ndarray:
    """Midpoint grid from an oracle queue-event trace (the list collected
    through ``SimCfg.on_queue_event``): every trace time, plus the
    PS-arrival (``t + prop``) and ACK (``+ ack_delay``) expansions of each
    ``deliver`` record, plus every executed generation time (deferred
    generations consume a gate draw but emit no queue event)."""
    prop = {s.name: s.uplink.prop_delay for s in cfg.switches}
    times: List[float] = []
    gen_times, _ = generation_schedule(cfg)
    for ts_w in gen_times.values():
        times.extend(ts_w)
    for ev in events:
        now, name, kind = ev[0], ev[1], ev[2]
        times.append(now)
        if kind == "deliver":
            times.append(now + prop[name])
            times.append(now + prop[name] + cfg.ack_delay)
    return midpoint_grid(times, cfg.horizon, bucket=bucket)


def oracle_event_times(cfg: SimCfg, *, bucket: int = 128
                       ) -> Tuple[np.ndarray, SimResult]:
    """Run the event-driven oracle once, returning ``(grid, SimResult)``:
    the exact midpoint grid for this scenario plus the oracle's own result
    (the equivalence suite's reference, so one heap run serves both)."""
    events: List[Tuple[float, str, str, Optional[Update]]] = []
    trace_cfg = dataclasses.replace(
        cfg, on_queue_event=lambda now, sw, kind, upd: events.append(
            (now, sw, kind, upd)))
    res = NetworkSimulator(trace_cfg).run()
    return grid_from_trace(cfg, events, bucket=bucket), res


# ---------------------------------------------------------------------------
# Scenario compilation (host): cfg -> static dims + staged arrays
# ---------------------------------------------------------------------------
class _Static(NamedTuple):
    S: int       # switches (padded)
    W: int       # workers (padded)
    C: int       # clusters (padded, dense ids)
    CC: int      # candidate columns
    Q: int       # queue slot buffer width
    Wm: int      # max workers per switch (padded)
    Rt: int      # transit ring slots
    Rp: int      # PS-wire ring slots
    Ra: int      # ACK ring slots
    G: int       # generation table width
    NL: int      # per-link loss-uniform table width
    K: int       # outage-window columns
    Gc: int      # delivery buffer rows
    Gd: int      # drop-record buffer rows
    D: int       # payload dim
    route: str   # "static" | "hash" | "adaptive"
    has_tx: bool


@dataclasses.dataclass
class _Compiled:
    static: _Static
    arrays: Dict[str, np.ndarray]
    switch_names: List[str]   # real switches only
    cluster_ids: List[int]    # dense index -> real cluster id
    n_real_switches: int
    generated: int            # len(schedule order)
    total_sends_bound: int
    wire: np.ndarray          # (S,) per-switch in-flight bound, 0 on egress


def _pow2(n: int, lo: int = 2) -> int:
    return max(lo, 1 << (int(n - 1).bit_length())) if n > 0 else lo


def compile_scenario(cfg: SimCfg, *, dim: int = 1,
                     payload_rows: Optional[np.ndarray] = None,
                     gen_rewards: Optional[np.ndarray] = None,
                     pad_pow2: bool = True) -> _Compiled:
    """Compile ``cfg`` into the scan's static dims and staged arrays.

    ``gen_rewards`` is an optional (n_workers, G) table of rewards aligned
    to each worker's *executed* generations (the oracle side wires the
    equivalent ``payload_fn``); omitted -> all rewards 0.0, matching a
    heap run without ``payload_fn``. ``pad_pow2`` buckets every axis to a
    power of two with provably inert padding (dummy egress switches with
    no traffic, workers that never generate, clusters never delivered) so
    randomized trials share one compiled program."""
    check_vecsim_supported(cfg)
    spec = spec_from_switch_cfgs(cfg.switches, route_policy=cfg.route_policy)
    if cfg.workers:
        spec.validate_ingress([w.ingress_switch for w in cfg.workers])
    sa = spec.scan_arrays()
    bucket = _pow2 if pad_pow2 else (lambda n, lo=2: max(n, 1))

    S0, W0 = spec.num_switches, len(cfg.workers)
    cluster_ids = sorted({w.cluster_id for w in cfg.workers})
    c_index = {c: i for i, c in enumerate(cluster_ids)}
    C0 = len(cluster_ids)
    CC0 = sa["cand_matrix"].shape[1]
    Q0 = int(sa["queue_slots"].max()) if S0 else 1

    gen_times, order = generation_schedule(cfg)
    counts = {wid: len(ts) for wid, ts in gen_times.items()}
    G0 = max(list(counts.values()) + [1])
    total_gens = len(order)

    by_ingress: Dict[str, List[int]] = defaultdict(list)
    for i, w in enumerate(cfg.workers):
        by_ingress[w.ingress_switch].append(i)
    Wm0 = max([len(v) for v in by_ingress.values()] + [1])

    # ring bounds: at most one completion per switch per step, so ring
    # occupancy is bounded by packets concurrently on the wire
    min_size = min((w.size_bits for w in cfg.workers), default=1)
    wire = spec.wire_packets(min_size)
    Rt0 = max(int(wire[~sa["is_egress"]].sum()), 2)
    Rp0 = max(int(wire[sa["is_egress"]].sum()), 2)
    ack_pkts = sum(
        int(math.ceil(cfg.ack_delay * cfg.switches[s].uplink.capacity_bps
                      / max(min_size, 1))) + 2
        for s in range(S0) if sa["is_egress"][s])
    Ra0 = max(min(ack_pkts, total_gens + 2), 2)

    st = _Static(
        S=bucket(S0), W=bucket(W0), C=bucket(C0), CC=bucket(CC0, 1),
        Q=bucket(Q0), Wm=bucket(Wm0), Rt=bucket(Rt0), Rp=bucket(Rp0),
        Ra=bucket(Ra0), G=bucket(G0), NL=bucket(total_gens + 2, 4),
        K=bucket(1, 1), Gc=bucket(max(total_gens, 1)),
        Gd=bucket(max(total_gens * max(S0, 1), 1)), D=max(int(dim), 1),
        route=cfg.route_policy, has_tx=cfg.tx_control is not None)

    # ---- per-switch arrays (padding rows are inert egress switches) ------
    S, CC, K = st.S, st.CC, st.K
    cand = np.full((S, CC), -1, np.int32)
    cand[:S0, :CC0] = sa["cand_matrix"]
    ccount = np.zeros(S, np.int32)
    ccount[:S0] = sa["cand_count"]
    next_hop = np.full(S, -1, np.int32)
    next_hop[:S0] = sa["next_hop"]
    is_eg = np.ones(S, bool)
    is_eg[:S0] = sa["is_egress"]
    is_fifo = np.zeros(S, bool)
    is_fifo[:S0] = sa["is_fifo"]
    slots = np.ones(S, np.int32)
    slots[:S0] = sa["queue_slots"]
    rthr = np.full(S, np.inf, np.float32)
    rthr[:S0] = sa["reward_threshold"]
    # rate/prop read straight from the cfg (the spec's gbps round-trip is
    # not bit-exact, which the bitwise AoM test relies on)
    rate = np.ones(S, np.float32)
    prop = np.zeros(S, np.float32)
    for i, sc in enumerate(cfg.switches):
        rate[i] = sc.uplink.capacity_bps
        prop[i] = sc.uplink.prop_delay

    # ---- fault tables: composite drop prob + outage windows + uniforms --
    # column j < CC: link (switch -> candidate j); column CC: egress -> PS
    f = cfg.faults
    K_need = 1
    windows: Dict[Tuple[int, int], List[Tuple[float, float]]] = {}
    p_tab = np.zeros((S, CC + 1), np.float32)
    lossy: List[Tuple[int, int, str, Optional[str]]] = []
    if f is not None and f.links:
        for si in range(S0):
            src = spec.names[si]
            cols: List[Tuple[int, Optional[str]]] = [
                (j, spec.names[cand[si, j]]) for j in range(int(ccount[si]))]
            cols.append((CC, None))
            for j, dst in cols:
                p = f.drop_prob(src, dst)
                if p > 0.0:
                    p_tab[si, j] = p
                    lossy.append((si, j, src, dst))
                win = [(t0, t1) for lf in f._match(src, dst)
                       for (t0, t1) in lf.down]
                if win:
                    windows[(si, j)] = win
                    K_need = max(K_need, len(win))
    K = _pow2(K_need, 1) if pad_pow2 else K_need
    st = st._replace(K=K)
    down_t0 = np.full((S, CC + 1, K), np.inf, np.float32)
    down_t1 = np.full((S, CC + 1, K), np.inf, np.float32)
    for (si, j), win in windows.items():
        for k, (t0, t1) in enumerate(win):
            down_t0[si, j, k] = t0
            down_t1[si, j, k] = t1
    loss_u = np.zeros((S, CC + 1, st.NL), np.float32)
    if lossy:
        base = f.seed * 104729 + cfg.seed * 7919 + 11
        for si, j, src, dst in lossy:
            rng = np.random.default_rng(
                [base, link_stream_index(spec, src, dst)])
            loss_u[si, j] = rng.random(st.NL)

    # ---- per-worker arrays ----------------------------------------------
    W, G = st.W, st.G
    gen_t = np.full((W, G), np.inf, np.float32)
    gen_sched = np.full((W, G), np.inf, np.float32)
    gen_sched2 = np.full((W, G), np.inf, np.float32)
    gen_rank = np.zeros((W, G), np.int32)
    gen_u = np.ones((W, G), np.float32)  # 1.0 => never sends (padding)
    gen_rw = np.zeros((W, G), np.float32)
    gcount = np.zeros(W, np.int32)
    w_cluster = np.full(W, -1, np.int32)
    w_id = np.full(W, -1, np.int32)
    w_size = np.ones(W, np.float32)
    sw_workers = np.full((S, st.Wm), -1, np.int32)
    rank_of = {pair: r for r, pair in enumerate(order)}
    for i, w in enumerate(cfg.workers):
        ts_w = gen_times[w.worker_id]
        n = len(ts_w)
        gcount[i] = n
        gen_t[i, :n] = ts_w
        # the heap event for generation k was PUSHED when generation k-1
        # fired (the first at init, before anything else): that push time
        # decides who wins exact event-time ties against completions and
        # transit arrivals (heap order is (time, eseq))
        gen_sched[i, :n] = [-1.0] + list(ts_w[:-1]) if n else []
        # depth-2 key: the PARENT event's own push time (generation k-1
        # was pushed at generation k-2's firing) — breaks recursive ties
        # between events pushed at the same instant
        gen_sched2[i, :n] = [-1.0, -1.0][:n] + list(ts_w[:-2])
        gen_rank[i, :n] = [rank_of[(w.worker_id, k)] for k in range(n)]
        if st.has_tx:
            gen_u[i, :G] = np.random.default_rng(
                cfg.seed * 7919 + w.worker_id).random(G)
        if gen_rewards is not None:
            m = min(n, gen_rewards.shape[1])
            gen_rw[i, :m] = gen_rewards[i, :m]
        w_cluster[i] = c_index[w.cluster_id]
        w_id[i] = w.worker_id
        w_size[i] = w.size_bits
    for name, idxs in by_ingress.items():
        si = spec.index[name]
        sw_workers[si, :len(idxs)] = idxs

    # ---- payload rows, consumed in global send order --------------------
    n_rows = max(total_gens, 1)
    rows = np.zeros((n_rows + 1, st.D), np.float32)
    if payload_rows is not None:
        pr = np.asarray(payload_rows, np.float32).reshape(-1, st.D)
        rows[:min(len(pr), n_rows)] = pr[:n_rows]

    tc = cfg.tx_control
    arrays = dict(
        cand=cand, ccount=ccount, next_hop=next_hop, is_eg=is_eg,
        is_fifo=is_fifo, slots=slots, slots_f=slots.astype(np.float32),
        rate=rate, prop=prop, rthr=rthr, p_tab=p_tab, down_t0=down_t0,
        down_t1=down_t1, loss_u=loss_u, gen_t=gen_t, gen_sched=gen_sched,
        gen_sched2=gen_sched2, gen_rank=gen_rank,
        gen_u=gen_u, gen_rw=gen_rw, gcount=gcount, w_cluster=w_cluster,
        w_id=w_id, w_size=w_size, sw_workers=sw_workers, rows=rows,
        cl_real=np.asarray(cluster_ids + [0] * (st.C - C0), np.int32),
        horizon=np.float32(cfg.horizon),
        ack_delay=np.float32(cfg.ack_delay),
        active_window=np.float32(cfg.active_window),
        delta_thr=np.float32(tc.delta_threshold if tc else 0.0),
        v_slope=np.float32(tc.v if tc else 0.0),
    )
    wire_pad = np.zeros(st.S, np.int64)
    wire_pad[:S0] = np.where(sa["is_egress"], 0, wire)
    return _Compiled(static=st, arrays=arrays,
                     switch_names=list(spec.names),
                     cluster_ids=cluster_ids, n_real_switches=S0,
                     generated=total_gens, total_sends_bound=total_gens,
                     wire=wire_pad)


# ---------------------------------------------------------------------------
# The jitted scan (built once per static shape, cached)
# ---------------------------------------------------------------------------
def _ring_insert(ring, ovf, mask, rows):
    """Insert ``rows[s]`` (masked) into the first free slot (time == +inf)
    of each ring array; sequential over the leading source axis so two
    same-step insertions land in distinct slots."""
    def body(c, x):
        r, o = c
        m, row = x
        free = jnp.isinf(r["time"])
        idx = jnp.argmax(free)
        ok = m & jnp.any(free)
        r = {k: v.at[idx].set(jnp.where(ok, row[k], v[idx]))
             for k, v in r.items()}
        return (r, o | (m & ~jnp.any(free))), None

    (ring, ovf), _ = lax.scan(body, (ring, ovf), (mask, rows))
    return ring, ovf


def _ring_insert_vec(ring, ovf, mask, rows):
    """Vectorized first-free ring insertion, identical to the sequential
    :func:`_ring_insert` within one call: no slot is freed between the
    insertions of one batch, so the k-th masked source row (in source
    order) lands in the k-th lowest free slot — one stable sort plus a
    rank instead of a scan over the source axis. Also returns ``slot``,
    each masked row's landing index (``R`` for unplaced rows): the
    sharded runner carries it as the ring-order tie key."""
    R = ring["time"].shape[0]
    free = jnp.isinf(ring["time"])
    forder = jnp.argsort(~free)  # stable: free slots first, ascending index
    rank = jnp.cumsum(mask.astype(jnp.int32)) - 1
    n_free = jnp.sum(free.astype(jnp.int32))
    ok = mask & (rank < n_free)
    slot = jnp.where(ok, forder[jnp.clip(rank, 0, R - 1)], R)
    ring = {k: _place(v, slot, rows[k]) for k, v in ring.items()}
    return ring, ovf | jnp.any(mask & ~ok), slot


def _append(buf, n, rows, k):
    """Write ``rows[:k]`` to ``buf[n:n + k]``, dropping rows past the end
    of ``buf``: one window read and one window write of ``min(len(rows),
    len(buf))`` rows, with no out-of-range index (see :func:`_place`)."""
    size, L = buf.shape[0], min(rows.shape[0], buf.shape[0])
    start = jnp.clip(n, 0, size - L)
    off = start + jnp.arange(L, dtype=jnp.int32) - n  # row of each window slot
    take = ((off >= 0) & (off < k)).reshape((L,) + (1,) * (buf.ndim - 1))
    win = lax.dynamic_slice_in_dim(buf, start, L)
    new = jnp.where(take, rows[jnp.clip(off, 0, rows.shape[0] - 1)], win)
    return lax.dynamic_update_slice_in_dim(buf, new, start, 0)


def _place(buf, idx, rows):
    """``buf.at[idx].set(rows, mode="drop")`` for distinct ``idx``, written
    as a gather and a select: entries of ``idx`` outside ``buf`` are
    dropped. On a TPU v5e the scatter form lost an in-range payload row
    when the same scatter also carried out-of-range indices (the PS ring's
    ``(S, D)`` rows inside the scan); the select has no such case."""
    n = buf.shape[0]
    hit = idx[None, :] == jnp.arange(n, dtype=idx.dtype)[:, None]  # (n, N)
    keep = jnp.any(hit, axis=1).reshape((n,) + (1,) * (buf.ndim - 1))
    return jnp.where(keep, rows[jnp.argmax(hit, axis=1)], buf)


def _init_carry(static: _Static, *, n_s: Optional[int] = None,
                n_w: Optional[int] = None, n_aom: Optional[int] = None,
                rt: Optional[int] = None, sharded: bool = False):
    """Build the scan's initial carry (eagerly — plain ``jnp`` zeros).

    The single-device entry builds it OUTSIDE the jitted runner so the
    buffers can be donated (every leaf aliases a same-shaped output).
    The sharded runner builds it inside the ``shard_map`` body with its
    local dims (``n_s`` switches / ``n_w`` workers / ``n_aom`` AoM rows
    per shard, ``rt`` local transit-ring slots); ``sharded`` additionally
    adds the replicated ghost transit ring plus the per-row ``key2`` tie
    key and the local-ring overflow flag (see ``_make_runner_sharded``)."""
    S = n_s if n_s is not None else static.S
    W = n_w if n_w is not None else static.W
    Ca = n_aom if n_aom is not None else static.C
    Rt = rt if rt is not None else static.Rt
    C, Q, D, CC = static.C, static.Q, static.D, static.CC
    Rp, Ra, Gc, Gd = static.Rp, static.Ra, static.Gc, static.Gd
    q = JaxQueueState(
        cluster=-jnp.ones((S, Q), jnp.int32),
        worker=-jnp.ones((S, Q), jnp.int32),
        seq=jnp.full((S, Q), _EMPTY_SEQ, jnp.int32),
        gen_time=jnp.zeros((S, Q), jnp.float32),
        reward=jnp.full((S, Q), -jnp.inf, jnp.float32),
        agg_count=jnp.zeros((S, Q), jnp.int32),
        replaceable=jnp.zeros((S, Q), bool),
        payload=jnp.zeros((S, Q, D), jnp.float32),
        next_seq=jnp.zeros((S,), jnp.int32),
        n_dropped=jnp.zeros((S,), jnp.int32),
        n_agg=jnp.zeros((S,), jnp.int32),
        n_repl=jnp.zeros((S,), jnp.int32),
        n_screened=jnp.zeros((S,), jnp.int32))
    aom0 = jax_aom_init(0.0)
    tr = dict(time=jnp.full((Rt,), jnp.inf, jnp.float32),
              sched=jnp.zeros((Rt,), jnp.float32),
              sched2=jnp.zeros((Rt,), jnp.float32),
              dst=-jnp.ones((Rt,), jnp.int32),
              rcl=jnp.zeros((Rt,), jnp.int32),
              wk=jnp.zeros((Rt,), jnp.int32),
              gen=jnp.zeros((Rt,), jnp.float32),
              rw=jnp.zeros((Rt,), jnp.float32),
              agg=jnp.zeros((Rt,), jnp.int32),
              subs=jnp.zeros((Rt,), jnp.int32),
              size=jnp.ones((Rt,), jnp.float32),
              rp=jnp.ones((Rt,), bool),
              pay=jnp.zeros((Rt, D), jnp.float32))
    ovf = dict(tr=jnp.asarray(False), ps=jnp.asarray(False),
               ack=jnp.asarray(False))
    if sharded:
        tr["key2"] = jnp.zeros((Rt,), jnp.int32)
        ovf["trl"] = jnp.asarray(False)
    carry = dict(
        q=q,
        rclq=-jnp.ones((S, Q), jnp.int32),
        subsq=jnp.zeros((S, Q), jnp.int32),
        sizeq=jnp.ones((S, Q), jnp.float32),
        srv=dict(valid=jnp.zeros((S,), bool),
                 rcl=-jnp.ones((S,), jnp.int32),
                 wk=-jnp.ones((S,), jnp.int32),
                 gen=jnp.zeros((S,), jnp.float32),
                 rw=jnp.zeros((S,), jnp.float32),
                 agg=jnp.zeros((S,), jnp.int32),
                 subs=jnp.zeros((S,), jnp.int32),
                 size=jnp.ones((S,), jnp.float32),
                 fin=jnp.full((S,), jnp.inf, jnp.float32),
                 rp=jnp.ones((S,), bool),
                 pay=jnp.zeros((S, D), jnp.float32)),
        free_t=jnp.zeros((S,), jnp.float32),
        nonempty=jnp.full((S,), jnp.inf, jnp.float32),
        last_seen=jnp.full((S, C), -jnp.inf, jnp.float32),
        tr=tr,
        ps=dict(time=jnp.full((Rp,), jnp.inf, jnp.float32),
                rcl=jnp.zeros((Rp,), jnp.int32),
                wk=jnp.zeros((Rp,), jnp.int32),
                gen=jnp.zeros((Rp,), jnp.float32),
                rw=jnp.zeros((Rp,), jnp.float32),
                agg=jnp.zeros((Rp,), jnp.int32),
                subs=jnp.zeros((Rp,), jnp.int32),
                pay=jnp.zeros((Rp, D), jnp.float32)),
        ack=dict(time=jnp.full((Ra,), jnp.inf, jnp.float32),
                 cl=-jnp.ones((Ra,), jnp.int32),
                 nact=jnp.zeros((Ra,), jnp.float32),
                 qmax=jnp.ones((Ra,), jnp.float32),
                 gen=jnp.zeros((Ra,), jnp.float32)),
        aom=jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (Ca,)), aom0),
        dlv=dict(n=jnp.int32(0),
                 time=jnp.zeros((Gc,), jnp.float32),
                 rcl=jnp.zeros((Gc,), jnp.int32),
                 wk=jnp.zeros((Gc,), jnp.int32),
                 gen=jnp.zeros((Gc,), jnp.float32),
                 rw=jnp.zeros((Gc,), jnp.float32),
                 agg=jnp.zeros((Gc,), jnp.int32),
                 subs=jnp.zeros((Gc,), jnp.int32),
                 pay=jnp.zeros((Gc, D), jnp.float32)),
        drp=dict(n=jnp.int32(0),
                 time=jnp.zeros((Gd,), jnp.float32),
                 rcl=jnp.zeros((Gd,), jnp.int32),
                 gen=jnp.zeros((Gd,), jnp.float32),
                 subs=jnp.zeros((Gd,), jnp.int32)),
        sent=jnp.int32(0), deferred=jnp.int32(0),
        link_dropped=jnp.int32(0), raw_link_dropped=jnp.int32(0),
        reroutes=jnp.int32(0), forwarded=jnp.int32(0),
        reroutes_s=jnp.zeros((S,), jnp.int32),
        drops_s=jnp.zeros((S,), jnp.int32),
        departed=jnp.zeros((S,), jnp.int32),
        rdrops=jnp.zeros((S,), jnp.int32),
        fctr=jnp.zeros((S,), jnp.int32),
        lctr=jnp.zeros((S, CC + 1), jnp.int32),
        gptr=jnp.zeros((W,), jnp.int32),
        srow=jnp.int32(0),
        ovf=ovf)
    if sharded:
        carry["ghost"] = jnp.full((static.Rt,), jnp.inf, jnp.float32)
    if static.has_tx:
        carry["tx"] = jax_txctl_init(W)
    return carry


@functools.lru_cache(maxsize=16)
def _make_runner(static: _Static):
    S, W, C, CC, Q = static.S, static.W, static.C, static.CC, static.Q
    Wm, Rt, Rp, Ra = static.Wm, static.Rt, static.Rp, static.Ra
    G, NL, K = static.G, static.NL, static.K
    Gc, Gd, D = static.Gc, static.Gd, static.D
    A = Rt + Wm
    KEY2_OFF = np.int32(W * G)
    aS, aW, aA = jnp.arange(S), jnp.arange(W), jnp.arange(A)

    def aux_walk(cl0, occ0, subs0, rcl0, size0, nocc0, xs):
        """Per-switch sequential replay of the burst's (slot, event)
        stream: maintains the per-slot real-cluster / subsumed / size
        sidecar, detects reward-drops (drop with a same-cluster hit) and
        the first append into an empty queue."""
        def body(c, x):
            clq, occ, subs, rcl, sizev, nocc, first_app, rdrop = c
            slot, ev, a, cps, cr, t_r, insub, insz = x
            occ_slot = occ[slot]
            hit = jnp.any(occ & (clq == cps))
            is_drop = a & (ev == _EV_DROP)
            rdrop = rdrop + (is_drop & hit).astype(jnp.int32)
            is_agg = a & (ev == _EV_AGG)
            is_rst = a & (ev == _EV_RESET)
            appendv = is_rst & ~occ_slot
            first_app = jnp.where(appendv & (nocc == 0),
                                  jnp.minimum(first_app, t_r), first_app)
            oh = jnp.arange(Q) == slot
            wrt = is_agg | is_rst
            addm = is_agg | (is_rst & occ_slot)
            subs = jnp.where(oh & addm, subs + insub, subs)
            subs = jnp.where(oh & appendv, insub, subs)
            rcl = jnp.where(oh & wrt, cr, rcl)
            sizev = jnp.where(oh & wrt, insz, sizev)
            clq = jnp.where(oh & is_rst, cps, clq)
            nocc = nocc + appendv.astype(jnp.int32)
            occ = occ | (oh & is_rst)
            return (clq, occ, subs, rcl, sizev, nocc, first_app, rdrop), None

        init = (cl0, occ0, subs0, rcl0, size0, nocc0,
                jnp.float32(jnp.inf), jnp.int32(0))
        (clq, occ, subs, rcl, sizev, nocc, first_app, rdrop), _ = lax.scan(
            body, init, xs)
        return subs, rcl, sizev, first_app, rdrop

    v_aux_walk = jax.vmap(aux_walk)

    def run(carry0, arrs, ts):
        horizon = arrs["horizon"]

        def try_start(q, subsq, rclq, sizeq, srv, free_t, nonempty):
            """Pop the min-seq packet into the service register wherever
            the server is free and the queue nonempty (netsim's
            restart-at-finish / lock_head)."""
            occ = jnp.sum((q.cluster >= 0).astype(jnp.int32), axis=1)
            start_m = ~srv["valid"] & (occ > 0)
            start_t = jnp.maximum(free_t, nonempty)
            slot_min = jnp.argmin(q.seq, axis=1)
            rp_g = q.replaceable[aS, slot_min]
            q_pop, outd = jax.vmap(jax_dequeue)(q)
            qf = jax.tree_util.tree_map(
                lambda a, b: jnp.where(
                    start_m.reshape((S,) + (1,) * (a.ndim - 1)), b, a),
                q, q_pop)
            size_g = sizeq[aS, slot_min]
            srv = dict(
                valid=srv["valid"] | start_m,
                rcl=jnp.where(start_m, rclq[aS, slot_min], srv["rcl"]),
                wk=jnp.where(start_m, outd["worker"], srv["wk"]),
                gen=jnp.where(start_m, outd["gen_time"], srv["gen"]),
                rw=jnp.where(start_m, outd["reward"], srv["rw"]),
                agg=jnp.where(start_m, outd["agg_count"], srv["agg"]),
                subs=jnp.where(start_m, subsq[aS, slot_min], srv["subs"]),
                size=jnp.where(start_m, size_g, srv["size"]),
                fin=jnp.where(start_m, start_t + size_g / arrs["rate"],
                              srv["fin"]),
                rp=jnp.where(start_m, rp_g, srv["rp"]),
                pay=jnp.where(start_m[:, None], outd["payload"],
                              srv["pay"]))
            oh = (jnp.arange(Q)[None, :] == slot_min[:, None]) \
                & start_m[:, None]
            return (qf, jnp.where(oh, 0, subsq), jnp.where(oh, -1, rclq),
                    jnp.where(oh, 1.0, sizeq), srv)

        def step(carry, t):
            q, srv = carry["q"], carry["srv"]
            # ======== phase 1: service completions =======================
            fin = srv["fin"]
            done = srv["valid"] & (fin <= t) & (fin <= horizon)
            depth = (jnp.sum(q.cluster >= 0, axis=1)
                     + srv["valid"].astype(jnp.int32))
            cand_valid = jnp.arange(CC)[None, :] < arrs["ccount"][:, None]
            finb = fin[:, None, None]
            down_c = jnp.any((arrs["down_t0"][:, :CC, :] <= finb)
                             & (finb < arrs["down_t1"][:, :CC, :]), axis=2)
            alive = cand_valid & ~down_c
            eg_down = jnp.any((arrs["down_t0"][:, CC, :] <= fin[:, None])
                              & (fin[:, None] < arrs["down_t1"][:, CC, :]),
                              axis=1)
            m = jnp.sum(alive, axis=1)
            if static.route == "hash":
                h = (arrs["cl_real"][jnp.clip(srv["rcl"], 0, C - 1)]
                     .astype(jnp.uint32) * np.uint32(2654435761)
                     + srv["wk"].astype(jnp.uint32) * np.uint32(40503)
                     + aS.astype(jnp.uint32) * np.uint32(9176))
                kth = (h % jnp.maximum(m, 1).astype(jnp.uint32)
                       ).astype(jnp.int32)
                csum = jnp.cumsum(alive, axis=1) - 1
                selcol = jnp.argmax((csum == kth[:, None]) & alive, axis=1)
            elif static.route == "adaptive":
                dsts = jnp.clip(arrs["cand"], 0, S - 1)
                dd = jnp.where(alive, depth[dsts].astype(jnp.float32),
                               jnp.inf)
                selcol = jnp.argmin(dd, axis=1)
            else:  # static: first alive candidate
                selcol = jnp.argmax(alive, axis=1)
            sel = arrs["cand"][aS, selcol]
            is_eg = arrs["is_eg"]
            drawcol = jnp.where(is_eg, CC, selcol)
            p = arrs["p_tab"][aS, drawcol]
            ctr = carry["lctr"][aS, drawcol]
            u = arrs["loss_u"][aS, drawcol, jnp.clip(ctr, 0, NL - 1)]
            need_draw = done & (p > 0.0) & jnp.where(is_eg, ~eg_down, m > 0)
            lost_draw = need_draw & (u < p)
            lctr = carry["lctr"].at[aS, drawcol].add(
                need_draw.astype(jnp.int32))
            eg_del = is_eg & done & ~eg_down & ~lost_draw
            ne_fwd = ~is_eg & done & (m > 0) & ~lost_draw
            dropped_now = done & ~eg_del & ~ne_fwd
            reroute_now = ne_fwd & (sel != arrs["next_hop"])
            raw_drop_add = jnp.sum(jnp.where(dropped_now, srv["subs"], 0))

            orderd = jnp.argsort(jnp.where(dropped_now, fin, jnp.inf))
            drp = carry["drp"]
            n_drp = jnp.sum(dropped_now.astype(jnp.int32))
            drp = dict(
                n=drp["n"] + n_drp,
                **{k: _append(drp[k], drp["n"], v[orderd], n_drp)
                   for k, v in (("time", fin), ("rcl", srv["rcl"]),
                                ("gen", srv["gen"]), ("subs", srv["subs"]))})

            ovf = carry["ovf"]
            ps, ovf_ps, _ = _ring_insert_vec(
                carry["ps"], ovf["ps"], eg_del,
                dict(time=fin + arrs["prop"], rcl=srv["rcl"], wk=srv["wk"],
                     gen=srv["gen"], rw=srv["rw"], agg=srv["agg"],
                     subs=srv["subs"], pay=srv["pay"]))
            # heap push time of this completion event (= its service
            # start): arrivals at the exact completion instant whose own
            # push time is earlier are processed BEFORE the completion.
            # It doubles as the forwarded arrival's depth-2 tie key: two
            # arrivals pushed at the same fin instant inherit their parent
            # completions' processing order, i.e. the parents' push times
            csched = fin - srv["size"] / arrs["rate"]
            tr, ovf_tr, _ = _ring_insert_vec(
                carry["tr"], ovf["tr"], ne_fwd,
                dict(time=fin + arrs["prop"], sched=fin, sched2=csched,
                     dst=sel,
                     rcl=srv["rcl"], wk=srv["wk"], gen=srv["gen"],
                     rw=srv["rw"], agg=srv["agg"], subs=srv["subs"],
                     size=srv["size"], rp=srv["rp"], pay=srv["pay"]))
            free_t = jnp.where(done, fin, carry["free_t"])
            srv = dict(srv, valid=srv["valid"] & ~done,
                       fin=jnp.where(done, jnp.inf, srv["fin"]))

            # ======== phase 2: PS deliveries + ACKs ======================
            due = (ps["time"] <= t) & (ps["time"] <= horizon)
            n_due = jnp.sum(due.astype(jnp.int32))
            orderp = jnp.argsort(jnp.where(due, ps["time"], jnp.inf))
            dlv = carry["dlv"]
            dlv = dict(
                n=dlv["n"] + n_due,
                **{k: _append(dlv[k], dlv["n"], ps[k][orderp], n_due)
                   for k in ("time", "rcl", "wk", "gen", "rw", "agg", "subs",
                             "pay")})
            ts_b = ps["time"][orderp]
            gen_b = ps["gen"][orderp]
            due_b = due[orderp]
            rcl_b = ps["rcl"][orderp]
            aom = jax.vmap(
                lambda st_, c: jax_aom_update_block(
                    st_, ts_b, gen_b, due_b & (rcl_b == c)))(
                carry["aom"], jnp.arange(C))
            if static.has_tx:
                # bottleneck-path feedback at each delivery instant (netsim
                # _path_feedback: first switch attaining max pressure), read
                # against the PRE-arrival last_seen: the heap processes a
                # delivery and a same-window arrival at distinct times
                age = (ps["time"][:, None, None]
                       - carry["last_seen"][None, :, :])
                nact = jnp.sum(age <= arrs["active_window"], axis=2
                               ).astype(jnp.float32)               # (Rp, S)
                pr = nact / jnp.maximum(arrs["slots_f"], 1.0)[None, :]
                s_star = jnp.argmax(pr, axis=1)
                fb_n = nact[jnp.arange(Rp), s_star]
                fb_q = arrs["slots_f"][s_star]
                ack, ovf_ack, _ = _ring_insert_vec(
                    carry["ack"], ovf["ack"], due_b,
                    dict(time=(ps["time"] + arrs["ack_delay"])[orderp],
                         cl=rcl_b, nact=fb_n[orderp], qmax=fb_q[orderp],
                         gen=gen_b))
            else:
                ack, ovf_ack = carry["ack"], ovf["ack"]
            ps = dict(ps, time=jnp.where(due, jnp.inf, ps["time"]))
            if static.has_tx:
                tx = carry["tx"]
                due_a = (ack["time"] <= t) & (ack["time"] <= horizon)
                ordera = jnp.argsort(jnp.where(due_a, ack["time"], jnp.inf))

                def ack_body(txc, i):
                    acked = (arrs["w_cluster"] == ack["cl"][i]) & due_a[i]
                    return jax_txctl_ack(
                        txc, acked, jnp.where(due_a[i], ack["time"][i], 0.0),
                        ack["nact"][i], ack["qmax"][i],
                        delivered_gen=ack["gen"][i]), None

                tx, _ = lax.scan(ack_body, tx, ordera)
                ack = dict(ack, time=jnp.where(due_a, jnp.inf, ack["time"]))

            # ======== phase 3: arrivals (transit + gated generations) ====
            gptr0 = carry["gptr"]
            gidx = jnp.clip(gptr0, 0, G - 1)
            g_t = arrs["gen_t"][aW, gidx]
            g_due = (gptr0 < arrs["gcount"]) & (g_t <= t) & (g_t <= horizon)
            if static.has_tx:
                p_send = jax_send_probability(
                    tx, g_t, arrs["delta_thr"], arrs["v_slope"])
                g_send = g_due & (arrs["gen_u"][aW, gidx] < p_send)
            else:
                g_send = g_due
            sent = carry["sent"] + jnp.sum(g_send.astype(jnp.int32))
            deferred = carry["deferred"] + jnp.sum(
                (g_due & ~g_send).astype(jnp.int32))
            grank = arrs["gen_rank"][aW, gidx]
            ordw = jnp.argsort(jnp.where(g_send, grank, _BIG_I32))
            posw = jnp.argsort(ordw)
            n_rows_tab = arrs["rows"].shape[0] - 1
            row_idx = jnp.where(g_send,
                                jnp.minimum(carry["srow"] + posw, n_rows_tab),
                                n_rows_tab)
            srow = carry["srow"] + jnp.sum(g_send.astype(jnp.int32))
            g_rw = arrs["gen_rw"][aW, gidx]
            gptr = gptr0 + g_due.astype(jnp.int32)
            if static.has_tx:
                tx = jax_txctl_send(tx, g_send, g_t, g_t,
                                    ack_timeout=jnp.inf)

            tr_due = (tr["time"] <= t) & (tr["time"] <= horizon)
            act_tr = tr_due[None, :] & (tr["dst"][None, :] == aS[:, None])

            def bcast(x):
                return jnp.broadcast_to(x[None, :], (S,) + x.shape)

            sww = arrs["sw_workers"]
            wv = jnp.clip(sww, 0, W - 1)
            act_g = (sww >= 0) & g_send[wv]
            time_c = jnp.concatenate([g_t[wv], bcast(tr["time"])], axis=1)
            cl_c = jnp.concatenate(
                [arrs["w_cluster"][wv], bcast(tr["rcl"])], axis=1)
            wk_c = jnp.concatenate([arrs["w_id"][wv], bcast(tr["wk"])],
                                   axis=1)
            gen_c = jnp.concatenate([g_t[wv], bcast(tr["gen"])], axis=1)
            rw_c = jnp.concatenate([g_rw[wv], bcast(tr["rw"])], axis=1)
            agg_c = jnp.concatenate(
                [jnp.ones((S, Wm), jnp.int32), bcast(tr["agg"])], axis=1)
            subs_c = jnp.concatenate(
                [jnp.ones((S, Wm), jnp.int32), bcast(tr["subs"])], axis=1)
            size_c = jnp.concatenate(
                [arrs["w_size"][wv], bcast(tr["size"])], axis=1)
            irp_c = jnp.concatenate(
                [jnp.ones((S, Wm), bool), bcast(tr["rp"])], axis=1)
            pay_c = jnp.concatenate(
                [arrs["rows"][row_idx[wv]],
                 jnp.broadcast_to(tr["pay"][None], (S, Rt, D))], axis=1)
            sch_c = jnp.concatenate(
                [arrs["gen_sched"][aW, gidx][wv], bcast(tr["sched"])],
                axis=1)
            sch2_c = jnp.concatenate(
                [arrs["gen_sched2"][aW, gidx][wv], bcast(tr["sched2"])],
                axis=1)
            key2 = jnp.concatenate(
                [grank[wv], jnp.broadcast_to(
                    KEY2_OFF + jnp.arange(Rt, dtype=jnp.int32)[None, :],
                    (S, Rt))], axis=1)
            act_c = jnp.concatenate([act_g, act_tr], axis=1)
            # lexsort (time, sched, sched2, key2) via stable argsorts: the
            # heap drains same-instant events in push (eseq) order, which
            # `sched` reproduces numerically; `sched2` (the parent event's
            # own push time) breaks one level of recursive push-time ties
            o1 = jnp.argsort(key2, axis=1)
            s2 = jnp.take_along_axis(jnp.where(act_c, sch2_c, jnp.inf), o1,
                                     axis=1)
            o1 = jnp.take_along_axis(o1, jnp.argsort(s2, axis=1), axis=1)
            s1 = jnp.take_along_axis(jnp.where(act_c, sch_c, jnp.inf), o1,
                                     axis=1)
            o2 = jnp.take_along_axis(o1, jnp.argsort(s1, axis=1), axis=1)
            t1 = jnp.take_along_axis(jnp.where(act_c, time_c, jnp.inf), o2,
                                     axis=1)
            ordA = jnp.take_along_axis(o2, jnp.argsort(t1, axis=1), axis=1)

            def gat(x):
                return jnp.take_along_axis(x, ordA, axis=1)

            time_s, cl_s, wk_s = gat(time_c), gat(cl_c), gat(wk_c)
            gen_s, rw_s, agg_s = gat(gen_c), gat(rw_c), gat(agg_c)
            subs_s, size_s, act_s = gat(subs_c), gat(size_c), gat(act_c)
            irp_s, sch_s = gat(irp_c), gat(sch_c)
            pay_s = jnp.take_along_axis(pay_c, ordA[:, :, None], axis=1)
            # FIFO: globally unique pseudo-cluster per arrival => Alg.1
            # reduces to pure tail-drop append (no hit is ever possible)
            eff_cl = jnp.where(arrs["is_fifo"][:, None],
                               C + carry["fctr"][:, None] + aA[None, :],
                               cl_s)
            fctr = carry["fctr"] + A

            # -- batch A: arrivals the heap processes BEFORE a completion
            # at this instant (earlier time, or equal time with earlier
            # push) — they still see the pre-completion queue, whose
            # in-flight head (now cleared from srv) occupies one slot
            early_s = act_s & done[:, None] & (
                (time_s < fin[:, None])
                | ((time_s == fin[:, None]) & (sch_s < csched[:, None])))
            cl_preA = q.cluster
            occ_preA = cl_preA >= 0
            pre_cntA = jnp.sum(occ_preA.astype(jnp.int32), axis=1)
            capA = arrs["slots"] - (srv["valid"] | done).astype(jnp.int32)
            q, slots_eA, events_eA = ops.olaf_burst_multi(
                q, eff_cl, wk_s, gen_s, rw_s, pay_s, arrs["rthr"], early_s,
                capA, agg_s, irp_s)
            subsqA, rclqA, sizeqA, first_appA, rdropA = v_aux_walk(
                cl_preA, occ_preA, carry["subsq"], carry["rclq"],
                carry["sizeq"], pre_cntA,
                (slots_eA, events_eA, early_s, eff_cl, cl_s, time_s,
                 subs_s, size_s))
            nonemptyA = jnp.where(
                (pre_cntA == 0) & jnp.isfinite(first_appA), first_appA,
                carry["nonempty"])

            # -- restart-at-finish: netsim dequeues and LOCKS the next
            # head at the completion instant, before any later-pushed
            # same-instant arrival can combine with it
            q, subsq0, rclq0, sizeq0, srv = try_start(
                q, subsqA, rclqA, sizeqA, srv, free_t, nonemptyA)

            # an arrival at an idle switch (queue necessarily empty at
            # this point) starts serializing — and is head-LOCKED — at its
            # arrival instant, before any later-pushed arrival: load the
            # first remaining active row straight into the service register
            act_late = act_s & ~early_s
            has_act = jnp.any(act_late, axis=1)
            fidx = jnp.argmax(act_late, axis=1)
            startA = ~srv["valid"] & has_act

            def rsel(x):
                return x[aS, fidx]

            startA_t = jnp.maximum(free_t, rsel(time_s))
            srv = dict(
                valid=srv["valid"] | startA,
                rcl=jnp.where(startA, rsel(cl_s), srv["rcl"]),
                wk=jnp.where(startA, rsel(wk_s), srv["wk"]),
                gen=jnp.where(startA, rsel(gen_s), srv["gen"]),
                rw=jnp.where(startA, rsel(rw_s), srv["rw"]),
                agg=jnp.where(startA, rsel(agg_s), srv["agg"]),
                subs=jnp.where(startA, rsel(subs_s), srv["subs"]),
                size=jnp.where(startA, rsel(size_s), srv["size"]),
                fin=jnp.where(startA,
                              startA_t + rsel(size_s) / arrs["rate"],
                              srv["fin"]),
                rp=jnp.where(startA, rsel(irp_s), srv["rp"]),
                pay=jnp.where(startA[:, None], pay_s[aS, fidx],
                              srv["pay"]))
            # the loaded row was appended-then-locked in heap terms: it
            # consumes a seq number and counts as enqueued
            q = dataclasses.replace(
                q, next_seq=q.next_seq + startA.astype(jnp.int32))
            act_B = act_late & ~((aA[None, :] == fidx[:, None])
                                 & startA[:, None])

            cl_pre = q.cluster
            occ_pre = cl_pre >= 0
            pre_cnt = jnp.sum(occ_pre.astype(jnp.int32), axis=1)
            cap = arrs["slots"] - srv["valid"].astype(jnp.int32)
            q, slots_a, events_a = ops.olaf_burst_multi(
                q, eff_cl, wk_s, gen_s, rw_s, pay_s, arrs["rthr"], act_B,
                cap, agg_s, irp_s)
            subsq, rclq, sizeq, first_app, rdrop = v_aux_walk(
                cl_pre, occ_pre, subsq0, rclq0, sizeq0, pre_cnt,
                (slots_a, events_a, act_B, eff_cl, cl_s, time_s, subs_s,
                 size_s))
            rdrops = carry["rdrops"] + rdropA + rdrop
            nonempty = jnp.where((pre_cnt == 0) & jnp.isfinite(first_app),
                                 first_app, nonemptyA)
            ls_upd = jnp.max(
                jnp.where(act_s[:, :, None]
                          & (cl_s[:, :, None] == jnp.arange(C)[None, None, :]),
                          time_s[:, :, None], -jnp.inf), axis=1)
            last_seen = jnp.maximum(carry["last_seen"], ls_upd)
            tr = dict(tr, time=jnp.where(tr_due, jnp.inf, tr["time"]))

            # ======== phase 4: service starts ============================
            qf, subsq, rclq, sizeq, srv = try_start(
                q, subsq, rclq, sizeq, srv, free_t, nonempty)

            new = dict(
                carry, q=qf, rclq=rclq, subsq=subsq, sizeq=sizeq, srv=srv,
                free_t=free_t, nonempty=nonempty, last_seen=last_seen,
                tr=tr, ps=ps, ack=ack, aom=aom, dlv=dlv, drp=drp,
                sent=sent, deferred=deferred,
                link_dropped=carry["link_dropped"]
                + jnp.sum(dropped_now.astype(jnp.int32)),
                raw_link_dropped=carry["raw_link_dropped"] + raw_drop_add,
                reroutes=carry["reroutes"]
                + jnp.sum(reroute_now.astype(jnp.int32)),
                forwarded=carry["forwarded"]
                + jnp.sum(ne_fwd.astype(jnp.int32)),
                reroutes_s=carry["reroutes_s"]
                + reroute_now.astype(jnp.int32),
                drops_s=carry["drops_s"] + dropped_now.astype(jnp.int32),
                departed=carry["departed"] + done.astype(jnp.int32),
                rdrops=rdrops, fctr=fctr, lctr=lctr, gptr=gptr, srow=srow,
                ovf=dict(tr=ovf_tr, ps=ovf_ps, ack=ovf_ack))
            if static.has_tx:
                new["tx"] = tx
            return new, None

        carry, _ = lax.scan(step, carry0, ts)
        carry["aom_avg"] = jax.vmap(jax_aom_average, in_axes=(0, None))(
            carry["aom"], horizon)
        return carry

    # the carry is built eagerly by the caller (_init_carry) and donated:
    # every input leaf aliases a same-shaped output leaf, so the scan state
    # is updated in place instead of copied per launch
    return jax.jit(run, donate_argnums=(0,))


# ---------------------------------------------------------------------------
# The sharded scan: per-switch state over a "switch" mesh axis, workers /
# txctl / AoM over a "worker" axis (see _make_runner_sharded below)
# ---------------------------------------------------------------------------
# staged-array axes: leading switch axis (sharded + stripe-permuted), leading
# worker axis (sharded contiguously), everything else replicated
_SWITCH_AXIS_KEYS = ("cand", "ccount", "next_hop", "is_eg", "is_fifo",
                     "slots", "slots_f", "rate", "prop", "rthr", "p_tab",
                     "down_t0", "down_t1", "loss_u", "sw_workers")
_WORKER_AXIS_KEYS = ("gen_t", "gen_sched", "gen_sched2", "gen_rank", "gen_u",
                     "gen_rw", "gcount", "w_cluster", "w_id", "w_size")


def _stripe_perm(S: int, ns: int) -> np.ndarray:
    """Stripe permutation: shard ``d`` holds original switches
    ``d, d+ns, d+2*ns, ...`` so heterogeneous fabrics (a fat-tree's edge /
    agg / core layers are laid out contiguously) spread evenly across
    shards instead of concentrating one layer's queues and transit load on
    one device. ``perm[d*S_loc + i] = i*ns + d`` maps shard-major position
    to original switch id."""
    return (np.arange(S // ns)[None, :] * ns
            + np.arange(ns)[:, None]).reshape(S)


@functools.lru_cache(maxsize=8)
def _make_runner_sharded(static: _Static, ns: int, nw: int, rt_loc: int,
                         keys: Tuple[str, ...]):
    """Build the sharded scan over a ``(ns, nw)`` ("switch", "worker")
    device mesh. Bitwise identical to the single-device runner by
    construction:

    * Per-switch state (queues, service registers, loss counters,
      last-seen) lives shard-resident; per-boundary, only the forwarding
      frontier — the (at most one per switch) completed packet heading to
      the PS ring or another switch — is exchanged, as a handful of
      stacked ``all_gather``s restored to original switch order (the
      stripe permutation's inverse is a reshape/transpose, no collective).
    * Worker generation pointers, txctl state and AoM integrals shard
      along "worker"; the per-boundary gather is four float32 and three
      int32 rows of width W — the ``(W,)`` feedback loop never gathers to
      one device.
    * Transit rows land in the DESTINATION shard's local ring (width
      ``rt_loc``), shrinking the arrival sort axis from ``Rt + Wm`` to
      ``rt_loc + Wm`` per shard — the work reduction that pays for the
      collectives. A replicated ghost ring of arrival times replays the
      single-device ring's global first-free slot assignment; the ghost
      slot rides along as each row's ``key2``, so the depth-3 ring-order
      tie key (and hence every sort) matches the single launch exactly.
    * Replicated bookkeeping (PS/ACK rings, delivery and drop buffers,
      scalar counters) is computed identically on every device from
      gathered values — all integer or order-preserving, no cross-shard
      float reductions, so f32 bit patterns cannot diverge.
    """
    from jax.sharding import Mesh, PartitionSpec

    S, W, C, CC, Q = static.S, static.W, static.C, static.CC, static.Q
    Wm, Rt, Rp, Ra = static.Wm, static.Rt, static.Rp, static.Ra
    G, NL, K = static.G, static.NL, static.K
    Gc, Gd, D = static.Gc, static.Gd, static.D
    S_loc, W_loc, C_loc, Rl = S // ns, W // nw, C // nw, rt_loc
    A = Rl + Wm
    KEY2_OFF = np.int32(W * G)
    aS = jnp.arange(S_loc)
    aW = jnp.arange(W_loc)
    aA = jnp.arange(A)
    devs = np.asarray(jax.devices()[:ns * nw]).reshape(ns, nw)
    mesh = Mesh(devs, ("switch", "worker"))
    SW, WK, RP = (PartitionSpec("switch"), PartitionSpec("worker"),
                  PartitionSpec())

    def unp0(x):
        # gathered shard-major switch axis (leading) -> original order
        return x.reshape((ns, S_loc) + x.shape[1:]).swapaxes(0, 1) \
                .reshape(x.shape)

    def unp_last(x):
        # gathered shard-major switch axis (trailing) -> original order
        return x.reshape(x.shape[:-1] + (ns, S_loc)).swapaxes(-1, -2) \
                .reshape(x.shape)

    def aux_walk(cl0, occ0, subs0, rcl0, size0, nocc0, xs):
        def body(c, x):
            clq, occ, subs, rcl, sizev, nocc, first_app, rdrop = c
            slot, ev, a, cps, cr, t_r, insub, insz = x
            occ_slot = occ[slot]
            hit = jnp.any(occ & (clq == cps))
            is_drop = a & (ev == _EV_DROP)
            rdrop = rdrop + (is_drop & hit).astype(jnp.int32)
            is_agg = a & (ev == _EV_AGG)
            is_rst = a & (ev == _EV_RESET)
            appendv = is_rst & ~occ_slot
            first_app = jnp.where(appendv & (nocc == 0),
                                  jnp.minimum(first_app, t_r), first_app)
            oh = jnp.arange(Q) == slot
            wrt = is_agg | is_rst
            addm = is_agg | (is_rst & occ_slot)
            subs = jnp.where(oh & addm, subs + insub, subs)
            subs = jnp.where(oh & appendv, insub, subs)
            rcl = jnp.where(oh & wrt, cr, rcl)
            sizev = jnp.where(oh & wrt, insz, sizev)
            clq = jnp.where(oh & is_rst, cps, clq)
            nocc = nocc + appendv.astype(jnp.int32)
            occ = occ | (oh & is_rst)
            return (clq, occ, subs, rcl, sizev, nocc, first_app, rdrop), None

        init = (cl0, occ0, subs0, rcl0, size0, nocc0,
                jnp.float32(jnp.inf), jnp.int32(0))
        (clq, occ, subs, rcl, sizev, nocc, first_app, rdrop), _ = lax.scan(
            body, init, xs)
        return subs, rcl, sizev, first_app, rdrop

    v_aux_walk = jax.vmap(aux_walk)

    def run(arrs, ts):
        si = lax.axis_index("switch")
        gid = aS * ns + si  # original switch ids of this shard's rows
        c_base = (lax.axis_index("worker") * C_loc).astype(jnp.int32)
        horizon = arrs["horizon"]
        # static full-width tables, gathered once outside the scan
        wcl_f = lax.all_gather(arrs["w_cluster"], "worker", axis=0,
                               tiled=True)
        wid_f = lax.all_gather(arrs["w_id"], "worker", axis=0, tiled=True)
        wsz_f = lax.all_gather(arrs["w_size"], "worker", axis=0, tiled=True)
        slotsf_f = unp0(lax.all_gather(arrs["slots_f"], "switch", axis=0,
                                       tiled=True))

        def try_start(q, subsq, rclq, sizeq, srv, free_t, nonempty):
            occ = jnp.sum((q.cluster >= 0).astype(jnp.int32), axis=1)
            start_m = ~srv["valid"] & (occ > 0)
            start_t = jnp.maximum(free_t, nonempty)
            slot_min = jnp.argmin(q.seq, axis=1)
            rp_g = q.replaceable[aS, slot_min]
            q_pop, outd = jax.vmap(jax_dequeue)(q)
            qf = jax.tree_util.tree_map(
                lambda a, b: jnp.where(
                    start_m.reshape((S_loc,) + (1,) * (a.ndim - 1)), b, a),
                q, q_pop)
            size_g = sizeq[aS, slot_min]
            srv = dict(
                valid=srv["valid"] | start_m,
                rcl=jnp.where(start_m, rclq[aS, slot_min], srv["rcl"]),
                wk=jnp.where(start_m, outd["worker"], srv["wk"]),
                gen=jnp.where(start_m, outd["gen_time"], srv["gen"]),
                rw=jnp.where(start_m, outd["reward"], srv["rw"]),
                agg=jnp.where(start_m, outd["agg_count"], srv["agg"]),
                subs=jnp.where(start_m, subsq[aS, slot_min], srv["subs"]),
                size=jnp.where(start_m, size_g, srv["size"]),
                fin=jnp.where(start_m, start_t + size_g / arrs["rate"],
                              srv["fin"]),
                rp=jnp.where(start_m, rp_g, srv["rp"]),
                pay=jnp.where(start_m[:, None], outd["payload"],
                              srv["pay"]))
            oh = (jnp.arange(Q)[None, :] == slot_min[:, None]) \
                & start_m[:, None]
            return (qf, jnp.where(oh, 0, subsq), jnp.where(oh, -1, rclq),
                    jnp.where(oh, 1.0, sizeq), srv)

        def step(carry, t):
            q, srv = carry["q"], carry["srv"]
            # ======== phase 1: service completions (local rows) ==========
            fin = srv["fin"]
            done = srv["valid"] & (fin <= t) & (fin <= horizon)
            depth = (jnp.sum(q.cluster >= 0, axis=1)
                     + srv["valid"].astype(jnp.int32))
            cand_valid = jnp.arange(CC)[None, :] < arrs["ccount"][:, None]
            finb = fin[:, None, None]
            down_c = jnp.any((arrs["down_t0"][:, :CC, :] <= finb)
                             & (finb < arrs["down_t1"][:, :CC, :]), axis=2)
            alive = cand_valid & ~down_c
            eg_down = jnp.any((arrs["down_t0"][:, CC, :] <= fin[:, None])
                              & (fin[:, None] < arrs["down_t1"][:, CC, :]),
                              axis=1)
            m = jnp.sum(alive, axis=1)
            if static.route == "hash":
                h = (arrs["cl_real"][jnp.clip(srv["rcl"], 0, C - 1)]
                     .astype(jnp.uint32) * np.uint32(2654435761)
                     + srv["wk"].astype(jnp.uint32) * np.uint32(40503)
                     + gid.astype(jnp.uint32) * np.uint32(9176))
                kth = (h % jnp.maximum(m, 1).astype(jnp.uint32)
                       ).astype(jnp.int32)
                csum = jnp.cumsum(alive, axis=1) - 1
                selcol = jnp.argmax((csum == kth[:, None]) & alive, axis=1)
            elif static.route == "adaptive":
                depth_f = unp0(lax.all_gather(depth, "switch", axis=0,
                                              tiled=True))
                dsts = jnp.clip(arrs["cand"], 0, S - 1)
                dd = jnp.where(alive, depth_f[dsts].astype(jnp.float32),
                               jnp.inf)
                selcol = jnp.argmin(dd, axis=1)
            else:  # static: first alive candidate
                selcol = jnp.argmax(alive, axis=1)
            sel = arrs["cand"][aS, selcol]
            is_eg = arrs["is_eg"]
            drawcol = jnp.where(is_eg, CC, selcol)
            p = arrs["p_tab"][aS, drawcol]
            ctr = carry["lctr"][aS, drawcol]
            u = arrs["loss_u"][aS, drawcol, jnp.clip(ctr, 0, NL - 1)]
            need_draw = done & (p > 0.0) & jnp.where(is_eg, ~eg_down, m > 0)
            lost_draw = need_draw & (u < p)
            lctr = carry["lctr"].at[aS, drawcol].add(
                need_draw.astype(jnp.int32))
            eg_del = is_eg & done & ~eg_down & ~lost_draw
            ne_fwd = ~is_eg & done & (m > 0) & ~lost_draw
            dropped_now = done & ~eg_del & ~ne_fwd
            reroute_now = ne_fwd & (sel != arrs["next_hop"])
            csched = fin - srv["size"] / arrs["rate"]

            # -- forwarding frontier exchange: the completed packets, in
            # original switch order so every replicated decision below is
            # bit-identical to the single launch
            fr_f = unp_last(lax.all_gather(jnp.stack(
                [fin + arrs["prop"], fin, csched, srv["gen"], srv["rw"],
                 srv["size"]]), "switch", axis=1, tiled=True))
            time_g, fin_g, csched_g, gen_g, rw_g, size_g = fr_f
            fr_i = unp_last(lax.all_gather(jnp.stack(
                [sel, srv["rcl"], srv["wk"], srv["agg"], srv["subs"]]),
                "switch", axis=1, tiled=True))
            sel_g, rcl_g, wk_g, agg_g, subs_g = fr_i
            fr_b = unp_last(lax.all_gather(jnp.stack(
                [eg_del, ne_fwd, dropped_now, reroute_now, srv["rp"]]),
                "switch", axis=1, tiled=True))
            egdel_g, nefwd_g, drop_g, rrt_g, rp_g = fr_b
            pay_g = unp0(lax.all_gather(srv["pay"], "switch", axis=0,
                                        tiled=True))
            raw_drop_add = jnp.sum(jnp.where(drop_g, subs_g, 0))

            orderd = jnp.argsort(jnp.where(drop_g, fin_g, jnp.inf))
            drp = carry["drp"]
            n_drp = jnp.sum(drop_g.astype(jnp.int32))
            drp = dict(
                n=drp["n"] + n_drp,
                **{k: _append(drp[k], drp["n"], v[orderd], n_drp)
                   for k, v in (("time", fin_g), ("rcl", rcl_g),
                                ("gen", gen_g), ("subs", subs_g))})

            ovf = carry["ovf"]
            ps, ovf_ps, _ = _ring_insert_vec(
                carry["ps"], ovf["ps"], egdel_g,
                dict(time=time_g, rcl=rcl_g, wk=wk_g, gen=gen_g, rw=rw_g,
                     agg=agg_g, subs=subs_g, pay=pay_g))
            # ghost transit ring: replicated arrival times replaying the
            # single-device ring's global slot assignment — the assigned
            # slot is the row's depth-3 tie key (key2) wherever it lands
            ghost, ovf_tr, slot_g = _ring_insert_vec(
                dict(time=carry["ghost"]), ovf["tr"], nefwd_g,
                dict(time=time_g))
            mine = nefwd_g & (sel_g % ns == si)
            tr, ovf_trl, _ = _ring_insert_vec(
                carry["tr"], ovf["trl"], mine,
                dict(time=time_g, sched=fin_g, sched2=csched_g, dst=sel_g,
                     rcl=rcl_g, wk=wk_g, gen=gen_g, rw=rw_g, agg=agg_g,
                     subs=subs_g, size=size_g, rp=rp_g,
                     key2=KEY2_OFF + slot_g.astype(jnp.int32), pay=pay_g))
            free_t = jnp.where(done, fin, carry["free_t"])
            srv = dict(srv, valid=srv["valid"] & ~done,
                       fin=jnp.where(done, jnp.inf, srv["fin"]))

            # ======== phase 2: PS deliveries + ACKs (replicated) =========
            due = (ps["time"] <= t) & (ps["time"] <= horizon)
            n_due = jnp.sum(due.astype(jnp.int32))
            orderp = jnp.argsort(jnp.where(due, ps["time"], jnp.inf))
            dlv = carry["dlv"]
            dlv = dict(
                n=dlv["n"] + n_due,
                **{k: _append(dlv[k], dlv["n"], ps[k][orderp], n_due)
                   for k in ("time", "rcl", "wk", "gen", "rw", "agg", "subs",
                             "pay")})
            ts_b = ps["time"][orderp]
            gen_b = ps["gen"][orderp]
            due_b = due[orderp]
            rcl_b = ps["rcl"][orderp]
            # AoM shards along "worker": each shard folds its C_loc rows
            aom = jax.vmap(
                lambda st_, c: jax_aom_update_block(
                    st_, ts_b, gen_b, due_b & (rcl_b == c)))(
                carry["aom"], c_base + jnp.arange(C_loc))
            if static.has_tx:
                age = (ps["time"][:, None, None]
                       - carry["last_seen"][None, :, :])
                nact_l = jnp.sum(age <= arrs["active_window"], axis=2
                                 ).astype(jnp.float32)       # (Rp, S_loc)
                nact = unp_last(lax.all_gather(nact_l, "switch", axis=1,
                                               tiled=True))  # (Rp, S)
                pr = nact / jnp.maximum(slotsf_f, 1.0)[None, :]
                s_star = jnp.argmax(pr, axis=1)
                fb_n = nact[jnp.arange(Rp), s_star]
                fb_q = slotsf_f[s_star]
                ack, ovf_ack, _ = _ring_insert_vec(
                    carry["ack"], ovf["ack"], due_b,
                    dict(time=(ps["time"] + arrs["ack_delay"])[orderp],
                         cl=rcl_b, nact=fb_n[orderp], qmax=fb_q[orderp],
                         gen=gen_b))
            else:
                ack, ovf_ack = carry["ack"], ovf["ack"]
            ps = dict(ps, time=jnp.where(due, jnp.inf, ps["time"]))
            if static.has_tx:
                tx = carry["tx"]
                due_a = (ack["time"] <= t) & (ack["time"] <= horizon)
                ordera = jnp.argsort(jnp.where(due_a, ack["time"], jnp.inf))

                def ack_body(txc, i):
                    acked = (arrs["w_cluster"] == ack["cl"][i]) & due_a[i]
                    return jax_txctl_ack(
                        txc, acked, jnp.where(due_a[i], ack["time"][i], 0.0),
                        ack["nact"][i], ack["qmax"][i],
                        delivered_gen=ack["gen"][i]), None

                tx, _ = lax.scan(ack_body, tx, ordera)
                ack = dict(ack, time=jnp.where(due_a, jnp.inf, ack["time"]))

            # ======== phase 3: arrivals (transit + gated generations) ====
            # worker side: local generation gating, then one gather of the
            # frontier rows — never the full (W, G) tables
            gptr0 = carry["gptr"]
            gidx = jnp.clip(gptr0, 0, G - 1)
            g_t = arrs["gen_t"][aW, gidx]
            g_due = (gptr0 < arrs["gcount"]) & (g_t <= t) & (g_t <= horizon)
            if static.has_tx:
                p_send = jax_send_probability(
                    tx, g_t, arrs["delta_thr"], arrs["v_slope"])
                g_send = g_due & (arrs["gen_u"][aW, gidx] < p_send)
            else:
                g_send = g_due
            grank = arrs["gen_rank"][aW, gidx]
            g_rw = arrs["gen_rw"][aW, gidx]
            wk_f32 = lax.all_gather(jnp.stack(
                [g_t, g_rw, arrs["gen_sched"][aW, gidx],
                 arrs["gen_sched2"][aW, gidx]]), "worker", axis=1,
                tiled=True)
            g_t_f, g_rw_f, sch_w_f, sch2_w_f = wk_f32
            wk_i32 = lax.all_gather(jnp.stack(
                [g_send.astype(jnp.int32), g_due.astype(jnp.int32), grank]),
                "worker", axis=1, tiled=True)
            g_send_f = wk_i32[0].astype(bool)
            g_due_f = wk_i32[1].astype(bool)
            grank_f = wk_i32[2]
            sent = carry["sent"] + jnp.sum(g_send_f.astype(jnp.int32))
            deferred = carry["deferred"] + jnp.sum(
                (g_due_f & ~g_send_f).astype(jnp.int32))
            ordw = jnp.argsort(jnp.where(g_send_f, grank_f, _BIG_I32))
            posw = jnp.argsort(ordw)
            n_rows_tab = arrs["rows"].shape[0] - 1
            row_idx = jnp.where(g_send_f,
                                jnp.minimum(carry["srow"] + posw, n_rows_tab),
                                n_rows_tab)
            srow = carry["srow"] + jnp.sum(g_send_f.astype(jnp.int32))
            gptr = gptr0 + g_due.astype(jnp.int32)
            if static.has_tx:
                tx = jax_txctl_send(tx, g_send, g_t, g_t,
                                    ack_timeout=jnp.inf)

            # switch side: local transit ring + this shard's ingress rows
            tr_due = (tr["time"] <= t) & (tr["time"] <= horizon)
            act_tr = tr_due[None, :] & (tr["dst"][None, :] == gid[:, None])

            def bcast(x):
                return jnp.broadcast_to(x[None, :], (S_loc,) + x.shape)

            sww = arrs["sw_workers"]
            wv = jnp.clip(sww, 0, W - 1)
            act_g = (sww >= 0) & g_send_f[wv]
            time_c = jnp.concatenate([g_t_f[wv], bcast(tr["time"])], axis=1)
            cl_c = jnp.concatenate([wcl_f[wv], bcast(tr["rcl"])], axis=1)
            wk_c = jnp.concatenate([wid_f[wv], bcast(tr["wk"])], axis=1)
            gen_c = jnp.concatenate([g_t_f[wv], bcast(tr["gen"])], axis=1)
            rw_c = jnp.concatenate([g_rw_f[wv], bcast(tr["rw"])], axis=1)
            agg_c = jnp.concatenate(
                [jnp.ones((S_loc, Wm), jnp.int32), bcast(tr["agg"])], axis=1)
            subs_c = jnp.concatenate(
                [jnp.ones((S_loc, Wm), jnp.int32), bcast(tr["subs"])],
                axis=1)
            size_c = jnp.concatenate([wsz_f[wv], bcast(tr["size"])], axis=1)
            irp_c = jnp.concatenate(
                [jnp.ones((S_loc, Wm), bool), bcast(tr["rp"])], axis=1)
            pay_c = jnp.concatenate(
                [arrs["rows"][row_idx[wv]],
                 jnp.broadcast_to(tr["pay"][None], (S_loc, Rl, D))], axis=1)
            sch_c = jnp.concatenate([sch_w_f[wv], bcast(tr["sched"])],
                                    axis=1)
            sch2_c = jnp.concatenate([sch2_w_f[wv], bcast(tr["sched2"])],
                                     axis=1)
            # the ring rows carry their ghost (global) slot as key2, so the
            # depth-3 tie falls back to the single-device ring order even
            # though the local slot differs
            key2 = jnp.concatenate([grank_f[wv], bcast(tr["key2"])], axis=1)
            act_c = jnp.concatenate([act_g, act_tr], axis=1)
            o1 = jnp.argsort(key2, axis=1)
            s2 = jnp.take_along_axis(jnp.where(act_c, sch2_c, jnp.inf), o1,
                                     axis=1)
            o1 = jnp.take_along_axis(o1, jnp.argsort(s2, axis=1), axis=1)
            s1 = jnp.take_along_axis(jnp.where(act_c, sch_c, jnp.inf), o1,
                                     axis=1)
            o2 = jnp.take_along_axis(o1, jnp.argsort(s1, axis=1), axis=1)
            t1 = jnp.take_along_axis(jnp.where(act_c, time_c, jnp.inf), o2,
                                     axis=1)
            ordA = jnp.take_along_axis(o2, jnp.argsort(t1, axis=1), axis=1)

            def gat(x):
                return jnp.take_along_axis(x, ordA, axis=1)

            time_s, cl_s, wk_s = gat(time_c), gat(cl_c), gat(wk_c)
            gen_s, rw_s, agg_s = gat(gen_c), gat(rw_c), gat(agg_c)
            subs_s, size_s, act_s = gat(subs_c), gat(size_c), gat(act_c)
            irp_s, sch_s = gat(irp_c), gat(sch_c)
            pay_s = jnp.take_along_axis(pay_c, ordA[:, :, None], axis=1)
            eff_cl = jnp.where(arrs["is_fifo"][:, None],
                               C + carry["fctr"][:, None] + aA[None, :],
                               cl_s)
            fctr = carry["fctr"] + A

            early_s = act_s & done[:, None] & (
                (time_s < fin[:, None])
                | ((time_s == fin[:, None]) & (sch_s < csched[:, None])))
            cl_preA = q.cluster
            occ_preA = cl_preA >= 0
            pre_cntA = jnp.sum(occ_preA.astype(jnp.int32), axis=1)
            capA = arrs["slots"] - (srv["valid"] | done).astype(jnp.int32)
            q, slots_eA, events_eA = ops.olaf_burst_multi(
                q, eff_cl, wk_s, gen_s, rw_s, pay_s, arrs["rthr"], early_s,
                capA, agg_s, irp_s)
            subsqA, rclqA, sizeqA, first_appA, rdropA = v_aux_walk(
                cl_preA, occ_preA, carry["subsq"], carry["rclq"],
                carry["sizeq"], pre_cntA,
                (slots_eA, events_eA, early_s, eff_cl, cl_s, time_s,
                 subs_s, size_s))
            nonemptyA = jnp.where(
                (pre_cntA == 0) & jnp.isfinite(first_appA), first_appA,
                carry["nonempty"])

            q, subsq0, rclq0, sizeq0, srv = try_start(
                q, subsqA, rclqA, sizeqA, srv, free_t, nonemptyA)

            act_late = act_s & ~early_s
            has_act = jnp.any(act_late, axis=1)
            fidx = jnp.argmax(act_late, axis=1)
            startA = ~srv["valid"] & has_act

            def rsel(x):
                return x[aS, fidx]

            startA_t = jnp.maximum(free_t, rsel(time_s))
            srv = dict(
                valid=srv["valid"] | startA,
                rcl=jnp.where(startA, rsel(cl_s), srv["rcl"]),
                wk=jnp.where(startA, rsel(wk_s), srv["wk"]),
                gen=jnp.where(startA, rsel(gen_s), srv["gen"]),
                rw=jnp.where(startA, rsel(rw_s), srv["rw"]),
                agg=jnp.where(startA, rsel(agg_s), srv["agg"]),
                subs=jnp.where(startA, rsel(subs_s), srv["subs"]),
                size=jnp.where(startA, rsel(size_s), srv["size"]),
                fin=jnp.where(startA,
                              startA_t + rsel(size_s) / arrs["rate"],
                              srv["fin"]),
                rp=jnp.where(startA, rsel(irp_s), srv["rp"]),
                pay=jnp.where(startA[:, None], pay_s[aS, fidx],
                              srv["pay"]))
            q = dataclasses.replace(
                q, next_seq=q.next_seq + startA.astype(jnp.int32))
            act_B = act_late & ~((aA[None, :] == fidx[:, None])
                                 & startA[:, None])

            cl_pre = q.cluster
            occ_pre = cl_pre >= 0
            pre_cnt = jnp.sum(occ_pre.astype(jnp.int32), axis=1)
            cap = arrs["slots"] - srv["valid"].astype(jnp.int32)
            q, slots_a, events_a = ops.olaf_burst_multi(
                q, eff_cl, wk_s, gen_s, rw_s, pay_s, arrs["rthr"], act_B,
                cap, agg_s, irp_s)
            subsq, rclq, sizeq, first_app, rdrop = v_aux_walk(
                cl_pre, occ_pre, subsq0, rclq0, sizeq0, pre_cnt,
                (slots_a, events_a, act_B, eff_cl, cl_s, time_s, subs_s,
                 size_s))
            rdrops = carry["rdrops"] + rdropA + rdrop
            nonempty = jnp.where((pre_cnt == 0) & jnp.isfinite(first_app),
                                 first_app, nonemptyA)
            ls_upd = jnp.max(
                jnp.where(act_s[:, :, None]
                          & (cl_s[:, :, None]
                             == jnp.arange(C)[None, None, :]),
                          time_s[:, :, None], -jnp.inf), axis=1)
            last_seen = jnp.maximum(carry["last_seen"], ls_upd)
            tr = dict(tr, time=jnp.where(tr_due, jnp.inf, tr["time"]))
            # the ghost ring frees the same rows the local rings free: the
            # single-device clear condition evaluated on the mirrored times
            gh_t = ghost["time"]
            gh_t = jnp.where((gh_t <= t) & (gh_t <= horizon), jnp.inf, gh_t)

            # ======== phase 4: service starts ============================
            qf, subsq, rclq, sizeq, srv = try_start(
                q, subsq, rclq, sizeq, srv, free_t, nonempty)

            new = dict(
                carry, q=qf, rclq=rclq, subsq=subsq, sizeq=sizeq, srv=srv,
                free_t=free_t, nonempty=nonempty, last_seen=last_seen,
                tr=tr, ghost=gh_t, ps=ps, ack=ack, aom=aom, dlv=dlv,
                drp=drp, sent=sent, deferred=deferred,
                link_dropped=carry["link_dropped"]
                + jnp.sum(drop_g.astype(jnp.int32)),
                raw_link_dropped=carry["raw_link_dropped"] + raw_drop_add,
                reroutes=carry["reroutes"]
                + jnp.sum(rrt_g.astype(jnp.int32)),
                forwarded=carry["forwarded"]
                + jnp.sum(nefwd_g.astype(jnp.int32)),
                reroutes_s=carry["reroutes_s"]
                + reroute_now.astype(jnp.int32),
                drops_s=carry["drops_s"] + dropped_now.astype(jnp.int32),
                departed=carry["departed"] + done.astype(jnp.int32),
                rdrops=rdrops, fctr=fctr, lctr=lctr, gptr=gptr, srow=srow,
                ovf=dict(tr=ovf_tr, ps=ovf_ps, ack=ovf_ack, trl=ovf_trl))
            if static.has_tx:
                new["tx"] = tx
            return new, None

        carry0 = _init_carry(static, n_s=S_loc, n_w=W_loc, n_aom=C_loc,
                             rt=Rl, sharded=True)
        carry, _ = lax.scan(step, carry0, ts)
        out = {k: carry[k] for k in (
            "q", "rdrops", "departed", "drops_s", "reroutes_s", "dlv",
            "drp", "sent", "deferred", "link_dropped", "raw_link_dropped",
            "reroutes", "forwarded")}
        out["srv"] = dict(valid=carry["srv"]["valid"])
        out["aom_avg"] = jax.vmap(jax_aom_average, in_axes=(0, None))(
            carry["aom"], horizon)
        # local-ring overflow differs per switch shard: surface it globally
        # (exact i32 psum) so the host can retry with a wider local ring
        out["ovf"] = dict(
            tr=carry["ovf"]["tr"], ps=carry["ovf"]["ps"],
            ack=carry["ovf"]["ack"],
            trl=lax.psum(carry["ovf"]["trl"].astype(jnp.int32),
                         "switch") > 0)
        return out

    in_spec = {k: SW if k in _SWITCH_AXIS_KEYS
               else WK if k in _WORKER_AXIS_KEYS else RP for k in keys}
    out_specs = dict(
        q=SW, rdrops=SW, departed=SW, drops_s=SW, reroutes_s=SW, srv=SW,
        aom_avg=WK, dlv=RP, drp=RP, sent=RP, deferred=RP, link_dropped=RP,
        raw_link_dropped=RP, reroutes=RP, forwarded=RP, ovf=RP)
    return jax.jit(jax.shard_map(run, mesh=mesh, in_specs=(in_spec, RP),
                                 out_specs=out_specs, check_vma=False))


def _mesh_shape(mesh) -> Tuple[int, int]:
    """Normalize a mesh request to ``(switch_shards, worker_shards)``:
    an int (switch shards only), a 2-tuple, or a :class:`jax.sharding.Mesh`
    whose axis sizes are read by name ("switch" required, "worker"
    optional — ``distributed.sharding.switch_mesh`` qualifies)."""
    if isinstance(mesh, int):
        return mesh, 1
    if isinstance(mesh, tuple):
        ns, nw = mesh
        return int(ns), int(nw)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if "switch" not in sizes:
        raise ValueError(f"mesh {mesh} has no 'switch' axis")
    return int(sizes["switch"]), int(sizes.get("worker", 1))


# ---------------------------------------------------------------------------
# Host entry point and result assembly
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class VecSimResult:
    """Vectorized-run output: the event-heap-compatible :class:`SimResult`
    plus device-side extras the heap cannot produce cheaply."""
    sim: SimResult
    aom: Dict[int, float]            # real cluster id -> time-averaged AoM
    n_steps: int                     # grid boundaries scanned
    h2d_transfers: int               # host->device arrays staged (total)
    forwarded: int                   # inter-switch forwards
    delivery_times: np.ndarray       # (n_del,) exact delivery instants
    delivered_payloads: np.ndarray   # (n_del, D), delivery order
    final_counts: np.ndarray         # (S_real, Q) residual per-slot agg
    residual: Dict[str, int]         # per-switch queue + in-service packets


def run_vecsim(cfg: SimCfg, *, dt: Optional[float] = None,
               grid: Optional[np.ndarray] = None, dim: int = 1,
               payload_rows: Optional[np.ndarray] = None,
               gen_rewards: Optional[np.ndarray] = None,
               pad_pow2: bool = True, allow_coarse: bool = False,
               grid_bucket: int = 128, mesh=None,
               rt_loc: Optional[int] = None) -> VecSimResult:
    """Run ``cfg`` through the vectorized scan.

    Grid selection: an explicit ``grid`` wins; else ``dt`` selects
    :func:`uniform_grid`; else an exact event-aligned grid is derived
    from one oracle heap run (:func:`oracle_event_times`) — accurate but
    host-bound, so performance-sensitive callers should pass ``dt`` or a
    precomputed grid.

    ``mesh`` selects the sharded runner: an int (switch shards), an
    ``(switch_shards, worker_shards)`` tuple, or a
    :class:`jax.sharding.Mesh` with a "switch" (and optionally "worker")
    axis — e.g. ``distributed.sharding.vecsim_mesh()``. The sharded scan
    is bitwise identical to the single-device one (the equivalence suite
    in ``tests/test_vecsim_sharded.py`` asserts it). ``rt_loc`` overrides
    the per-shard transit-ring width; on local-ring overflow the run
    transparently retries with a doubled ring (a recompile, logged by the
    retry loop's growth), so the default only costs time, never
    correctness."""
    comp = compile_scenario(cfg, dim=dim, payload_rows=payload_rows,
                            gen_rewards=gen_rewards, pad_pow2=pad_pow2)
    if grid is None:
        if dt is not None:
            grid = uniform_grid(cfg, dt, allow_coarse=allow_coarse,
                                bucket=grid_bucket)
        else:
            grid, _ = oracle_event_times(cfg, bucket=grid_bucket)
    ts = np.asarray(grid, np.float32)
    if mesh is None:
        runner = _make_runner(comp.static)
        carry0 = _init_carry(comp.static)
        host = jax.device_get(runner(carry0, comp.arrays, ts))
        return _assemble(cfg, comp, host, len(ts))

    ns, nw = _mesh_shape(mesh)
    st = comp.static
    n_dev = len(jax.devices())
    if ns * nw > n_dev:
        raise ValueError(
            f"mesh ({ns} switch x {nw} worker shards) needs {ns * nw} "
            f"devices, only {n_dev} available")
    if st.S % ns or st.W % nw or st.C % nw:
        raise ValueError(
            f"padded dims (S={st.S}, W={st.W}, C={st.C}) are not divisible "
            f"by the mesh ({ns} switch x {nw} worker shards)")
    perm = _stripe_perm(st.S, ns)
    arrs = dict(comp.arrays)
    for k in _SWITCH_AXIS_KEYS:
        arrs[k] = comp.arrays[k][perm]
    keys = tuple(sorted(arrs))
    if rt_loc is not None:
        rl = rt_loc
    else:
        # destination-aware local-ring bound: a source's in-flight rows can
        # land in shard d's ring only if one of its candidates lives there
        # (stripe owner of original switch v is v % ns). Skew beyond the
        # bound overflows the local ring, which the runner reports and we
        # retry doubled — capped at Rt, since a destination subset can
        # never hold more rows than the global ring
        cand, cnt = comp.arrays["cand"], comp.arrays["ccount"]
        inflow = np.zeros(ns, np.int64)
        for u in range(st.S):
            if comp.wire[u] > 0:
                for d in {int(c) % ns
                          for c in cand[u, :int(cnt[u])] if c >= 0}:
                    inflow[d] += int(comp.wire[u])
        rl = min(st.Rt, _pow2(max(int(inflow.max()), 2)))
    while True:
        runner = _make_runner_sharded(st, ns, nw, rl, keys)
        host = jax.device_get(runner(arrs, ts))
        if not bool(host["ovf"].pop("trl")) or rl >= st.Rt:
            break
        rl = min(st.Rt, rl * 2)
    inv = np.argsort(perm)
    host = dict(host)
    host["q"] = jax.tree_util.tree_map(lambda a: a[inv], host["q"])
    host["srv"] = dict(valid=host["srv"]["valid"][inv])
    for k in ("rdrops", "departed", "drops_s", "reroutes_s"):
        host[k] = host[k][inv]
    return _assemble(cfg, comp, host, len(ts))


def auto_dt(cfg: SimCfg, *, tol: float = 0.05, prefix_frac: float = 0.25,
            max_iters: int = 6, dim: int = 1) -> float:
    """Pick the largest :func:`uniform_grid` ``dt`` whose coarse-grid AoM
    stays within ``tol`` (relative, worst cluster) of the exact
    event-aligned grid, bisected in log space against one oracle run on a
    short prefix (``prefix_frac`` of the horizon). Thousands-of-worker
    scenarios then skip the event-aligned grid (one heap event per send)
    and pay only ``horizon / dt`` boundaries, trading a bounded AoM error
    the caller names explicitly."""
    check_vecsim_supported(cfg)
    min_size = min((w.size_bits for w in cfg.workers), default=1)
    max_rate = max((s.uplink.capacity_bps for s in cfg.switches), default=1.0)
    lo = min_size / max_rate  # the documented exact-regime bound
    pre = dataclasses.replace(cfg, horizon=float(cfg.horizon) * prefix_frac)
    hi = max(float(pre.horizon) / 8.0, lo)
    if hi <= lo:
        return lo
    ref = run_vecsim(pre, dim=dim)  # exact event-aligned prefix reference

    def rel_err(dt: float) -> float:
        res = run_vecsim(pre, dt=dt, dim=dim, allow_coarse=True)
        worst = 0.0
        for c, want in ref.aom.items():
            got = res.aom.get(c, float("inf"))
            worst = max(worst, abs(got - want) / max(abs(want), 1e-6))
        return worst

    if rel_err(hi) <= tol:
        return hi
    good, bad = lo, hi
    for _ in range(max_iters):
        mid = math.sqrt(good * bad)
        if rel_err(mid) <= tol:
            good = mid
        else:
            bad = mid
    return good


def _assemble(cfg: SimCfg, comp: _Compiled, host, n_steps: int
              ) -> VecSimResult:
    st = comp.static
    S0 = comp.n_real_switches
    names = comp.switch_names
    cl_real = comp.arrays["cl_real"]
    n_del = int(host["dlv"]["n"])
    n_drop = int(host["drp"]["n"])
    ovf = host["ovf"]
    if (bool(ovf["tr"]) or bool(ovf["ps"]) or bool(ovf["ack"])
            or n_del > st.Gc or n_drop > st.Gd):
        raise RuntimeError(
            "vecsim internal buffer overflow (tr=%s ps=%s ack=%s dlv=%d/%d "
            "drp=%d/%d) — ring bound estimate too small for this scenario"
            % (bool(ovf["tr"]), bool(ovf["ps"]), bool(ovf["ack"]),
               n_del, st.Gc, n_drop, st.Gd))

    dlv = host["dlv"]
    order = np.argsort(dlv["time"][:n_del], kind="stable")
    deliveries: Dict[int, List[Tuple[float, float]]] = defaultdict(list)
    delivered_updates: List[Update] = []
    agg_counts: List[int] = []
    for i in order:
        rc = int(cl_real[int(dlv["rcl"][i])])
        t = float(dlv["time"][i])
        g = float(dlv["gen"][i])
        deliveries[rc].append((t, g))
        delivered_updates.append(Update(
            cluster_id=rc, worker_id=int(dlv["wk"][i]), gen_time=g,
            reward=float(dlv["rw"][i]), payload=None,
            agg_count=int(dlv["agg"][i]), subsumed=int(dlv["subs"][i])))
        agg_counts.append(int(dlv["agg"][i]))

    max_gen: Dict[int, float] = {}
    for u in delivered_updates:
        max_gen[u.cluster_id] = max(max_gen.get(u.cluster_id, -np.inf),
                                    u.gen_time)
    drp = host["drp"]
    unrecovered = sum(
        1 for i in range(n_drop)
        if float(drp["gen"][i]) > max_gen.get(
            int(cl_real[int(drp["rcl"][i])]), -np.inf))

    q = host["q"]
    queue_stats = {
        name: dict(enqueued=int(q.next_seq[s]), dropped=int(q.n_dropped[s]),
                   aggregations=int(q.n_agg[s]),
                   replacements=int(q.n_repl[s]),
                   reward_drops=int(host["rdrops"][s]),
                   departed=int(host["departed"][s]))
        for s, name in enumerate(names)}
    drops_by_switch = {names[s]: int(host["drops_s"][s])
                       for s in range(S0) if int(host["drops_s"][s])}
    reroutes_by_switch = {names[s]: int(host["reroutes_s"][s])
                          for s in range(S0) if int(host["reroutes_s"][s])}

    sim = SimResult(
        horizon=cfg.horizon,
        deliveries=dict(deliveries),
        delivered_updates=delivered_updates,
        generated=comp.generated,
        sent=int(host["sent"]),
        deferred=int(host["deferred"]),
        received_at_ps=n_del,
        # netsim's "raw" counter sums subsumed (fresh sends represented),
        # not agg_count (which replacements can under-count)
        raw_updates_delivered=int(np.sum(dlv["subs"][:n_del])),
        queue_stats=queue_stats,
        agg_counts=agg_counts,
        link_dropped=int(host["link_dropped"]),
        raw_link_dropped=int(host["raw_link_dropped"]),
        reroutes=int(host["reroutes"]),
        unrecovered_drops=int(unrecovered),
        drops_by_switch=drops_by_switch,
        reroutes_by_switch=reroutes_by_switch,
        unique_delivered=int(np.sum(dlv["subs"][:n_del])))

    occ = np.asarray(q.cluster[:S0]) >= 0
    final_counts = np.where(occ, np.asarray(q.agg_count[:S0]), 0)
    residual = {
        names[s]: int(occ[s].sum()) + int(host["srv"]["valid"][s])
        for s in range(S0)}
    aom = {comp.cluster_ids[c]: float(host["aom_avg"][c])
           for c in range(len(comp.cluster_ids))}
    h2d = len(jax.tree_util.tree_leaves(comp.arrays)) + 1  # + the grid
    return VecSimResult(
        sim=sim, aom=aom, n_steps=n_steps, h2d_transfers=h2d,
        forwarded=int(host["forwarded"]),
        delivery_times=np.asarray(dlv["time"][:n_del][order]),
        delivered_payloads=np.asarray(dlv["pay"][:n_del][order]),
        final_counts=final_counts, residual=residual)
