"""Equivalence suite for the vectorized device-resident simulator.

``repro.core.vecsim`` replays a whole scenario as one jitted ``lax.scan``;
this suite proves the scan matches the event-heap oracle
(``repro.core.netsim``) update for update.

Exactness precondition (see the vecsim module docstring): the suite
parameterizes every topology with *dyadic* link rates (powers of two in
bps), propagation delays, and generation intervals with zero jitter, so
every event time is a dyadic rational exactly representable in both
float32 and float64. Under that precondition the heap's event order is
arithmetic-exact and the scan reproduces it bit for bit — genuine
same-instant ties resolve through the heap's push-order model, which the
scan mirrors. Non-dyadic configs remain correct but the comparison keys
must tolerate one-ULP accumulation noise (the relative gen-time key
below).
"""
import numpy as np
import pytest

from repro.core import vecsim
from repro.core.aom import average_aom
from repro.core.netsim import (FaultSpec, LinkFault, NetworkSimulator,
                               multihop_cfg)
from repro.core.olaf_queue import (EVENT_OF_CLASS, _EV_AGG, _EV_DROP,
                                   _EV_RESET, classify_slot_events)
from repro.core.topology import (SwitchSpec, TopologySpec, build_sim_cfg,
                                 fattree_spec, multips_spec)
from repro.core.txctl import TxControlConfig

# dyadic parameter pools: every value is a power of two (or a small
# integer multiple of one), so service/propagation/generation arithmetic
# stays exact in f32 and f64
_RATES_BPS = [2.0 ** k for k in (17, 18, 19, 20, 21)]
_PROPS = [2.0 ** -13, 2.0 ** -12, 2.0 ** -11]
_INTERVALS = [2.0 ** -7, 3 * 2.0 ** -7, 2.0 ** -6]
_SLOTS = [2, 3, 4, 6]


def _counters(res):
    return {f: getattr(res, f) for f in (
        "generated", "sent", "deferred", "received_at_ps",
        "raw_updates_delivered", "unique_delivered", "link_dropped",
        "raw_link_dropped", "reroutes", "stale_rejected", "stale_deferred",
        "ps_dropped")}


def assert_equivalent(cfg, *, exact_times=True):
    """Oracle heap run vs vectorized scan on the same cfg.

    ``exact_times=True`` (the dyadic regime) compares generation times
    bitwise; otherwise a 1e-5 relative tolerance absorbs f32 accumulation
    noise on long horizons.
    """
    grid, ref = vecsim.oracle_event_times(cfg)
    res = vecsim.run_vecsim(cfg, grid=grid)
    sim = res.sim

    def keys(updates):
        return sorted((u.cluster_id, u.worker_id, float(u.gen_time),
                       u.agg_count, u.subsumed) for u in updates)

    ka, kb = keys(ref.delivered_updates), keys(sim.delivered_updates)
    assert len(ka) == len(kb), (len(ka), len(kb))
    for a, b in zip(ka, kb):
        assert a[:2] == b[:2] and a[3:] == b[3:], (a, b)
        if exact_times:
            assert a[2] == b[2], (a, b)
        else:
            assert abs(a[2] - b[2]) <= 1e-5 * max(1.0, a[2]), (a, b)
    assert ref.queue_stats == sim.queue_stats
    assert _counters(ref) == _counters(sim)
    assert ref.drops_by_switch == sim.drops_by_switch
    assert ref.reroutes_by_switch == sim.reroutes_by_switch
    for c, pairs in ref.deliveries.items():
        want = average_aom(pairs, cfg.horizon)
        got = res.aom.get(c, 0.0)
        assert abs(got - want) <= 2e-4 * max(1.0, abs(want)), (c, got, want)
    return ref, res


def _random_dyadic_cfg(trial: int):
    """A random layered DAG under the dyadic exactness precondition:
    2-4 layers, random multi-path candidate sets into strictly later
    layers (acyclic by construction), mixed olaf/fifo disciplines,
    optional per-switch reward gates, random route policy, and optional
    link faults (i.i.d. loss + a scheduled outage window) and txctl send
    gating."""
    rng = np.random.default_rng(1000 + trial)
    n_layers = int(rng.integers(2, 5))
    sizes = [int(rng.integers(1, 4)) for _ in range(n_layers)]
    names = [[f"L{i}S{j}" for j in range(sizes[i])]
             for i in range(n_layers)]
    switches = []
    for i in range(n_layers):
        later = [n for lay in names[i + 1:] for n in lay]
        for nm in names[i]:
            if i == n_layers - 1 or not later:
                hops = None
            else:
                k = int(rng.integers(1, min(3, len(later)) + 1))
                pick = rng.choice(len(later), size=k, replace=False)
                hops = tuple(later[int(x)] for x in pick)
            switches.append(SwitchSpec(
                name=nm, next_hops=hops,
                queue_slots=int(_SLOTS[rng.integers(len(_SLOTS))]),
                rate_gbps=_RATES_BPS[rng.integers(len(_RATES_BPS))] / 1e9,
                prop_delay=float(_PROPS[rng.integers(len(_PROPS))]),
                queue="fifo" if rng.random() < 0.2 else "olaf",
                reward_threshold=2.0 if rng.random() < 0.25 else None))
    policy = ("static", "hash", "adaptive")[int(rng.integers(3))]
    spec = TopologySpec(switches, route_policy=policy)

    faults = None
    if rng.random() < 0.5:
        links = []
        for s in spec.switches:
            if rng.random() < 0.4:
                down = []
                if rng.random() < 0.5:
                    t0 = float([2.0 ** -4, 2.0 ** -3,
                                2.0 ** -2][rng.integers(3)])
                    down = [(t0, t0 + 2.0 ** -3)]
                links.append(LinkFault(
                    switch=s.name,
                    drop_prob=0.1 if rng.random() < 0.7 else 0.0,
                    down=down))
        if links:
            faults = FaultSpec(links=links, seed=int(rng.integers(1000)))
    txc = (TxControlConfig(delta_threshold=0.5)
           if rng.random() < 0.4 else None)
    return build_sim_cfg(
        spec,
        clusters_per_ingress=int(rng.integers(1, 3)),
        workers_per_cluster=2,
        gen_interval=float(_INTERVALS[rng.integers(len(_INTERVALS))]),
        gen_jitter=0.0, size_bits=8192, horizon=0.5,
        tx_control=txc, seed=trial, faults=faults)


# a couple of trials stay in the fast lane as a canary; the bulk of the
# 25-trial acceptance sweep runs with the full (tier-1) suite
@pytest.mark.parametrize("trial", range(2))
def test_randomized_dag_equivalence_fast(trial):
    assert_equivalent(_random_dyadic_cfg(trial))


@pytest.mark.slow
@pytest.mark.parametrize("trial", range(2, 26))
def test_randomized_dag_equivalence(trial):
    assert_equivalent(_random_dyadic_cfg(trial))


def _dyadic_fattree_cfg(route_policy="static", faults=None, seed=0):
    spec = fattree_spec(2, edge_gbps=2 ** 19 / 1e9, agg_gbps=2 ** 20 / 1e9,
                        core_gbps=2 ** 21 / 1e9, prop_delay=2.0 ** -12,
                        route_policy=route_policy)
    return build_sim_cfg(spec, gen_interval=3 * 2.0 ** -7, gen_jitter=0.0,
                         size_bits=8192, horizon=0.5, seed=seed,
                         faults=faults)


def test_fattree_dyadic_exact():
    """Fast smoke: dyadic fat-tree k=2 reproduces the heap bitwise."""
    assert_equivalent(_dyadic_fattree_cfg())


@pytest.mark.slow
def test_fattree_dyadic_adaptive_faults_exact():
    cfg0 = _dyadic_fattree_cfg("adaptive")
    faults = FaultSpec(
        links=[LinkFault(switch=s.name, drop_prob=0.05)
               for s in cfg0.switches], seed=11)
    assert_equivalent(_dyadic_fattree_cfg("adaptive", faults=faults))


@pytest.mark.slow
def test_multihop_default_relative():
    """The non-dyadic §8.3 preset: exact modulo f32 gen-time rounding
    (the documented relative-tolerance regime)."""
    assert_equivalent(multihop_cfg("olaf", seed=3), exact_times=False)


def test_dyadic_bitwise_aom():
    """Satellite: with dyadic times every (delivery, gen) pair the scan
    reports is bitwise identical to the heap's, so the host-side AoM
    integral over the scan's deliveries equals the oracle's exactly."""
    cfg = _dyadic_fattree_cfg()
    grid, ref = vecsim.oracle_event_times(cfg)
    res = vecsim.run_vecsim(cfg, grid=grid)
    for c, pairs in ref.deliveries.items():
        got = sorted(res.sim.deliveries.get(c, []))
        assert got == sorted(pairs), c
        assert average_aom(got, cfg.horizon) == average_aom(
            sorted(pairs), cfg.horizon)


def test_uniform_grid_dt_assert():
    """Satellite: a dt coarser than the minimum link service time is an
    error unless the caller opts into the coarse tolerance."""
    cfg = multihop_cfg("olaf", seed=0)
    min_service = min(w.size_bits for w in cfg.workers) / max(
        s.uplink.capacity_bps for s in cfg.switches)
    with pytest.raises(ValueError, match="allow_coarse") as exc:
        vecsim.uniform_grid(cfg, 4 * min_service)
    # the error must name the offending link and its service time so the
    # caller can see *which* switch sets the exact-regime bound
    msg = str(exc.value)
    fastest = max(cfg.switches, key=lambda s: s.uplink.capacity_bps)
    assert f"({fastest.name} ->" in msg
    assert f"{min_service:g}s" in msg
    grid = vecsim.uniform_grid(cfg, 4 * min_service, allow_coarse=True)
    assert grid[-1] >= cfg.horizon
    fine = vecsim.uniform_grid(cfg, min_service / 2)
    assert np.all(np.diff(fine) >= 0)


# ---------------------------------------------------------------------------
# scan_arrays edge cases (satellite)
# ---------------------------------------------------------------------------
def test_scan_arrays_single_switch():
    spec = TopologySpec([SwitchSpec(name="SW", queue_slots=4,
                                    rate_gbps=2 ** 19 / 1e9)])
    arr = spec.scan_arrays()
    assert arr["cand_matrix"].shape == (1, 1)  # Cmax floor of 1
    assert arr["cand_matrix"][0, 0] == -1 and arr["cand_count"][0] == 0
    assert bool(arr["is_egress"][0])
    # and the single-switch scenario actually runs end to end
    cfg = build_sim_cfg(spec, gen_interval=2.0 ** -6, gen_jitter=0.0,
                        horizon=0.25, size_bits=8192)
    assert_equivalent(cfg)


def test_scan_arrays_heterogeneous_slots():
    spec = TopologySpec([
        SwitchSpec(name="A", next_hop="C", queue_slots=2),
        SwitchSpec(name="B", next_hop="C", queue_slots=7),
        SwitchSpec(name="C", queue_slots=3)])
    arr = spec.scan_arrays()
    assert list(arr["queue_slots"]) == [2, 7, 3]
    # the scan pads its shared queue buffer to Qmax but must enforce each
    # switch's own capacity — drops happen at the per-switch bound
    assert arr["queue_slots"].max() == 7


def test_scan_arrays_multips_egress():
    spec = multips_spec(2)
    arr = spec.scan_arrays()
    assert int(arr["is_egress"].sum()) == 2  # one PS egress per group
    assert all(arr["cand_count"][i] == 0
               for i in np.flatnonzero(arr["is_egress"]))


def test_classify_slot_events_matches_event_map():
    """Satellite: the shared Algorithm 1 label map. RESET into a vacant
    slot is an append, RESET into an occupied slot is a replace; AGG and
    DROP map straight through."""
    slots = np.asarray([0, 0, 1, -1])
    events = np.asarray([_EV_RESET, _EV_AGG, _EV_RESET, _EV_DROP])
    labels = classify_slot_events(slots, events, np.asarray([False, True]))
    assert labels == ["append", "agg", "replace", "drop"]
    assert [EVENT_OF_CLASS[l] for l in labels] == [
        _EV_RESET, _EV_AGG, _EV_RESET, _EV_DROP]


# ---------------------------------------------------------------------------
# hybrid third consumer path
# ---------------------------------------------------------------------------
def test_hybrid_vectorized_matches_window():
    """The vectorized consumer path of run_hybrid_multihop delivers the
    same (meta, payload) stream as the windowed replay, in one fused
    dispatch with a single staged upload."""
    from repro.core.hybrid import run_hybrid_multihop

    kw = dict(dim=16, seed=3, horizon=0.1)
    rw, _ = run_hybrid_multihop(sim_impl="window", **kw)
    rv, _ = run_hybrid_multihop(sim_impl="vectorized", **kw)

    def skey(x):
        t, u, _ = x
        return (u.cluster_id, u.worker_id, u.gen_time, u.agg_count,
                u.subsumed, t)

    assert len(rw.delivered) == len(rv.delivered)
    for (tw, uw, pw), (tv, uv, pv) in zip(sorted(rw.delivered, key=skey),
                                          sorted(rv.delivered, key=skey)):
        assert abs(tw - tv) <= 2e-5 * max(1.0, tw)
        assert (uw.cluster_id, uw.worker_id, uw.agg_count, uw.subsumed) \
            == (uv.cluster_id, uv.worker_id, uv.agg_count, uv.subsumed)
        np.testing.assert_allclose(np.asarray(pw), np.asarray(pv),
                                   rtol=1e-5, atol=1e-6)
    assert rw.queue_stats == rv.queue_stats
    assert rw.residual_slot_counts == rv.residual_slot_counts
    assert np.array_equal(np.asarray(rw.final_counts),
                          np.asarray(rv.final_counts))
    assert rv.launches == 1
    assert rv.h2d_transfers < rw.h2d_transfers / 5


def test_run_vecsim_auto_grid():
    """With neither dt nor grid, run_vecsim derives the oracle grid
    itself (convenience path)."""
    cfg = _dyadic_fattree_cfg()
    res = vecsim.run_vecsim(cfg)
    ref = NetworkSimulator(cfg).run()
    assert len(res.sim.delivered_updates) == len(ref.delivered_updates)
    assert res.sim.queue_stats == ref.queue_stats


# ---------------------------------------------------------------------------
# vectorized ring insertion, donation, auto-dt (scale-out satellites)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("trial", range(4))
def test_ring_insert_vec_matches_sequential(trial):
    """The one-shot vectorized first-free insert must land every masked
    row in exactly the slot the sequential scan would pick (no frees
    happen intra-batch, so the two are provably identical)."""
    import jax.numpy as jnp

    rng = np.random.default_rng(50 + trial)
    R, N = 16, 12
    t = np.full(R, np.inf, np.float32)
    occupied = rng.random(R) < 0.5
    t[occupied] = rng.random(occupied.sum()).astype(np.float32)
    ring_a = {"time": jnp.asarray(t),
              "val": jnp.asarray(rng.integers(0, 99, R), jnp.int32),
              "pay": jnp.asarray(rng.normal(size=(R, 5)), jnp.float32)}
    ring_b = {k: v for k, v in ring_a.items()}
    mask = jnp.asarray(rng.random(N) < 0.6)
    rows = {"time": jnp.asarray(rng.random(N), jnp.float32),
            "val": jnp.asarray(rng.integers(100, 199, N), jnp.int32),
            "pay": jnp.asarray(rng.normal(size=(N, 5)), jnp.float32)}
    ovf0 = jnp.asarray(False)
    ra, oa = vecsim._ring_insert(ring_a, ovf0, mask, rows)
    rb, ob, slot = vecsim._ring_insert_vec(ring_b, ovf0, mask, rows)
    for k in ("time", "val", "pay"):  # payload rows too, not just scalars
        np.testing.assert_array_equal(np.asarray(ra[k]), np.asarray(rb[k]))
    assert bool(oa) == bool(ob)
    # returned landing slots point at the inserted rows
    for i in np.nonzero(np.asarray(mask))[0]:
        s = int(np.asarray(slot)[i])
        if s < R:
            assert int(np.asarray(rb["val"])[s]) >= 100


@pytest.mark.parametrize("size,n,k", [
    (16, 0, 3), (16, 5, 8), (16, 12, 8), (16, 15, 8), (16, 20, 8),
    (16, 9, 0), (4, 1, 6), (4, 3, 8)])
def test_append_matches_drop_scatter(size, n, k):
    """``_append`` writes ``rows[:k]`` at ``buf[n:n + k]`` exactly as the
    drop-mode scatter it replaces — rows past the end of the log are
    dropped, including when the source batch is longer than the log."""
    import jax.numpy as jnp

    rng = np.random.default_rng(size * 100 + n * 10 + k)
    N = 8
    buf = jnp.asarray(rng.normal(size=(size, 3)), jnp.float32)
    rows = jnp.asarray(rng.normal(size=(N, 3)), jnp.float32)
    idx = jnp.where(jnp.arange(N) < k, n + jnp.arange(N), size + 1)
    want = buf.at[idx].set(rows, mode="drop")
    got = vecsim._append(buf, jnp.int32(n), rows, jnp.int32(k))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_scan_carry_is_donated():
    """The scan carry is donated into the jitted runner: after the call
    every input carry buffer must be consumed in place (a spurious copy
    would leave it alive and double peak memory)."""
    import warnings
    import jax

    import jax.numpy as jnp

    cfg = _dyadic_fattree_cfg()
    comp = vecsim.compile_scenario(cfg, dim=2)
    runner = vecsim._make_runner(comp.static)
    carry0 = vecsim._init_carry(comp.static)
    grid, _ = vecsim.oracle_event_times(cfg)
    ts = jnp.asarray(np.asarray(grid, np.float32))
    arrs = {k: jnp.asarray(v) for k, v in comp.arrays.items()}
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # any donation fallback warns
        out = runner(carry0, arrs, ts)
    for leaf in jax.tree_util.tree_leaves(carry0):
        assert leaf.is_deleted()
    # and the compiled program reports a real cost model (no silent
    # interpret fallback)
    cost = runner.lower(
        vecsim._init_carry(comp.static), arrs, ts).compile() \
        .cost_analysis()
    flops = cost[0]["flops"] if isinstance(cost, (list, tuple)) else \
        cost["flops"]
    assert np.isfinite(flops) and flops > 0
    del out


@pytest.mark.slow
def test_auto_dt_monotone_and_runs():
    """auto_dt returns a dt no finer than the exact-regime bound, a loose
    tolerance admits a coarser grid than a tight one, and the chosen dt
    actually runs under allow_coarse."""
    cfg = _dyadic_fattree_cfg()
    min_size = min(w.size_bits for w in cfg.workers)
    max_rate = max(s.uplink.capacity_bps for s in cfg.switches)
    lo = min_size / max_rate
    d_tight = vecsim.auto_dt(cfg, tol=1e-3, max_iters=3)
    d_loose = vecsim.auto_dt(cfg, tol=0.5, max_iters=3)
    assert d_loose >= d_tight >= lo
    res = vecsim.run_vecsim(cfg, dt=d_loose, allow_coarse=True)
    assert res.sim.received_at_ps > 0
