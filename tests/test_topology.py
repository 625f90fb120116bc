"""The declarative ``TopologySpec`` data plane.

Covers the compiled-spec surface (next-hop vector, adjacency/reachability,
topological drain order, per-switch flush sets), the netsim wiring builders
(presets as one-liners; ``multihop_cfg``'s SW1/SW2/SW3 wiring now compiles
from the spec), heterogeneous per-switch queue capacities through the
jittable queue and the fused Pallas ``olaf_step`` kernel, and the hybrid
data plane end to end on topologies the hard-coded fan-in could never
express — chains, fat-tree, multi-PS egress and fully randomized DAGs —
with the batched ``feed_window`` consumer proven event-for-event equivalent
to the per-event reference on every sampled spec.
"""
import numpy as np
import pytest

import jax

from repro.core.hybrid import run_hybrid_multihop
from repro.core.netsim import NetworkSimulator, multihop_cfg
from repro.core.olaf_queue import (jax_enqueue_burst, jax_olaf_step,
                                   jax_queue_init)
from repro.core.topology import (SwitchSpec, TopologySpec, build_sim_cfg,
                                 chain_spec, fanin_spec, fattree_cfg,
                                 fattree_spec, multihop_spec, multips_cfg,
                                 multips_spec, multirack_spec,
                                 spec_from_switch_cfgs)
from tests.test_hybrid_window import _assert_results_equal, _payload_source

DIM = 24

# ---------------------------------------------------------------------------
# Spec compilation
# ---------------------------------------------------------------------------
def test_spec_compiles_static_arrays():
    spec = fattree_spec(2)
    assert spec.num_switches == 7
    # next-hop vector: edges -> their pod agg, aggs -> core, core -> PS
    assert int(spec.next_hop[spec.index["EDGE11"]]) == spec.index["AGG1"]
    assert int(spec.next_hop[spec.index["AGG2"]]) == spec.index["CORE"]
    assert int(spec.next_hop[spec.index["CORE"]]) == -1
    # adjacency is the one-hot rows of next_hop
    assert spec.adjacency[spec.index["EDGE21"], spec.index["AGG2"]]
    assert not spec.adjacency[spec.index["EDGE21"], spec.index["AGG1"]]
    # reachability is its transitive closure
    assert spec.reachability[spec.index["EDGE11"], spec.index["CORE"]]
    assert not spec.reachability[spec.index["AGG1"], spec.index["EDGE11"]]
    # topological order visits upstreams before their next hop
    pos = {int(s): i for i, s in enumerate(spec.topo_order)}
    for u in range(spec.num_switches):
        if int(spec.next_hop[u]) >= 0:
            assert pos[u] < pos[int(spec.next_hop[u])]
    # the flush set is the switch plus its upstream frontier
    assert set(spec.flush_set("AGG1")) == {"EDGE11", "EDGE12", "AGG1"}
    assert set(spec.flush_set("EDGE12")) == {"EDGE12"}
    assert spec.source_names == ("EDGE11", "EDGE12", "EDGE21", "EDGE22")


def test_spec_rejects_cycles_and_unknown_hops():
    with pytest.raises(ValueError, match="cycle"):
        TopologySpec([SwitchSpec("A", next_hop="B"),
                      SwitchSpec("B", next_hop="A")])
    with pytest.raises(ValueError, match="unknown next hop"):
        TopologySpec([SwitchSpec("A", next_hop="Z")])


def test_multips_spec_has_multiple_egress():
    spec = multips_spec(groups=2)
    assert len(spec.egress) == 2
    # per-switch slot/rate vectors are data, not wiring
    assert spec.queue_slots.shape == (spec.num_switches,)
    assert (spec.rate_bps > 0).all() and (spec.prop_delay > 0).all()


def test_multihop_cfg_wiring_comes_from_spec():
    """The §8.3 preset and the compiled spec emit identical SwitchCfgs,
    and a SwitchCfg round-trip re-compiles to the same spec arrays."""
    cfg = multihop_cfg("olaf", x1_gbps=3.0, sw12_slots=4, sw3_slots=6,
                       reward_threshold=0.5)
    spec = multihop_spec(x1_gbps=3.0, sw12_slots=4, sw3_slots=6,
                         reward_threshold=0.5)
    assert spec.switch_cfgs(queue="olaf") == cfg.switches
    back = spec_from_switch_cfgs(cfg.switches)
    np.testing.assert_array_equal(back.next_hop, spec.next_hop)
    np.testing.assert_array_equal(back.queue_slots, spec.queue_slots)


def test_build_sim_cfg_spreads_clusters_over_sources():
    spec = fanin_spec(3)
    cfg = build_sim_cfg(spec, clusters_per_ingress=2, workers_per_cluster=3)
    assert len(cfg.workers) == 3 * 2 * 3
    by_cluster = {}
    for w in cfg.workers:
        by_cluster.setdefault(w.cluster_id, set()).add(w.ingress_switch)
    # each cluster is co-located behind one source switch
    assert all(len(s) == 1 for s in by_cluster.values())
    assert {s for ss in by_cluster.values() for s in ss} == \
        set(spec.source_names)


# ---------------------------------------------------------------------------
# Heterogeneous per-switch capacity (padded (S, Qmax) buffers)
# ---------------------------------------------------------------------------
def _burst(rng, U, D, n_clusters=6):
    import jax.numpy as jnp
    return (jnp.asarray(rng.integers(0, n_clusters, U), jnp.int32),
            jnp.asarray(rng.integers(0, 4, U), jnp.int32),
            jnp.asarray(rng.random(U), jnp.float32),
            jnp.asarray(rng.normal(size=U), jnp.float32),
            jnp.asarray(rng.normal(size=(U, D)), jnp.float32))


def test_capacity_caps_logical_slots():
    """A (Q=8, capacity=5) queue must make exactly the decisions of a
    Q=5 queue: same occupancy, seqs, counters, payloads; slots >= 5 never
    host an append."""
    rng = np.random.default_rng(0)
    for trial in range(10):
        b = _burst(rng, 12, 16)
        big = jax_enqueue_burst(jax_queue_init(8, 16), *b, capacity=5)
        small = jax_enqueue_burst(jax_queue_init(5, 16), *b)
        np.testing.assert_array_equal(np.asarray(big.cluster[:5]),
                                      np.asarray(small.cluster))
        np.testing.assert_array_equal(np.asarray(big.cluster[5:]), -1)
        np.testing.assert_array_equal(np.asarray(big.seq[:5]),
                                      np.asarray(small.seq))
        for f in ("n_dropped", "n_agg", "n_repl", "next_seq"):
            assert int(getattr(big, f)) == int(getattr(small, f)), f
        np.testing.assert_allclose(np.asarray(big.payload[:5]),
                                   np.asarray(small.payload), rtol=1e-6)


def test_olaf_step_kernel_capacity_matches_oracle():
    """The fused Pallas cycle honors the logical capacity exactly like the
    XLA oracle (drop-when-logically-full, append below the cap only)."""
    from repro.kernels import ops
    rng = np.random.default_rng(1)
    for cap in (3, 5, 8):
        b = _burst(rng, 10, 32)
        st_p, out_p = ops.olaf_step(jax_queue_init(8, 32), *b, k=3,
                                    capacity=cap, impl="pallas")
        st_x, out_x = ops.olaf_step(jax_queue_init(8, 32), *b, k=3,
                                    capacity=cap, impl="xla")
        for f in ("cluster", "seq", "agg_count", "n_dropped"):
            np.testing.assert_array_equal(np.asarray(getattr(st_p, f)),
                                          np.asarray(getattr(st_x, f)), f)
        np.testing.assert_array_equal(np.asarray(out_p["valid"]),
                                      np.asarray(out_x["valid"]))
        np.testing.assert_allclose(np.asarray(out_p["payload"]),
                                   np.asarray(out_x["payload"]),
                                   rtol=1e-5, atol=1e-6)


def test_olaf_step_multi_heterogeneous_capacities():
    """One padded (S, Qmax) multi-queue launch with a per-switch capacity
    vector equals per-switch single-queue cycles at their exact sizes."""
    import jax.numpy as jnp
    from repro.kernels import ops
    rng = np.random.default_rng(2)
    caps = [3, 5, 8]
    S, Q, U, D = len(caps), max(caps), 9, 16
    bursts = [_burst(rng, U, D) for _ in range(S)]
    states = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *[jax_queue_init(Q, D) for _ in range(S)])
    stacked = tuple(jnp.stack([b[i] for b in bursts]) for i in range(5))
    st_m, out_m = ops.olaf_step_multi(
        states, *stacked, capacity=jnp.asarray(caps, jnp.int32), k=3)
    for s, cap in enumerate(caps):
        st_1, out_1 = jax_olaf_step(jax_queue_init(cap, D), *bursts[s], 3)
        np.testing.assert_array_equal(np.asarray(st_m.cluster[s][:cap]),
                                      np.asarray(st_1.cluster))
        np.testing.assert_array_equal(np.asarray(st_m.cluster[s][cap:]), -1)
        np.testing.assert_array_equal(np.asarray(out_m["valid"][s][:3]),
                                      np.asarray(out_1["valid"][:3]))
        np.testing.assert_allclose(np.asarray(out_m["payload"][s][:3]),
                                   np.asarray(out_1["payload"][:3]),
                                   rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# Hybrid data plane over spec-only topologies
# ---------------------------------------------------------------------------
def test_fattree_hybrid_smoke():
    """Fat-lane smoke (runs in the CI ``-m "not slow"`` job): a fat-tree
    k=2 — a topology the hard-coded SW1/SW2/SW3 path could never express —
    runs end-to-end through ``feed_window`` with device-resident
    forwarding (transit hops counted, zero host-side forward matching)."""
    hyb, cfg = run_hybrid_multihop(
        DIM, topology=fattree_cfg(2, horizon=0.25, gen_interval=0.01,
                                  clusters_per_ingress=1,
                                  workers_per_cluster=2, seed=5),
        batched=True)
    assert len(hyb.delivered) > 0
    assert hyb.forwarded > 0  # edge->agg->core transit actually happened
    assert hyb.forward_launches >= hyb.forwarded
    assert len(cfg.switches) == 7
    # fused forwarding: combine launches never exceed departures + final
    assert hyb.launches <= hyb.forward_launches + 1


def test_multips_hybrid_delivers_at_every_egress():
    """Multi-PS egress: both sub-trees drain to their own PS through the
    same (S, Q, D) buffer, on both replay paths, identically."""
    cfg = multips_cfg(2, horizon=0.3, gen_interval=0.012, seed=9)
    src = _payload_source(123, DIM)
    per_event, _ = run_hybrid_multihop(DIM, sim_cfg=cfg, batched=False,
                                       payload_source=src)
    batched, _ = run_hybrid_multihop(DIM, sim_cfg=cfg, batched=True,
                                     payload_source=_payload_source(123, DIM))
    _assert_results_equal(per_event, batched)
    sim = NetworkSimulator(cfg).run()
    egress_with_traffic = {n for n, st in sim.queue_stats.items()
                           if st["departed"] > 0 and n.endswith("E")}
    assert egress_with_traffic == {"G1E", "G2E"}
    assert len(batched.delivered) > 0


def _congested_chain_spec(n=6):
    """Decreasing downstream rates so every hop of the chain queues."""
    return TopologySpec([
        SwitchSpec(f"SW{i + 1}",
                   next_hop=None if i == n - 1 else f"SW{i + 2}",
                   queue_slots=4, rate_gbps=0.9e-3 * 0.85 ** i)
        for i in range(n)
    ])


def test_chain_flush_cadence_cuts_launches():
    """Satellite metric: on a 6-switch chain the per-switch flush cadence
    (departing switch + upstream frontier) must land strictly fewer
    per-switch combine windows than the every-switch flush, while
    delivering the same packets."""
    spec = _congested_chain_spec(6)
    kw = dict(clusters_per_ingress=3, workers_per_cluster=3,
              gen_interval=0.008, horizon=0.3, seed=11)
    cad, _ = run_hybrid_multihop(DIM, topology=spec, flush_cadence=True,
                                 **kw)
    full, _ = run_hybrid_multihop(DIM, topology=spec, flush_cadence=False,
                                  **kw)
    assert len(cad.delivered) == len(full.delivered) > 0
    for (t0, u0, p0), (t1, u1, p1) in zip(cad.delivered, full.delivered):
        assert t0 == t1 and u0.cluster_id == u1.cluster_id \
            and u0.agg_count == u1.agg_count
        np.testing.assert_allclose(np.asarray(p0), np.asarray(p1),
                                   rtol=1e-4, atol=1e-5)
    assert cad.queue_stats == full.queue_stats
    # the cadence evidence: fewer per-switch window landings AND fewer
    # combine launches overall
    assert sum(cad.switch_launches.values()) \
        < sum(full.switch_launches.values())
    assert cad.launches < full.launches
    # deep-chain tail switches benefit most: SW1 only ever lands at its
    # own/SW2's boundaries under the cadence
    assert cad.switch_launches["SW1"] < full.switch_launches["SW1"]


# ---------------------------------------------------------------------------
# Randomized DAG equivalence (the acceptance property)
# ---------------------------------------------------------------------------
def _random_dag_spec(rng):
    """Random fan-in forest: 3-8 switches, every non-root pointing at a
    higher-indexed switch (acyclic by construction), 1 or 2 PS egress
    roots, heterogeneous slots/rates/propagation delays and per-switch
    reward thresholds."""
    S = int(rng.integers(3, 9))
    n_roots = 2 if (S >= 4 and rng.random() < 0.35) else 1
    names = [f"N{i}" for i in range(S)]
    switches = []
    for i in range(S):
        nh = None if i >= S - n_roots else names[int(rng.integers(i + 1, S))]
        switches.append(SwitchSpec(
            names[i], next_hop=nh,
            queue_slots=int(rng.integers(3, 7)),
            rate_gbps=float(rng.uniform(0.3e-3, 1.0e-3)),
            prop_delay=float(rng.uniform(0.5e-6, 5e-6)),
            reward_threshold=[None, 0.3, 1.0][int(rng.integers(3))]))
    return TopologySpec(switches)


@pytest.mark.slow
def test_random_dag_windowed_equivalence():
    """Property: >= 25 randomized DAG topologies (random fan-in, multi-PS
    cases, heterogeneous slots and link delays) replayed through the
    per-event reference and the batched zero-matching ``feed_window`` must
    produce identical ``HybridResult``s — delivered payloads bitwise."""
    rng = np.random.default_rng(2026)
    n_nonempty = n_multips = n_transit = 0
    for trial in range(26):
        spec = _random_dag_spec(rng)
        cfg = build_sim_cfg(
            spec,
            clusters_per_ingress=int(rng.integers(1, 3)),
            workers_per_cluster=int(rng.integers(1, 4)),
            gen_interval=float(rng.uniform(0.008, 0.03)),
            horizon=float(rng.uniform(0.08, 0.16)),
            seed=int(rng.integers(0, 100000)))
        src_seed = int(rng.integers(0, 100000))
        per_event, _ = run_hybrid_multihop(
            DIM, sim_cfg=cfg, batched=False,
            payload_source=_payload_source(src_seed, DIM))
        batched, _ = run_hybrid_multihop(
            DIM, sim_cfg=cfg, batched=True,
            payload_source=_payload_source(src_seed, DIM))
        _assert_results_equal(per_event, batched)
        assert batched.h2d_transfers <= per_event.h2d_transfers, trial
        n_nonempty += bool(batched.delivered)
        n_multips += len(spec.egress) > 1
        n_transit += batched.forwarded > 0
    # the sample actually covered the interesting regimes
    assert n_nonempty >= 20
    assert n_multips >= 2
    assert n_transit >= 15


# ---------------------------------------------------------------------------
# Trainer integration
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_async_trainer_runs_on_spec_topology():
    """``AsyncDRLTrainer(topology=...)`` spreads clusters over the spec's
    sources and trains end to end over the multi-hop fabric."""
    from repro.configs.olaf_ppo import PPOConfig
    from repro.rl.async_trainer import AsyncDRLTrainer, AsyncTrainConfig

    cfg = AsyncTrainConfig(
        n_clusters=2, workers_per_cluster=1, n_updates_per_worker=4,
        topology=fanin_spec(2, leaf_gbps=2e-5, core_gbps=3e-5),
        ppo=PPOConfig(rollout_len=8, hidden=8), n_envs=2, seed=3)
    res = AsyncDRLTrainer(cfg).run()
    assert res.sim_result.received_at_ps > 0
    assert set(res.sim_result.queue_stats) == {"LEAF1", "LEAF2", "CORE"}
    # traffic flowed through the transit hop, not just the ingress queues
    assert res.sim_result.queue_stats["CORE"]["departed"] > 0
    assert np.all(np.isfinite(np.asarray(res.ps.w)))
