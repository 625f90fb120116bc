"""Payload-integrity hardening: corruption injection, ingress screening,
robust combining, and the chaos invariant harness.

Four legs:

  * **corruption fault model** — ``CorruptionFault`` markers ride the
    trace as data: both hybrid consumers replay the identical byte damage
    (``apply_corruption``) and a zero-probability spec is byte-identical
    to no spec at all (the dedicated fault-RNG contract).
  * **ingress screening** — detectable corruption is withheld at the
    worker's ingress switch and recovered by ACK-timeout retransmission
    from the worker's clean cache (NACK by silence); the device twin
    (``jax_screen_mask`` + the screen-gated queue ops) agrees across the
    XLA and Pallas-interpret paths.
  * **robust aggregation** — the winsorized trimmed combine (numpy oracle
    vs jax twin) plus the NaN-safety satellites (``int8_quantize``,
    ``grad_clip``).
  * **chaos campaign** — randomized mixed link/node/corruption specs
    replayed bitwise-identically through both hybrid consumers with PS
    payloads finite whenever screening is on (``CHAOS_SEED`` rotates the
    campaign in the nightly lane).
"""
import dataclasses
import os

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.aggregation import (Update, aggregate, jax_trimmed_combine,
                                    replace, trimmed_combine)
from repro.core.hybrid import run_hybrid_multihop
from repro.core.netsim import (CORRUPTION_MODES, CorruptionFault, FaultSpec,
                               LinkFault, NetworkSimulator, SwitchStall,
                               apply_corruption, corruption_detectable)
from repro.core.olaf_queue import (jax_enqueue_burst, jax_queue_init,
                                   jax_screen_mask)
from repro.core.topology import (SwitchSpec, TopologySpec, build_sim_cfg,
                                 fattree_spec)
from repro.core.txctl import TxControlConfig
from repro.kernels import ops

DIM = 16


def _assert_results_equal(a, b):
    """Bitwise per-event vs windowed equivalence, extended with the
    payload-integrity counters."""
    assert len(a.delivered) == len(b.delivered)
    for (t0, u0, p0), (t1, u1, p1) in zip(a.delivered, b.delivered):
        assert t0 == t1
        assert (u0.cluster_id, u0.worker_id, u0.gen_time, u0.reward,
                u0.agg_count, u0.seq, u0.corrupt) == \
               (u1.cluster_id, u1.worker_id, u1.gen_time, u1.reward,
                u1.agg_count, u1.seq, u1.corrupt)
        np.testing.assert_array_equal(np.asarray(p0), np.asarray(p1))
    assert a.queue_stats == b.queue_stats
    np.testing.assert_array_equal(a.final_counts, b.final_counts)
    assert a.link_dropped == b.link_dropped
    assert a.drops_by_switch == b.drops_by_switch
    assert a.corrupted == b.corrupted
    assert a.screened == b.screened
    assert a.tainted_delivered == b.tainted_delivered


def _payload_source(seed, dim):
    r = np.random.default_rng(seed)

    def src(now, worker_id):
        return r.normal(size=dim).astype(np.float32), float(r.normal())

    return src


# ---------------------------------------------------------------------------
# The corruption primitive
# ---------------------------------------------------------------------------
def test_apply_corruption_modes():
    row = np.linspace(-1.0, 1.0, 32, dtype=np.float32)
    nan_out = apply_corruption(row, ("nan", 7, 0.0))
    assert np.isnan(nan_out).sum() == 1 and np.isnan(nan_out[7 % 32])
    inf_out = apply_corruption(row, ("inf", 3, 0.0))
    assert np.isinf(inf_out).sum() == 1
    sc = apply_corruption(row, ("scale", 0, 1e4))
    np.testing.assert_allclose(sc, row * np.float32(1e4))
    bf = apply_corruption(row, ("bitflip", 5, 0.0))
    assert (bf != row).sum() == 1  # exactly one element damaged
    # the bit flip is an XOR: applying the same marker twice round-trips
    np.testing.assert_array_equal(
        apply_corruption(bf, ("bitflip", 5, 0.0)), row)
    # determinism: the marker fully determines the damage
    np.testing.assert_array_equal(
        apply_corruption(row, ("nan", 7, 0.0)), nan_out)
    # the input row is never mutated in place
    np.testing.assert_array_equal(row, np.linspace(-1.0, 1.0, 32,
                                                   dtype=np.float32))
    with pytest.raises(ValueError, match="unknown corruption mode"):
        apply_corruption(row, ("gamma-ray", 0, 0.0))


def test_corruption_detectability_model():
    # checksum/isfinite-class damage is always detectable
    for mode in ("bitflip", "nan", "inf"):
        assert corruption_detectable((mode, 0, 0.0), 16.0)
    # norm-scaling only when the factor clears the screen threshold
    assert corruption_detectable(("scale", 0, 1e4), 16.0)
    assert corruption_detectable(("scale", 0, -32.0), 16.0)
    assert not corruption_detectable(("scale", 0, 2.0), 16.0)


def test_taint_merge_rules():
    w = Update(cluster_id=0, worker_id=0, gen_time=0.1, reward=0.0,
               payload=np.ones(4, np.float32), corrupt=("nan", 1, 0.0))
    i = Update(cluster_id=0, worker_id=1, gen_time=0.2, reward=0.0,
               payload=np.ones(4, np.float32))
    # aggregation with a tainted side taints the merge
    assert aggregate(w, i).corrupt == ("nan", 1, 0.0)
    assert aggregate(i.clone(), dataclasses.replace(
        w, corrupt=("inf", 2, 0.0))).corrupt == ("inf", 2, 0.0)
    # a clean replacement heals the slot (waiting bytes are discarded)
    assert replace(w, i).corrupt is None
    assert replace(i, dataclasses.replace(
        w, corrupt=("scale", 3, 8.0))).corrupt == ("scale", 3, 8.0)


def test_zero_probability_corruption_is_byte_identical():
    """An armed-but-zero-probability CorruptionFault must not perturb the
    run: the fault RNG is consulted only for prob > 0 faults."""
    spec = fattree_spec(2)
    base = build_sim_cfg(spec, horizon=0.2, seed=3)
    faulty = dataclasses.replace(base, faults=FaultSpec(
        corruption=[CorruptionFault(prob=0.0, mode="nan"),
                    CorruptionFault(worker=1, prob=0.0, mode="scale")],
        seed=9))
    ra, rb = NetworkSimulator(base).run(), NetworkSimulator(faulty).run()
    assert ra.deliveries == rb.deliveries
    assert ra.queue_stats == rb.queue_stats
    assert rb.corrupted == rb.screened == rb.tainted_delivered == 0


# ---------------------------------------------------------------------------
# Trace replay + ingress screening (fast lane)
# ---------------------------------------------------------------------------
def _corruption_faults():
    return FaultSpec(links=[LinkFault(switch="AGG1", drop_prob=0.2)],
                     corruption=[
                         CorruptionFault(worker=0, prob=0.4, mode="nan"),
                         CorruptionFault(switch="EDGE12", prob=0.3,
                                         mode="scale", factor=1e3),
                         CorruptionFault(prob=0.1, mode="bitflip"),
                         # seed chosen so the screened/dropped sends are all
                         # covered by in-budget retransmissions (the
                         # per-link loss streams are keyed by
                         # link_stream_index, so this is stable)
                     ], seed=14)


def test_corruption_trace_hybrid_smoke():
    """Fast-lane smoke: corruption markers ride the trace and both hybrid
    consumers replay the identical byte damage (screening off — tainted
    payloads reach the PS and the taint counters agree with the sim)."""
    spec = fattree_spec(2, spines=2, route_policy="hash")
    cfg = build_sim_cfg(
        spec, clusters_per_ingress=1, workers_per_cluster=2,
        gen_interval=0.015, horizon=0.2, faults=_corruption_faults(),
        seed=7, tx_control=TxControlConfig(ack_timeout=0.004, max_retries=2))
    per_event, _ = run_hybrid_multihop(DIM, sim_cfg=cfg, batched=False)
    batched, _ = run_hybrid_multihop(DIM, sim_cfg=cfg, batched=True)
    _assert_results_equal(per_event, batched)
    sim = NetworkSimulator(cfg).run()
    assert batched.corrupted == sim.corrupted > 0
    assert batched.tainted_delivered == sim.tainted_delivered > 0
    assert sim.screened == 0  # screening off
    # the NaN corruption really reached a delivered payload
    tainted = [p for _, u, p in batched.delivered if u.corrupt is not None]
    assert tainted and any(not np.isfinite(np.asarray(p)).all()
                           or u.corrupt[0] == "scale"
                           for (_, u, p) in batched.delivered
                           if u.corrupt is not None)


def test_ingress_screen_blocks_tainted_delivery():
    """With screening on, detectable corruption never reaches the PS: it
    is withheld at the ingress switch, NACK'd by silence, and recovered by
    retransmission from the worker's clean cache — every delivered payload
    is finite and nothing is lost for good."""
    spec = fattree_spec(2, spines=2, route_policy="hash")
    cfg = build_sim_cfg(
        spec, clusters_per_ingress=1, workers_per_cluster=2,
        gen_interval=0.02, horizon=0.4, n_updates=10,
        faults=_corruption_faults(), seed=7,
        tx_control=TxControlConfig(ack_timeout=0.02, max_retries=6))
    cfg = dataclasses.replace(cfg, ingress_screen=True)
    per_event, _ = run_hybrid_multihop(DIM, sim_cfg=cfg, batched=False)
    batched, _ = run_hybrid_multihop(DIM, sim_cfg=cfg, batched=True)
    _assert_results_equal(per_event, batched)
    sim = NetworkSimulator(cfg).run()
    assert sim.corrupted > 0
    assert batched.screened == sim.screened > 0
    assert batched.tainted_delivered == sim.tainted_delivered == 0
    assert sim.unrecovered_drops == 0  # retransmission recovered them all
    assert sim.delivery_rate <= 1.0
    for _, u, p in batched.delivered:
        assert u.corrupt is None
        assert np.isfinite(np.asarray(p)).all()


# ---------------------------------------------------------------------------
# Device twin: jax_screen_mask + the screen-gated queue ops
# ---------------------------------------------------------------------------
def test_jax_screen_mask_rejects_nonfinite_and_outliers():
    rows = np.ones((6, 8), np.float32)
    rows[2, 3] = np.nan  # non-finite -> always screened
    rows[4] *= 1e4  # norm outlier vs the running estimate
    screen, med = jax_screen_mask(jnp.asarray(rows), jnp.float32(0.0),
                                  factor=16.0)
    assert list(np.asarray(screen)) == [False, False, True, False, True,
                                        False]
    assert float(med) > 0.0
    # masked-out rows neither screen nor move the estimate
    rows2 = np.zeros((3, 8), np.float32)
    rows2[1] = np.nan
    screen2, med2 = jax_screen_mask(
        jnp.asarray(rows2), jnp.float32(1.0), factor=16.0,
        mask=jnp.asarray([False, False, False]))
    assert not np.asarray(screen2).any()
    assert float(med2) == 1.0


def test_screen_gate_threads_through_queue_ops():
    """The ingress screen gate behaves identically across the sequential
    oracle, the fused XLA composition, and the Pallas-interpret kernel —
    including the ``n_screened`` counter."""
    rng = np.random.default_rng(0)
    Q, D, U, k = 8, 128, 6, 3

    def burst():
        return (jnp.asarray(rng.integers(0, 4, U), jnp.int32),
                jnp.asarray(rng.integers(0, 8, U), jnp.int32),
                jnp.asarray(rng.random(U), jnp.float32),
                jnp.asarray(rng.normal(size=U), jnp.float32),
                jnp.asarray(rng.normal(size=(U, D)), jnp.float32))

    st_o, st_p = jax_queue_init(Q, D), jax_queue_init(Q, D)
    for _ in range(4):
        c, w, t, r, p = burst()
        scr = jnp.asarray(rng.random(U) < 0.4)
        st_o = jax_enqueue_burst(st_o, c, w, t, r, p, 0.5, screen=scr)
        st_p = ops.olaf_enqueue(st_p, c, w, t, r, p, 0.5, None, scr)
    for f in ("cluster", "worker", "seq", "agg_count", "next_seq",
              "n_dropped", "n_agg", "n_repl", "n_screened"):
        np.testing.assert_array_equal(np.asarray(getattr(st_o, f)),
                                      np.asarray(getattr(st_p, f)), f)
    np.testing.assert_allclose(np.asarray(st_o.payload),
                               np.asarray(st_p.payload), atol=1e-5)
    assert int(st_o.n_screened) > 0

    st_x, st_p = jax_queue_init(Q, D), jax_queue_init(Q, D)
    for _ in range(4):
        c, w, t, r, p = burst()
        snd = jnp.asarray(rng.random(U) < 0.8)
        scr = jnp.asarray(rng.random(U) < 0.3)
        st_x, out_x = ops.olaf_step(st_x, c, w, t, r, p, 0.5, snd, None,
                                    None, scr, k=k, impl="xla")
        st_p, out_p = ops.olaf_step(st_p, c, w, t, r, p, 0.5, snd, None,
                                    None, scr, k=k, impl="pallas")
        for key in out_x:
            np.testing.assert_allclose(np.asarray(out_x[key]),
                                       np.asarray(out_p[key]), atol=1e-5,
                                       err_msg=key)
    np.testing.assert_array_equal(np.asarray(st_x.n_screened),
                                  np.asarray(st_p.n_screened))
    assert int(st_x.n_screened) > 0


def test_screened_state_is_backward_compatible_pytree():
    """Pre-hardening ``JaxQueueState`` constructions (no ``n_screened``)
    must stay valid pytrees with a zero counter."""
    st = jax_queue_init(4, 8)
    assert int(st.n_screened) == 0
    st2 = jax_enqueue_burst(st, jnp.asarray([0], jnp.int32),
                            jnp.asarray([0], jnp.int32),
                            jnp.asarray([0.1], jnp.float32),
                            jnp.asarray([0.0], jnp.float32),
                            jnp.ones((1, 8), jnp.float32))
    assert int(st2.n_screened) == 0  # no screen arg -> nothing screened


# ---------------------------------------------------------------------------
# Robust combining + NaN-safety satellites
# ---------------------------------------------------------------------------
def test_trimmed_combine_numpy_vs_jax():
    rng = np.random.default_rng(5)
    rows = rng.normal(size=(8, 24)).astype(np.float32)
    rows[3] *= 1e6  # exploding row
    rows[5, 2] = np.nan  # non-finite coordinate
    weights = rng.integers(0, 3, 8).astype(np.float32)
    ref = trimmed_combine(rows, weights)
    out = np.asarray(jax_trimmed_combine(jnp.asarray(rows),
                                         jnp.asarray(weights)))
    np.testing.assert_allclose(out, ref, atol=1e-4)
    assert np.isfinite(out).all()
    # the winsorized mean is bounded by the clean rows' scale, not the
    # exploding row's
    assert np.abs(out).max() < 1e3
    # no valid rows -> all-zero (a skipped PS step)
    zero = np.asarray(jax_trimmed_combine(jnp.asarray(rows),
                                          jnp.zeros(8, jnp.float32)))
    np.testing.assert_array_equal(zero, np.zeros(24, np.float32))


def test_int8_quantize_nonfinite_and_degenerate():
    from repro.optim.compress import int8_dequantize, int8_quantize
    # all-zero gradient: defined output, finite scale
    q, scale = int8_quantize(jnp.zeros(16))
    assert np.isfinite(float(scale))
    np.testing.assert_array_equal(np.asarray(q), np.zeros(16, np.int8))
    # non-finite coordinates: quantization defined, dequantized row finite
    g = jnp.asarray([1.0, -2.0, jnp.nan, jnp.inf, -jnp.inf, 0.5])
    q, scale = int8_quantize(g)
    deq = np.asarray(int8_dequantize(q, scale))
    assert np.isfinite(deq).all()
    assert int(np.asarray(q)[2]) == 0  # NaN -> 0
    assert int(np.asarray(q)[3]) == 127 and int(np.asarray(q)[4]) == -127
    # the finite coordinates still round-trip on the finite scale
    np.testing.assert_allclose(deq[[0, 1, 5]], [1.0, -2.0, 0.5], atol=0.02)
    # clean path unchanged: extreme but finite values round-trip
    g2 = jnp.asarray(np.random.default_rng(1).normal(size=64) * 1e3,
                     jnp.float32)
    q2, s2 = int8_quantize(g2)
    np.testing.assert_allclose(np.asarray(int8_dequantize(q2, s2)),
                               np.asarray(g2), atol=float(s2) * 0.51)


def test_grad_clip_nonfinite_skips_update():
    from repro.optim.optimizers import (OptConfig, apply_updates,
                                        init_opt_state)
    cfg = OptConfig(lr=0.1, grad_clip=1.0)
    params = {"w": jnp.ones(4), "b": jnp.zeros(2)}
    state = init_opt_state(params, cfg)
    bad = {"w": jnp.full(4, jnp.nan), "b": jnp.ones(2)}
    new_params, new_state = apply_updates(params, bad, state, cfg)
    for k in params:  # the step is skipped, params never NaN-wiped
        np.testing.assert_array_equal(np.asarray(new_params[k]),
                                      np.asarray(params[k]))
        assert np.isfinite(np.asarray(new_params[k])).all()
    # a finite gradient afterwards still applies normally
    good = {"w": jnp.ones(4), "b": jnp.ones(2)}
    after, _ = apply_updates(new_params, good, new_state, cfg)
    assert not np.array_equal(np.asarray(after["w"]),
                              np.asarray(params["w"]))
    assert np.isfinite(np.asarray(after["w"])).all()


# ---------------------------------------------------------------------------
# Chaos campaign: randomized mixed-fault invariants
# ---------------------------------------------------------------------------
def _random_multipath_spec(rng):
    S = int(rng.integers(4, 9))
    n_roots = 2 if (S >= 5 and rng.random() < 0.3) else 1
    names = [f"N{i}" for i in range(S)]
    switches = []
    for i in range(S):
        if i >= S - n_roots:
            nhs = None
        else:
            pool = names[i + 1:]
            k = min(len(pool), int(rng.integers(1, 4)))
            nhs = tuple(rng.choice(pool, size=k, replace=False))
        switches.append(SwitchSpec(
            names[i], next_hop=None if nhs is None else nhs[0],
            next_hops=nhs if nhs is not None and len(nhs) > 1 else None,
            queue_slots=int(rng.integers(3, 7)),
            rate_gbps=float(rng.uniform(0.3e-3, 1.0e-3)),
            prop_delay=float(rng.uniform(0.5e-6, 5e-6)),
            reward_threshold=[None, 0.3][int(rng.integers(2))]))
    policy = ["static", "hash", "adaptive"][int(rng.integers(3))]
    return TopologySpec(switches, route_policy=policy)


def _random_mixed_faults(rng, spec, horizon):
    """Random links + stalls + corruption: the mixed-fault chaos spec."""
    links = []
    for name in spec.names:
        if rng.random() < 0.4:
            links.append(LinkFault(switch=name,
                                   drop_prob=float(rng.uniform(0.0, 0.4))))
    stalls = []
    if rng.random() < 0.3:
        s0 = float(rng.uniform(0.1, 0.5)) * horizon
        stalls.append(SwitchStall(
            switch=spec.names[int(rng.integers(len(spec.names)))],
            from_t=s0, until_t=s0 + 0.2 * horizon))
    corruption = []
    for _ in range(int(rng.integers(1, 4))):
        mode = CORRUPTION_MODES[int(rng.integers(len(CORRUPTION_MODES)))]
        # scale draws an undetectable (2x) or detectable (1e3) factor
        factor = [2.0, 1e3][int(rng.integers(2))]
        corruption.append(CorruptionFault(
            worker=None if rng.random() < 0.5 else int(rng.integers(0, 4)),
            switch=None if rng.random() < 0.7
            else spec.names[int(rng.integers(len(spec.names)))],
            prob=float(rng.uniform(0.05, 0.5)), mode=mode, factor=factor))
    return FaultSpec(links=links, stalls=stalls, corruption=corruption,
                     seed=int(rng.integers(0, 1000)))


def _chaos_trial(rng):
    """One randomized mixed-fault spec through both hybrid consumers and
    the metadata sim; asserts every invariant. Returns coverage bits."""
    spec = _random_multipath_spec(rng)
    horizon = float(rng.uniform(0.08, 0.16))
    screen = bool(rng.random() < 0.5)
    cfg = build_sim_cfg(
        spec,
        clusters_per_ingress=int(rng.integers(1, 3)),
        workers_per_cluster=int(rng.integers(1, 4)),
        gen_interval=float(rng.uniform(0.008, 0.03)),
        horizon=horizon,
        faults=_random_mixed_faults(rng, spec, horizon),
        seed=int(rng.integers(0, 100000)))
    if rng.random() < 0.5:
        cfg = dataclasses.replace(cfg, tx_control=TxControlConfig(
            ack_timeout=float(rng.uniform(0.004, 0.02)), max_retries=3))
    cfg = dataclasses.replace(cfg, ingress_screen=screen)
    src_seed = int(rng.integers(0, 100000))
    per_event, _ = run_hybrid_multihop(
        DIM, sim_cfg=cfg, batched=False,
        payload_source=_payload_source(src_seed, DIM))
    batched, _ = run_hybrid_multihop(
        DIM, sim_cfg=cfg, batched=True,
        payload_source=_payload_source(src_seed, DIM))
    # invariant 1: bitwise per-event vs windowed equivalence
    _assert_results_equal(per_event, batched)
    # the metadata sim must see the SAME reward stream as the trace runs —
    # rewards feed Algorithm 1's replace/drop gate, so a reward-less run
    # would merge (and taint) differently on reward-thresholded switches
    meta_src = _payload_source(src_seed, DIM)
    sim = NetworkSimulator(dataclasses.replace(
        cfg, payload_fn=lambda now, wid: (None, meta_src(now, wid)[1]))).run()
    # invariant 2: both consumers agree with the metadata sim's counters
    assert batched.corrupted == sim.corrupted
    assert batched.screened == sim.screened
    assert batched.tainted_delivered == sim.tainted_delivered
    assert batched.link_dropped == sim.link_dropped
    assert len(batched.delivered) == sim.received_at_ps
    # invariant 3: delivery accounting never exceeds unity and the loss
    # decomposition stays exact under mixed fault types
    assert sim.delivery_rate <= 1.0
    assert abs(sim.loss_pct - sim.link_loss_pct - sim.absorbed_pct) < 1e-9
    # invariant 4: with screening on, no detectable corruption survives to
    # the PS — every delivered payload is finite
    if screen:
        for _, u, p in batched.delivered:
            if u.corrupt is not None:
                assert not corruption_detectable(
                    u.corrupt, cfg.screen_factor)
            assert np.isfinite(np.asarray(p)).all()
    return dict(corrupted=sim.corrupted > 0,
                screened=sim.screened > 0,
                tainted=sim.tainted_delivered > 0,
                delivered=bool(batched.delivered))


def test_chaos_smoke_fixed_seed():
    """Fast-lane chaos smoke: three fixed-seed mixed-fault trials."""
    rng = np.random.default_rng(2718)
    cover = [_chaos_trial(rng) for _ in range(3)]
    assert any(c["corrupted"] for c in cover)
    assert any(c["delivered"] for c in cover)


@pytest.mark.slow
def test_chaos_campaign_randomized():
    """The chaos invariant harness: >= 10 randomized mixed-fault specs
    (link loss, outage-free lossy DAGs, stalls, corruption in all four
    modes, screening on ~half) replayed bitwise-identically with zero
    invariant violations. ``CHAOS_SEED`` rotates the campaign."""
    seed = int(os.environ.get("CHAOS_SEED", "424242")) % (2 ** 31)
    rng = np.random.default_rng(seed)
    cover = [_chaos_trial(rng) for _ in range(12)]
    n = len(cover)
    # the sample really exercised the integrity machinery
    assert sum(c["corrupted"] for c in cover) >= n // 2
    assert sum(c["delivered"] for c in cover) >= n // 2
    assert any(c["screened"] for c in cover)
    # tainted-delivery coverage rides a pinned trial: a marker only
    # survives to the PS when no later clean write erases it, so a rotated
    # campaign can legitimately sample zero such deliveries — the
    # invariants still run on every rotated trial above
    cover.append(_chaos_trial(np.random.default_rng(11)))
    assert any(c["tainted"] for c in cover)
