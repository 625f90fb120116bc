"""Fused single-launch ``olaf_step`` cycle benchmarks (BENCH_step.json).

Measures the full PS data-plane cycle — burst enqueue, drain-k, weighted
apply — in its two generations:

  * ``two_launch`` — the PR 2 pipeline verbatim (the shape of
    ``AsyncDRLTrainer._drain_ps_queue`` + ``ParameterServer.on_updates``):
    a ``jax_enqueue_burst`` dispatch, a ``jax_dequeue_burst`` dispatch, a
    blocking host round trip on the drained block (validity + the O(k·D)
    payload copy), the agg_count-weighted mean in numpy, and a separately
    dispatched apply.
  * ``fused`` — one jitted ``olaf_step`` cycle (enqueue+drain in a single
    launch) with the weighted apply and the running AoM accumulator folded
    into the same executable; donated buffers, zero host syncs.

The ratio of the two timings is taken in the same run on the same machine,
so it is machine-independent — ``check_regression.py --floors`` gates it
(floor 2×). A separate row times the Pallas kernel itself: through the
interpreter off a TPU (informational), compiled on a TPU backend.
"""
from __future__ import annotations

import time

import numpy as np


def olaf_step_micro(Q: int = 8, D: int = 65536, burst: int = 8, k: int = 8,
                    iters: int = 30) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.core.aom import jax_aom_init, jax_aom_update_block
    from repro.core.olaf_queue import (jax_dequeue_burst_donating,
                                       jax_enqueue_burst_donating,
                                       jax_olaf_step, jax_queue_init)
    from repro.core.txctl import (QueueFeedback, TransmissionController,
                                  TxControlConfig, jax_txctl_ack,
                                  jax_txctl_gate, jax_txctl_init)

    rng = np.random.default_rng(0)
    state = jax_queue_init(Q, D)
    params0 = jnp.asarray(rng.normal(size=D), jnp.float32)
    workers = rng.integers(0, 8, burst)
    args = (jnp.asarray(rng.integers(0, Q, burst), jnp.int32),
            jnp.asarray(workers, jnp.int32),
            jnp.asarray(rng.random(burst), jnp.float32),
            jnp.asarray(rng.normal(size=burst), jnp.float32),
            jnp.asarray(rng.normal(size=(burst, D)), jnp.float32))
    lr = 1e-3
    tx_cfg = TxControlConfig(delta_threshold=0.4)

    # Both pipelines run the same full cycle — §5 txctl gate, enqueue,
    # drain-k, the paper's running-average PS apply (g_a <- avg(g_a, g);
    # w <- w - γ·g_a), AoM accounting, ACK production. PR 2 ran everything
    # but the two queue launches host-side (numpy PS + per-worker
    # controllers + sawtooth log, as in AsyncDRLTrainer + the simulator);
    # the fused step keeps all of it on device.
    def two_launch_iter(queue, w_host, ga_host, ctls, aom_log, now):
        for wid in np.unique(workers):  # per-worker host txctl (§5)
            ctls[wid].should_send(now)
        queue = jax_enqueue_burst_donating(queue, *args)
        queue, out = jax_dequeue_burst_donating(queue, k)
        valid = np.asarray(out["valid"])  # blocking device sync
        if valid.any():
            wts = np.asarray(out["agg_count"])[valid].astype(np.float64)
            p = np.asarray(out["payload"])[valid]  # O(k·D) host copy
            gen = np.asarray(out["gen_time"])[valid]
            g = (wts[:, None] * p).sum(0) / wts.sum()
            ga_host = g if ga_host is None else 0.5 * (ga_host + g)
            w_host = w_host - lr * ga_host
            for t in gen:  # host AoM sawtooth accounting
                aom_log.append((now, float(t)))
            fb = QueueFeedback(int(valid.sum()), queue.cluster.shape[0],
                               int(valid.sum()))
            for wid in np.unique(workers):
                ctls[wid].on_ack(now, fb)
        ack = np.asarray(w_host, np.float32)  # ACK multicast weights
        return queue, w_host, ga_host, ack

    def fused_step(queue, params, ga, aom, tx, key, now):
        key, sub = jax.random.split(key)
        # the gate result feeds the cycle, so it cannot be dead-code
        # eliminated from the fused timing (the feedback state mirrors the
        # two-launch side's: uncongested, so every row in fact sends and
        # both pipelines enqueue the identical workload)
        send, _ = jax_txctl_gate(tx, sub, now, tx_cfg.delta_threshold,
                                 tx_cfg.v, worker_ids=args[1])
        queue, out = jax_olaf_step(queue, *args, k, jnp.inf, send)
        wts = out["valid"] * out["agg_count"].astype(jnp.float32)
        g = jnp.einsum("k,kd->d", wts, out["payload"]) \
            / jnp.maximum(wts.sum(), 1.0)
        ga = 0.5 * (ga + g)
        aom = jax_aom_update_block(
            aom, jnp.full(out["valid"].shape, now, jnp.float32),
            out["gen_time"], out["valid"])
        acked = jnp.zeros((8,), bool).at[args[1]].set(True)
        tx = jax_txctl_ack(tx, acked, now, out["n_valid"].astype(jnp.float32),
                           float(queue.cluster.shape[0]))
        return queue, params - lr * ga, ga, aom, tx, key

    fused = jax.jit(fused_step, donate_argnums=(0, 1, 2, 3, 4))

    def fresh():
        return (jax.tree_util.tree_map(jnp.copy, state), jnp.copy(params0),
                jnp.zeros((D,), jnp.float32), jax_aom_init(),
                jax_txctl_init(8), jax.random.key(0))

    def run_two_launch(q, p, *_):
        w_host, ga_host = np.asarray(p, np.float64), None
        ctls = {w: TransmissionController(tx_cfg, np.random.default_rng(w))
                for w in np.unique(workers)}
        aom_log = []
        for it in range(iters):
            q, w_host, ga_host, _ack = two_launch_iter(
                q, w_host, ga_host, ctls, aom_log, float(it))
        jax.block_until_ready(q.payload)

    def run_fused(q, p, ga, a, tx, key):
        for it in range(iters):
            q, p, ga, a, tx, key = fused(q, p, ga, a, tx, key,
                                         jnp.float32(it))
        jax.block_until_ready(p)

    def timed(run, reps=4):
        """Best-of-``reps``: the min suppresses scheduler/load noise."""
        run(*fresh())  # compile/warm
        best = float("inf")
        for _ in range(reps):
            st = fresh()
            t0 = time.time()
            run(*st)
            best = min(best, (time.time() - t0) / iters * 1e6)
        return best

    two_us = timed(run_two_launch)
    fused_us = timed(run_fused)
    return dict(Q=Q, D=D, burst=burst, k=k, two_launch_us=two_us,
                fused_us=fused_us, speedup=two_us / fused_us)


def olaf_step_kernel_micro(Q: int = 32, D: int = 4096, burst: int = 8,
                           k: int = 4, iters: int = 5) -> dict:
    """Times the Pallas ``olaf_step`` kernel itself (interpreted off a TPU
    — informational; the roofline target applies compiled, on a TPU)."""
    import jax
    import jax.numpy as jnp
    from repro.core.olaf_queue import jax_queue_init
    from repro.kernels import ops

    rng = np.random.default_rng(1)
    state = jax_queue_init(Q, D)
    args = (jnp.asarray(rng.integers(0, Q, burst), jnp.int32),
            jnp.asarray(rng.integers(0, 8, burst), jnp.int32),
            jnp.asarray(rng.random(burst), jnp.float32),
            jnp.asarray(rng.normal(size=burst), jnp.float32),
            jnp.asarray(rng.normal(size=(burst, D)), jnp.float32))

    def run():
        st = jax.tree_util.tree_map(jnp.copy, state)
        for _ in range(iters):
            st, out = ops.olaf_step(st, *args, k=k, impl="pallas")
        jax.block_until_ready(st.payload)

    run()  # compile/warm
    best = float("inf")
    for _ in range(3):
        t0 = time.time()
        run()
        best = min(best, (time.time() - t0) / iters * 1e6)
    # HBM roofline: the cycle must touch the queue payload once and the
    # burst + drained rows once each
    bytes_moved = 4 * (2 * Q * D + burst * D + k * D)
    return dict(Q=Q, D=D, burst=burst, k=k, kernel_us=best,
                backend=jax.default_backend(), bytes_moved=bytes_moved,
                gbps=bytes_moved / (best * 1e-6) / 1e9)


def hybrid_replay_micro(dim: int = 512, reps: int = 3) -> dict:
    """The §8.3 hybrid control-plane replay: per-event vs windowed batch.

    Runs the identical congested SW1/SW2/SW3 trace through both consumers
    (``HybridMultiSwitchDataPlane.feed`` one Python call per queue event
    with one device put per ingress row, vs ``feed_window`` with one
    host-batched classify pass and one staged ``(S, U, D)`` block put per
    transmission window) and reports host→device transfers per delivered
    update — the host-share metric the windowed replay exists to cut — and
    the hybrid wall clock. The transfer ratio is structural (a property of
    the trace, not the machine), so ``check_regression.py --floors`` gates
    it at ≥ 2×.
    """
    from repro.core.hybrid import run_hybrid_multihop
    from repro.core.netsim import multihop_cfg

    kw = dict(n_clusters_per_group=3, workers_per_cluster=6, horizon=0.3,
              interval_s1=0.008, interval_s2=0.009, x1_gbps=0.4e-3,
              x2_gbps=0.4e-3, sw3_gbps=0.6e-3, size_bits=8192,
              sw12_slots=6, sw3_slots=6)

    def run(batched):
        best, res = float("inf"), None
        for _ in range(reps):
            cfg = multihop_cfg("olaf", seed=7, **kw)
            t0 = time.time()
            res, _ = run_hybrid_multihop(dim, sim_cfg=cfg, batched=batched)
            best = min(best, time.time() - t0)
        return best, res

    ev_s, ev = run(batched=False)  # warm-compiles the combine variants
    win_s, win = run(batched=True)
    n = max(len(win.delivered), 1)
    assert len(ev.delivered) == len(win.delivered)
    return dict(
        dim=dim, delivered=len(win.delivered),
        combined_updates=win.combined_updates, launches=win.launches,
        per_event_s=ev_s, windowed_s=win_s,
        per_event_h2d=ev.h2d_transfers, windowed_h2d=win.h2d_transfers,
        per_event_h2d_per_delivery=ev.h2d_transfers / n,
        windowed_h2d_per_delivery=win.h2d_transfers / n,
        wall_speedup=ev_s / win_s,
        speedup=ev.h2d_transfers / max(win.h2d_transfers, 1))


def topology_sweep(dim: int = 256, reps: int = 2) -> dict:
    """The declarative-topology hybrid data plane across spec presets.

    Runs one congested trace per named topology (chain-3, wide fan-in-4,
    fat-tree k=2, multi-rack) through both trace consumers and records, per
    topology: hybrid wall clock, host→device transfers per delivered
    update, combine launches (per-switch flush cadence) and fused
    combine+forward dispatches. ``speedup`` is the per-event vs windowed
    h2d-transfer ratio — structural, like ``hybrid_replay``'s — and the
    fat-tree row is gated in ``check_regression.py --floors``
    (``topology_fattree``).
    """
    from repro.core.hybrid import run_hybrid_multihop
    from repro.core.topology import (chain_cfg, fanin_cfg, fattree_cfg,
                                     multirack_cfg)

    load = dict(gen_interval=0.006, horizon=0.3, seed=7)
    topos = {
        "chain3": lambda: chain_cfg(3, clusters_per_ingress=3,
                                    workers_per_cluster=4, **load),
        "fanin4": lambda: fanin_cfg(4, clusters_per_ingress=2,
                                    workers_per_cluster=3, **load),
        "fattree_k2": lambda: fattree_cfg(2, clusters_per_ingress=2,
                                          workers_per_cluster=5, **load),
        "multirack": lambda: multirack_cfg(6, clusters_per_ingress=1,
                                           workers_per_cluster=4, **load),
    }
    out = {}
    for name, mk in topos.items():
        def run(batched):
            best, res = float("inf"), None
            for _ in range(reps):
                cfg = mk()
                t0 = time.time()
                res, _ = run_hybrid_multihop(dim, sim_cfg=cfg,
                                             batched=batched)
                best = min(best, time.time() - t0)
            return best, res

        ev_s, ev = run(batched=False)
        win_s, win = run(batched=True)
        n = max(len(win.delivered), 1)
        assert len(ev.delivered) == len(win.delivered)
        out[name] = dict(
            switches=len(win.switch_launches), dim=dim,
            delivered=len(win.delivered), forwarded=win.forwarded,
            launches=win.launches, forward_launches=win.forward_launches,
            switch_window_landings=sum(win.switch_launches.values()),
            per_event_s=ev_s, windowed_s=win_s,
            per_event_h2d_per_delivery=ev.h2d_transfers / n,
            windowed_h2d_per_delivery=win.h2d_transfers / n,
            wall_speedup=ev_s / win_s,
            speedup=ev.h2d_transfers / max(win.h2d_transfers, 1))
    return out


def main(report):
    micro = olaf_step_micro()
    report("olaf_step_fused_q8_d64k", micro["fused_us"],
           f"two-launch {micro['two_launch_us']:.0f}us vs fused "
           f"{micro['fused_us']:.0f}us = {micro['speedup']:.1f}x "
           f"(burst {micro['burst']}, drain-k {micro['k']})")
    kern = olaf_step_kernel_micro()
    report("olaf_step_kernel_q32_d4k", kern["kernel_us"],
           f"pallas cycle {kern['kernel_us']:.0f}us, "
           f"{kern['gbps']:.3f} GB/s vs HBM roofline "
           f"({kern['backend']}: compiled on tpu, interpreted "
           f"elsewhere)")
    hyb = hybrid_replay_micro()
    report("hybrid_window_replay_d512", hyb["windowed_s"] * 1e6,
           f"windowed {hyb['windowed_s'] * 1e3:.0f}ms vs per-event "
           f"{hyb['per_event_s'] * 1e3:.0f}ms "
           f"({hyb['wall_speedup']:.2f}x wall); h2d/delivery "
           f"{hyb['per_event_h2d_per_delivery']:.1f} -> "
           f"{hyb['windowed_h2d_per_delivery']:.1f} = "
           f"{hyb['speedup']:.1f}x fewer transfers")
    topo = topology_sweep()
    for name, row in topo.items():
        report(f"topology_{name}", row["windowed_s"] * 1e6,
               f"{row['switches']} switches, {row['delivered']} delivered, "
               f"{row['forwarded']} forwarded; h2d/delivery "
               f"{row['per_event_h2d_per_delivery']:.1f} -> "
               f"{row['windowed_h2d_per_delivery']:.1f} = "
               f"{row['speedup']:.1f}x; {row['launches']} combine + "
               f"{row['forward_launches']} fused forward launches")
    return dict(olaf_step_cycle=micro, olaf_step_kernel=kern,
                hybrid_replay=hyb, topology_sweep=topo,
                topology_fattree=topo["fattree_k2"])
