#!/usr/bin/env python3
"""Smoke test: the OLAF data plane end to end on one TPU chip.

    python chip_smoke.py                # one chip: every phase below
    python chip_smoke.py --four-chips   # four chips: the sharded fabric only

One process drives the system's own entry points, each phase printing
what it ran and how its comparison came out:

* scenario (a) — ``run_hybrid_multihop(sim_impl="vectorized")`` (the path
  of ``launch/train.py --mode scenario``) on the §8.3 SW1/SW2→SW3 fan-in
  with dyadic rates, delays and generation times, on the exact event grid.
  Delivery times, queue statistics and counters must equal the ``netsim``
  heap oracle run on the host; payloads must match the heap's own
  combining within a printed tolerance.
* scenario (b) — the same entry on a fat-tree k=8 with 8 spines (80
  switches, 1024 workers) on the coarse ``dt = 2**-11`` grid, at the
  paper's update width (the flattened PPO actor-critic). Deliveries must
  be nonzero and finite, packets conserved, and the run equal to the same
  jitted scan on the host CPU in this process.
* paper workload — ``run_hybrid_ppo`` on the §8.3 topology with real PPO
  gradients, once through the windowed replay (compiled ``olaf_forward`` /
  ``olaf_combine_window`` kernels) and once through the vectorized scan;
  the two parameter servers must agree.
* LM gradients — ``run_olaf_async`` with ``smollm-360m`` at its published
  widths, depth cut to fit one chip, once with the compiled ``olaf_step``
  kernel and once with its XLA composition: queue metadata and applied /
  combined counts equal, losses within a printed tolerance.

``--four-chips`` runs only scenario (b) sharded over a 2x2
``("switch", "worker")`` mesh and compares it bitwise with the one-chip
run in the same process.

The last line of standard output is ``{"ok": true, "device": {...}}``,
printed only when every phase passed on a TPU. Without a TPU, or when a
phase fails, the script exits nonzero; it never falls back to the CPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# scenario (a): §8.3 fan-in, dyadic so the heap's event order is exact
A_DIM = 64
A_HORIZON = 0.5
# scenario (b): the bench_vecsim.py k=8 row
B_K, B_SPINES, B_WPC, B_HORIZON, B_DT = 8, 8, 8, 0.125, 2.0 ** -11
# paper workload: §8.3 default topology, horizon cut to a few hundred
# PPO gradients
PPO_HORIZON = 0.2
# LM gradients: smollm-360m, published widths, depth cut for one chip
LM_ARCH, LM_LAYERS = "smollm-360m", 4
LM_WORKERS, LM_QUEUE, LM_BURST, LM_DRAIN, LM_STEPS = 4, 4, 4, 2, 4

PAYLOAD_RTOL, PAYLOAD_ATOL = 1e-5, 1e-6  # f32 association/division order
LOSS_RTOL = 1e-3  # bf16 model, weights that differ by f32 rounding


class PhaseError(AssertionError):
    pass


def check(cond, msg):
    if not cond:
        raise PhaseError(msg)


def ppo_width() -> int:
    """The paper's update width, sized exactly as ``run_hybrid_ppo`` does:
    the flattened CartPole actor-critic."""
    import jax
    import numpy as np
    from repro.configs.olaf_ppo import PPOConfig
    from repro.models.rlnets import init_actor_critic
    from repro.rl.async_trainer import flatten_params
    from repro.rl.env import make_env
    env = make_env("cartpole")
    cfg = dataclasses.replace(PPOConfig(), obs_dim=env.obs_dim,
                              n_actions=env.n_actions)
    flat, _ = flatten_params(init_actor_critic(jax.random.key(0), cfg))
    return int(np.asarray(flat).size)


def payload_diff(a, b):
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    if a.size == 0:
        return 0.0, True
    return float(np.max(np.abs(a - b))), bool(np.array_equal(a, b))


# ---------------------------------------------------------------------------
# scenario (a): exact grid vs the host heap oracle
# ---------------------------------------------------------------------------
def dyadic_multihop_cfg(horizon: float):
    from repro.core.topology import TopologySpec, build_sim_cfg, \
        multihop_spec
    spec = multihop_spec(x1_gbps=2.0 ** 20 / 1e9, x2_gbps=2.0 ** 20 / 1e9,
                         sw3_gbps=2.0 ** 21 / 1e9)
    spec = TopologySpec([dataclasses.replace(s, prop_delay=2.0 ** -12)
                         for s in spec.switches])
    return build_sim_cfg(spec, clusters_per_ingress=5, workers_per_cluster=10,
                         gen_interval=2.0 ** -3, gen_jitter=0.0,
                         size_bits=8192, horizon=horizon, seed=0)


def phase_scenario_exact(dim: int = A_DIM, horizon: float = A_HORIZON):
    import numpy as np
    from repro.core.hybrid import run_hybrid_multihop
    from repro.core.netsim import NetworkSimulator

    cfg = dyadic_multihop_cfg(horizon)
    rows = []  # dyadic payload rows, one per fresh send, in send order
    rng = np.random.default_rng(1)

    def payload_fn(now, worker_id):
        rows.append(rng.integers(-64, 64, dim).astype(np.float32) / 8)
        return rows[-1].copy(), 0.0

    t0 = time.time()
    ref = NetworkSimulator(dataclasses.replace(cfg,
                                               payload_fn=payload_fn)).run()
    t_heap = time.time() - t0
    t0 = time.time()
    hyb, _ = run_hybrid_multihop(dim, sim_cfg=cfg,
                                 payload_rows=np.stack(rows),
                                 sim_impl="vectorized")
    t_dev = time.time() - t0

    def ref_key(t, u):
        return (u.cluster_id, u.worker_id, u.gen_time, u.agg_count,
                u.subsumed, t)

    want = {}
    by_cluster = {}
    for u in ref.delivered_updates:
        by_cluster.setdefault(u.cluster_id, []).append(u)
    for c, ups in by_cluster.items():
        for (t, _), u in zip(ref.deliveries[c], ups):
            want[ref_key(t, u)] = u.payload
    got = {ref_key(t, u): p for t, u, p in hyb.delivered}
    check(len(want) == len(ref.delivered_updates), "heap delivery keys "
          "are not unique")
    check(sorted(got) == sorted(want),
          f"deliveries differ: {len(got)} on device vs {len(want)} heap")
    check(hyb.queue_stats == ref.queue_stats,
          f"queue stats differ: {hyb.queue_stats} vs {ref.queue_stats}")
    counters = dict(link_dropped=ref.link_dropped, rerouted=ref.reroutes,
                    ps_dropped=ref.ps_dropped,
                    stale_rejected=ref.stale_rejected,
                    stale_deferred=ref.stale_deferred)
    for f, v in counters.items():
        check(getattr(hyb, f) == v, f"{f}: {getattr(hyb, f)} vs {v}")
    diff, bitwise = payload_diff(np.stack([got[k] for k in sorted(want)]),
                                 np.stack([want[k] for k in sorted(want)]))
    np.testing.assert_allclose(
        np.stack([np.asarray(got[k]) for k in sorted(want)]),
        np.stack([want[k] for k in sorted(want)]),
        rtol=PAYLOAD_RTOL, atol=PAYLOAD_ATOL)
    agg = sum(q["aggregations"] for q in ref.queue_stats.values())
    print(f"scenario (a) §8.3 exact grid: {len(cfg.switches)} switches, "
          f"{len(cfg.workers)} workers, dim {dim}, {len(rows)} sends, "
          f"{len(got)} deliveries, {agg} aggregations; delivery times, "
          f"queue stats and counters equal the heap oracle; payloads "
          f"max|diff| {diff:.3g} (bitwise {bitwise}; tolerance rtol "
          f"{PAYLOAD_RTOL} atol {PAYLOAD_ATOL}); heap {t_heap:.2f}s, "
          f"device path {t_dev:.2f}s wall incl. compile")


# ---------------------------------------------------------------------------
# scenario (b): fat-tree k=8, 1024 workers, coarse grid
# ---------------------------------------------------------------------------
def fattree_cfg(k: int = B_K, spines: int = B_SPINES, wpc: int = B_WPC,
                horizon: float = B_HORIZON):
    from repro.core.topology import build_sim_cfg, fattree_spec
    return build_sim_cfg(fattree_spec(k, spines=spines),
                         clusters_per_ingress=2, workers_per_cluster=wpc,
                         gen_interval=2.0 ** -6, gen_jitter=0.3,
                         size_bits=8192, horizon=horizon, seed=3)


def run_fattree(cfg, dim, mesh=None):
    from repro.core.hybrid import run_hybrid_multihop
    t0 = time.time()
    hyb, _ = run_hybrid_multihop(dim, sim_cfg=cfg, sim_impl="vectorized",
                                 sim_dt=B_DT, sim_mesh=mesh)
    return hyb, time.time() - t0


def same_run(a, b) -> bool:
    import numpy as np
    if len(a.delivered) != len(b.delivered):
        return False
    for (ta, ua, pa), (tb, ub, pb) in zip(a.delivered, b.delivered):
        if (ta, ua.cluster_id, ua.worker_id, ua.gen_time, ua.agg_count) != \
                (tb, ub.cluster_id, ub.worker_id, ub.gen_time, ub.agg_count):
            return False
        if not np.array_equal(np.asarray(pa), np.asarray(pb)):
            return False
    return (a.queue_stats == b.queue_stats
            and np.array_equal(a.final_counts, b.final_counts)
            and a.forwarded == b.forwarded)


def check_fattree(hyb, cfg):
    import numpy as np
    qs = hyb.queue_stats
    tot = {f: sum(q[f] for q in qs.values()) for f in next(iter(qs.values()))}
    occupied = sum(len(v) for v in hyb.residual_slot_counts.values())
    lost = (hyb.link_dropped + hyb.ps_dropped + hyb.stale_rejected
            + hyb.stale_deferred)
    check(len(hyb.delivered) > 0, "no update reached the PS")
    pay = np.stack([np.asarray(p) for _, _, p in hyb.delivered])
    check(bool(np.all(np.isfinite(pay))), "non-finite delivered payload")
    # every departure is delivered, forwarded or lost in flight; every
    # append has departed, still waits in a slot, or is in service (at
    # most one packet per switch)
    check(tot["departed"] == len(hyb.delivered) + hyb.forwarded + lost,
          f"departures not conserved: {tot} vs {len(hyb.delivered)} "
          f"delivered + {hyb.forwarded} forwarded + {lost} lost")
    in_service = tot["enqueued"] - tot["departed"] - occupied
    check(0 <= in_service <= len(cfg.switches),
          f"appends not conserved: {tot}, {occupied} occupied")
    return tot, in_service, pay.shape


def phase_scenario_fattree(dim: int, k: int = B_K, spines: int = B_SPINES,
                           **cfg_kw):
    import jax
    cfg = fattree_cfg(k, spines, **cfg_kw)
    hyb, t_dev = run_fattree(cfg, dim)
    tot, in_service, shape = check_fattree(hyb, cfg)
    line = (f"scenario (b) fat-tree k={k} x {spines} spines: "
            f"{len(cfg.switches)} switches, {len(cfg.workers)} workers, dim "
            f"{dim}, dt 2**-11: {len(hyb.delivered)} deliveries "
            f"{shape}, all finite; {tot['enqueued']} appends = "
            f"{tot['departed']} departed + "
            f"{tot['enqueued'] - tot['departed'] - in_service} waiting + "
            f"{in_service} in service; {tot['departed']} departed = "
            f"{len(hyb.delivered)} delivered + {hyb.forwarded} forwarded "
            f"+ {hyb.link_dropped} lost; device path {t_dev:.2f}s wall "
            f"incl. compile")
    with jax.default_device(jax.devices("cpu")[0]):
        ref, t_cpu = run_fattree(cfg, dim)
    check(same_run(hyb, ref), "chip run differs from the CPU run of the "
          "same scan")
    print(line + f"; equals the host CPU run bitwise ({t_cpu:.2f}s wall)")


def phase_fattree_four_chips(dim: int, k: int = B_K,
                             spines: int = B_SPINES, **cfg_kw):
    from repro.distributed.sharding import vecsim_mesh
    cfg = fattree_cfg(k, spines, **cfg_kw)
    mesh = vecsim_mesh(len(cfg.switches), worker_shards=2)
    check(mesh.devices.size == 4,
          f"mesh holds {mesh.devices.size} devices, not 4")
    one, t_one = run_fattree(cfg, dim)
    sharded, t_sh = run_fattree(cfg, dim, mesh=mesh)
    check_fattree(sharded, cfg)
    check(same_run(one, sharded), "sharded run differs from the one-chip "
          "run")
    print(f"four chips: fat-tree k={k} x {spines} spines, "
          f"{len(cfg.workers)} workers, dim {dim}, mesh "
          f"{dict(zip(mesh.axis_names, mesh.devices.shape))} on "
          f"{[d.id for d in mesh.devices.flat]}: {len(sharded.delivered)} "
          f"deliveries equal the one-chip run bitwise (one chip "
          f"{t_one:.2f}s, sharded {t_sh:.2f}s wall incl. compile)")


# ---------------------------------------------------------------------------
# paper workload: real PPO gradients, window replay vs vectorized scan
# ---------------------------------------------------------------------------
def phase_ppo(horizon: float = PPO_HORIZON):
    import numpy as np
    from repro.rl.async_trainer import run_hybrid_ppo
    out = {}
    for impl in ("window", "vectorized"):
        t0 = time.time()
        hyb, ps, cfg = run_hybrid_ppo(seed=0, sharded=False, sim_impl=impl,
                                      horizon=horizon)
        out[impl] = (hyb, ps, time.time() - t0)
    (hw, pw, tw), (hv, pv, tv) = out["window"], out["vectorized"]
    check(len(hw.delivered) == len(hv.delivered) > 0,
          f"deliveries {len(hw.delivered)} (window) vs "
          f"{len(hv.delivered)} (vectorized)")
    check(hw.queue_stats == hv.queue_stats, "queue stats differ")
    check(hw.forward_launches > 0, "the window path ran no forward kernel")
    diff, bitwise = payload_diff(pw.w, pv.w)
    np.testing.assert_allclose(pw.w, pv.w, rtol=PAYLOAD_RTOL,
                               atol=PAYLOAD_ATOL)
    print(f"paper workload run_hybrid_ppo §8.3, horizon {horizon}: "
          f"{len(cfg.workers)} workers, dim {pw.w.size}, "
          f"{len(hw.delivered)} deliveries, {hw.launches} window launches "
          f"+ {hw.forward_launches} forward launches vs 1 scan; PS weights "
          f"max|diff| {diff:.3g} (bitwise {bitwise}; tolerance rtol "
          f"{PAYLOAD_RTOL} atol {PAYLOAD_ATOL}); window {tw:.2f}s, "
          f"vectorized {tv:.2f}s wall incl. compile")


# ---------------------------------------------------------------------------
# LM gradients: smollm-360m at published widths, kernel vs XLA
# ---------------------------------------------------------------------------
def lm_args(step_impl: str, **kw):
    base = dict(seq=64, batch=8, seed=0, lr=1e-3, workers=LM_WORKERS,
                queue_slots=LM_QUEUE, burst_size=LM_BURST,
                drain_k=LM_DRAIN, steps=LM_STEPS, log_every=0, ckpt=None,
                ckpt_every=0, step_impl=step_impl)
    base.update(kw)
    return argparse.Namespace(**base)


def phase_lm(cfg=None, **kw):
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.launch.train import run_olaf_async
    from repro.models import api

    full = get_config(LM_ARCH)
    cfg = cfg or dataclasses.replace(full, n_layers=LM_LAYERS)
    shapes = jax.eval_shape(lambda: api.init_model(jax.random.key(0), cfg))
    dim = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    args = {impl: lm_args(impl, **kw) for impl in ("pallas", "xla")}
    a = args["pallas"]
    print(f"LM cut: {cfg.name} d_model {cfg.d_model}, heads {cfg.n_heads}/"
          f"{cfg.n_kv_heads}, d_ff {cfg.d_ff}, vocab {cfg.vocab}, depth "
          f"{cfg.n_layers} of {full.n_layers}; flattened gradient "
          f"{dim} f32 = {dim * 4 / 2**30:.2f} GiB per queue slot; queue "
          f"{a.queue_slots} slots, burst {a.burst_size}, drain-k "
          f"{a.drain_k}, {a.workers} workers, {a.steps} PS steps")
    res = {}
    for impl in ("pallas", "xla"):
        t0 = time.time()
        res[impl] = run_olaf_async(cfg, args[impl])
        stats = jax.devices()[0].memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        print(f"LM {impl}: losses {res[impl].losses}, applied "
              f"{res[impl].applied}, combined {res[impl].combined}; "
              f"{time.time() - t0:.2f}s wall incl. compile; device peak "
              f"{'not reported' if peak is None else f'{peak / 2**30:.2f} GiB'}")
    p, x = res["pallas"], res["xla"]
    check(p.applied == x.applied and p.combined == x.combined,
          "applied/combined counts differ")
    for f in p.queue:
        check(np.array_equal(p.queue[f], x.queue[f]), f"queue {f} differs")
    check(len(p.losses) == a.steps, f"{len(p.losses)} applies logged")
    check(bool(np.all(np.isfinite(p.losses))), "non-finite loss")
    np.testing.assert_allclose(p.losses, x.losses, rtol=LOSS_RTOL)
    diff = float(np.max(np.abs(np.subtract(p.losses, x.losses))))
    print(f"LM gradients: pallas vs xla queue metadata and "
          f"applied/combined counts equal; losses max|diff| {diff:.3g} "
          f"(tolerance rtol {LOSS_RTOL})")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the fat-tree sharded over four chips "
                         "and its one-chip reference")
    args = ap.parse_args()

    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX found {dev.platform} devices", file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devs) < need:
        print(f"needs {need} TPU chips, found {len(devs)}", file=sys.stderr)
        return 1
    print(f"device: {dev.platform} {dev.device_kind} x {len(devs)}")

    dim = ppo_width()
    if args.four_chips:
        phases = [lambda: phase_fattree_four_chips(dim)]
    else:
        phases = [phase_scenario_exact,
                  lambda: phase_scenario_fattree(dim),
                  phase_ppo, phase_lm]
    t_all = time.time()
    for phase in phases:
        phase()
        sys.stdout.flush()
    print(f"all phases passed in {time.time() - t_all:.1f}s wall")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
