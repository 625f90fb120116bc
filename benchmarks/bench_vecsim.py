"""Vectorized device-resident simulator benchmarks (BENCH_vecsim.json).

Two rows:

  * ``vecsim_h2d`` — the §8.3 congested SW1/SW2/SW3 trace through the
    windowed hybrid replay vs the vectorized consumer
    (``run_hybrid_multihop(sim_impl="vectorized")``): the whole scenario
    advances as one jitted ``lax.scan`` with a single staged payload
    upload, so host→device transfers per delivered update collapse from
    one block put per transmission window to a handful of staged arrays
    for the entire run. The transfer ratio is structural (a property of
    the trace, not the machine), so ``check_regression.py --floors``
    gates it at ≥ 5×.
  * ``vecsim_scan_rate`` — raw scan throughput: grid boundaries resolved
    per second by the warm jitted runner on the same congested scenario
    (informational; absolute, so not floor-gated).
  * ``vecsim_scale`` — the multi-device scale-out table: fat-tree k=8
    with 8 spines (80 switches, ~1k workers) on a coarse uniform grid,
    single-device vs the 8-way sharded ``shard_map`` runner (per-shard
    transit rings shrink the dominant arrival-sort axis; the frontier is
    the only cross-shard exchange). Runs in a subprocess on the CPU
    (``JAX_PLATFORMS=cpu``) with ``--xla_force_host_platform_device_count=8``
    so the parent process's device count doesn't matter — and so the child
    never competes with the parent for an accelerator the parent holds.
    These rows are CPU counts by construction and say so in their output
    (``platform``). Floor-gated: sharded ≥ 2× single-device
    boundaries/s on the k=8 row, and the single-device rate itself ≥ 1×
    a conservatively recorded baseline (``vecsim_scale_base``). The two
    runs must agree bitwise (asserted in-child).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

# fat-tree k=8 single-device boundaries/s recorded on the container this
# suite was authored on (measured ~23/s warm); deliberately conservative
# so slower CI runners stay green while a real algorithmic regression
# (e.g. the arrival-sort axis growing back to the global ring bound)
# still trips the 1.0x floor.
K8_BASE_RATE = 10.0

_SCALE_CHILD = r"""
import os, sys, json, time
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
import numpy as np
import jax
from repro.core import vecsim
from repro.core.topology import build_sim_cfg, fattree_spec

assert jax.default_backend() == "cpu", jax.default_backend()
k, spines, wpc, dim, reps = map(int, sys.argv[1:6])
spec = fattree_spec(k, spines=spines)
cfg = build_sim_cfg(spec, clusters_per_ingress=2, workers_per_cluster=wpc,
                    gen_interval=2.0 ** -6, gen_jitter=0.3,
                    size_bits=8192, horizon=0.125, seed=3)
dt = 2.0 ** -11  # coarse uniform grid: horizon/dt = 256 boundaries

def run(mesh):
    res = vecsim.run_vecsim(cfg, dt=dt, allow_coarse=True, dim=dim,
                            mesh=mesh)  # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.time()
        res = vecsim.run_vecsim(cfg, dt=dt, allow_coarse=True, dim=dim,
                                mesh=mesh)
        best = min(best, time.time() - t0)
    return res, best

ndev = len(jax.devices())
r1, t1 = run(None)
rs, ts = run((min(8, ndev), 1))
assert np.array_equal(r1.delivery_times, rs.delivery_times)
assert np.array_equal(r1.delivered_payloads, rs.delivered_payloads)
assert r1.aom == rs.aom and r1.sim.queue_stats == rs.sim.queue_stats
n = max(len(r1.sim.delivered_updates), 1)
print(json.dumps(dict(
    platform="cpu", switches=len(spec.switches), workers=len(cfg.workers),
    devices=min(8, ndev), n_steps=int(r1.n_steps), delivered=n,
    wall_1dev_s=t1, wall_shard_s=ts,
    rate_1dev=r1.n_steps / t1, rate_shard=rs.n_steps / ts,
    h2d=int(rs.h2d_transfers), h2d_per_delivery=rs.h2d_transfers / n,
    speedup=t1 / ts, bitwise=True)))
"""


def vecsim_scale_row(k: int, spines: int, wpc: int, dim: int = 64,
                     reps: int = 2) -> dict:
    """One (switches x devices) scale row, measured in a child process
    on 8 forced host-platform CPU devices (jax device count is fixed at
    import time, so the parent cannot retrofit it; and a parent that has
    touched an accelerator holds it, so the child stays on the CPU)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(root, "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", _SCALE_CHILD, str(k), str(spines),
         str(wpc), str(dim), str(reps)],
        capture_output=True, text=True, env=env, timeout=3000)
    if out.returncode != 0:
        raise RuntimeError(f"vecsim_scale child failed:\n{out.stderr[-2000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def vecsim_replay_micro(dim: int = 512, reps: int = 3) -> dict:
    """Windowed batch replay vs the one-dispatch vectorized scan on the
    identical congested multihop trace."""
    from repro.core.hybrid import run_hybrid_multihop
    from repro.core.netsim import multihop_cfg

    kw = dict(n_clusters_per_group=3, workers_per_cluster=6, horizon=0.3,
              interval_s1=0.008, interval_s2=0.009, x1_gbps=0.4e-3,
              x2_gbps=0.4e-3, sw3_gbps=0.6e-3, size_bits=8192,
              sw12_slots=6, sw3_slots=6)

    def run(sim_impl):
        best, res = float("inf"), None
        for _ in range(reps):
            cfg = multihop_cfg("olaf", seed=7, **kw)
            t0 = time.time()
            res, _ = run_hybrid_multihop(dim, sim_cfg=cfg,
                                         sim_impl=sim_impl)
            best = min(best, time.time() - t0)
        return best, res

    win_s, win = run("window")  # warm-compiles the combine variants
    vec_s, vec = run("vectorized")
    n = max(len(vec.delivered), 1)
    assert len(win.delivered) == len(vec.delivered)
    return dict(
        dim=dim, delivered=len(vec.delivered),
        windowed_launches=win.launches, vectorized_launches=vec.launches,
        windowed_s=win_s, vectorized_s=vec_s,
        windowed_h2d=win.h2d_transfers, vectorized_h2d=vec.h2d_transfers,
        windowed_h2d_per_delivery=win.h2d_transfers / n,
        vectorized_h2d_per_delivery=vec.h2d_transfers / n,
        wall_speedup=win_s / vec_s,
        speedup=win.h2d_transfers / max(vec.h2d_transfers, 1))


def vecsim_scan_rate(reps: int = 3) -> dict:
    """Warm-runner scan throughput: boundaries resolved per second on the
    congested multihop scenario (oracle-aligned exact grid)."""
    from repro.core import vecsim
    from repro.core.netsim import multihop_cfg

    cfg = multihop_cfg("olaf", seed=7, n_clusters_per_group=3,
                       workers_per_cluster=6, horizon=0.3,
                       interval_s1=0.008, interval_s2=0.009,
                       x1_gbps=0.4e-3, x2_gbps=0.4e-3, sw3_gbps=0.6e-3,
                       size_bits=8192, sw12_slots=6, sw3_slots=6)
    grid, _ = vecsim.oracle_event_times(cfg)
    res = vecsim.run_vecsim(cfg, grid=grid)  # compile + correctness pass
    best = float("inf")
    for _ in range(reps):
        t0 = time.time()
        res = vecsim.run_vecsim(cfg, grid=grid)
        best = min(best, time.time() - t0)
    return dict(n_steps=res.n_steps, delivered=len(res.sim.delivered_updates),
                wall_s=best, steps_per_s=res.n_steps / best)


def main(report):
    hyb = vecsim_replay_micro()
    report("vecsim_replay_d512", hyb["vectorized_s"] * 1e6,
           f"windowed {hyb['windowed_s'] * 1e3:.0f}ms vs vectorized "
           f"{hyb['vectorized_s'] * 1e3:.0f}ms "
           f"({hyb['wall_speedup']:.2f}x wall); h2d/delivery "
           f"{hyb['windowed_h2d_per_delivery']:.1f} -> "
           f"{hyb['vectorized_h2d_per_delivery']:.3f} = "
           f"{hyb['speedup']:.1f}x fewer transfers; launches "
           f"{hyb['windowed_launches']} -> {hyb['vectorized_launches']}")
    rate = vecsim_scan_rate()
    report("vecsim_scan_rate", rate["wall_s"] * 1e6,
           f"{rate['n_steps']} grid steps in {rate['wall_s'] * 1e3:.0f}ms "
           f"= {rate['steps_per_s']:.0f} steps/s (warm runner, "
           f"{rate['delivered']} delivered)")
    rows = {}
    for label, (k, spines, wpc) in (("k4", (4, 4, 8)), ("k8", (8, 8, 8))):
        r = vecsim_scale_row(k, spines, wpc)
        rows[label] = r
        report(f"vecsim_scale_{label}", r["wall_shard_s"] * 1e6,
               f"{r['switches']}sw x {r['devices']} {r['platform']} dev, "
               f"{r['workers']} workers: {r['rate_1dev']:.1f} -> "
               f"{r['rate_shard']:.1f} "
               f"boundaries/s = {r['speedup']:.2f}x sharded; "
               f"h2d/delivery {r['h2d_per_delivery']:.2f}; bitwise")
    k8 = rows["k8"]
    scale = dict(k8, rows=rows)
    base = dict(rate_1dev=k8["rate_1dev"], recorded_base=K8_BASE_RATE,
                speedup=k8["rate_1dev"] / K8_BASE_RATE)
    return dict(vecsim_h2d=hyb, vecsim_scan_rate=rate,
                vecsim_scale=scale, vecsim_scale_base=base)
