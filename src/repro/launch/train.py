"""Production LM training driver.

Runs the same ``train_step`` the dry-run lowers, on whatever devices exist
(host CPU for development, a TPU mesh in production), with the full
substrate: deterministic sharded data pipeline, AdamW, checkpoint/restart
(resume is bit-identical thanks to counter-keyed data), and optional
OLAF-async mode where data-parallel worker groups push gradients through an
OlafQueue combining stage instead of a synchronous all-reduce.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --steps 20 \
      --reduced --ckpt /tmp/ckpt
  PYTHONPATH=src python -m repro.launch.train --arch gemma-2b --reduced \
      --mode olaf-async --workers 4 --steps 30
  PYTHONPATH=src python -m repro.launch.train --mode scenario \
      --topology fattree --fattree-k 2 --sim-impl vectorized
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.ckpt import latest_step, restore_checkpoint, save_checkpoint
from repro.configs import SHAPES, get_config
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.models import api
from repro.optim.optimizers import OptConfig, apply_updates, init_opt_state


def make_train_step(cfg, opt):
    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: api.loss_fn(p, batch, cfg))(params)
        params, opt_state = apply_updates(params, grads, opt_state, opt)
        return params, opt_state, loss
    return jax.jit(train_step)


def run_sync(cfg, args) -> float:
    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                                  global_batch=args.batch, seed=args.seed))
    opt = OptConfig(lr=args.lr, grad_clip=1.0)
    params = api.init_model(jax.random.key(args.seed), cfg)
    opt_state = init_opt_state(params, opt)
    start = 0
    if args.ckpt and latest_step(args.ckpt) is not None:
        start, params, opt_state = restore_checkpoint(
            args.ckpt, params_like=jax.eval_shape(lambda: params),
            opt_like=jax.eval_shape(lambda: opt_state))
        print(f"resumed from step {start}")
    step_fn = make_train_step(cfg, opt)
    losses = []
    t0 = time.time()
    for step in range(start, args.steps):
        batch = {k: jnp.asarray(v) for k, v in data.batch(step).items()}
        params, opt_state, loss = step_fn(params, opt_state, batch)
        losses.append(float(loss))
        if args.log_every and step % args.log_every == 0:
            print(f"step {step}: loss {float(loss):.4f} "
                  f"({(time.time()-t0)/(step-start+1):.2f}s/step)")
        if args.ckpt and args.ckpt_every and (step + 1) % args.ckpt_every == 0:
            save_checkpoint(args.ckpt, step + 1, params, opt_state)
    if args.ckpt:
        save_checkpoint(args.ckpt, args.steps, params, opt_state)
    print(f"final loss {losses[-1]:.4f} (first {losses[0]:.4f})")
    return losses[-1]


@dataclasses.dataclass
class OlafAsyncResult:
    """What a :func:`run_olaf_async` run leaves: per applied PS step the
    mean worker loss of its burst, the drained rows it applied and the raw
    updates they combine; host copies of the final queue's metadata and
    counters (the payload stays on the device); the run's totals of burst
    rows the txctl gate deferred, drained rows the staleness bound rejected
    and rows the ingress screen withheld; and the time-averaged Age of
    Model (virtual time)."""
    losses: List[float]
    applied: List[int]
    combined: List[int]
    queue: Dict[str, np.ndarray]
    deferred: int
    stale: int
    screened: int
    avg_aom: float


def run_olaf_async(cfg, args) -> OlafAsyncResult:
    """OLAF-async data parallelism: N worker groups compute gradients on
    their own shard streams and push flattened updates through the device-
    resident OlafQueue; the PS side drains the queue and applies combined
    updates. Workers proceed without a barrier — a straggler's update merges
    or is superseded (the paper's technique applied to LM training).

    The whole feedback loop is device-resident: ONE jitted
    ``txctl_gate → olaf_step → weighted apply`` step with donated
    queue/params/opt/feedback buffers. The §5 transmission-control gate
    (vectorized ``jax_txctl`` with on-device PRNG) decides which burst rows
    transmit, the fused ``olaf_step`` cycle performs the burst enqueue and
    drain-k in a single launch, the agg_count-weighted mean gradient is
    applied, and the running Age-of-Model accumulator and per-worker ACK
    feedback are folded into the same step — zero per-iteration host
    syncs. Only buffered scalar logs cross the host boundary, in batches
    of ``log_every``.
    """
    with jax.profiler.TraceAnnotation("olaf/setup"):
        from repro.core.aggregation import jax_trimmed_combine
        from repro.core.aom import (jax_aom_average, jax_aom_init,
                                    jax_aom_update_block, jax_staleness_mask)
        from repro.core.olaf_queue import jax_queue_init, jax_screen_mask
        from repro.core.txctl import (TxControlConfig, jax_txctl_ack,
                                      jax_txctl_gate, jax_txctl_init,
                                      jax_txctl_set_active)
        from repro.kernels import ops
        from repro.models.module import tree_paths

        opt = OptConfig(lr=args.lr, grad_clip=1.0)
        params = api.init_model(jax.random.key(args.seed), cfg)
        opt_state = init_opt_state(params, opt)
        flat_like = tree_paths(params)
        sizes = {k: int(np.prod(v.shape)) for k, v in flat_like.items()}
        dim = sum(sizes.values())
        # a capacity below the cluster count (--queue-slots) makes the
        # paper's congestion regime (N active clusters > Q_max) reachable,
        # which is what arms the transmission-control gate
        capacity = getattr(args, "queue_slots", 0) or max(args.workers, 4)
        queue = jax_queue_init(capacity=capacity, dim=dim)
        drain_k = max(1, min(args.drain_k, capacity))

        # node churn: a subset of workers crashes at --crash-at (their
        # queued updates expire on the next drain, the txctl gate stops
        # scheduling them) and optionally rejoins at --restart-at as fresh
        # members
        crash_set = sorted({int(s) for s in
                            getattr(args, "crash_workers", "").split(",")
                            if s})
        crash_at = getattr(args, "crash_at", -1)
        restart_at = getattr(args, "restart_at", -1)
        churn = bool(crash_set) and crash_at >= 0
        # hard PS staleness bound (virtual time); 0 disables admission
        # control
        stale_bound = getattr(args, "staleness_bound", 0.0) or None
        # payload-integrity hardening: the device ingress screen
        # (non-finite / norm-outlier rows withheld before the queue) plus the
        # winsorized robust combine the PS falls back to when the screened
        # fraction of a burst exceeds --robust-threshold
        screen_on = bool(getattr(args, "ingress_screen", False))
        screen_factor = getattr(args, "screen_factor", 16.0)
        robust_threshold = getattr(args, "robust_threshold", 0.25)

        shards = [SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                                         global_batch=args.batch,
                                         n_shards=args.workers, shard_id=i,
                                         seed=args.seed))
                  for i in range(args.workers)]

        def flatten(tree):
            return jnp.concatenate([jnp.ravel(v).astype(jnp.float32)
                                    for v in tree_paths(tree).values()])

        def unflatten_like(flat, like):
            out, off = {}, 0
            for k, v in tree_paths(like).items():
                n = int(np.prod(v.shape))
                out[k] = flat[off:off + n].reshape(v.shape).astype(v.dtype)
                off += n
            # rebuild nested dict
            root = {}
            for path, leaf in out.items():
                d = root
                parts = path.split("/")
                for p in parts[:-1]:
                    d = d.setdefault(p, {})
                d[parts[-1]] = leaf
            return root

        # workers grouped into clusters
        n_clusters = max(args.workers // 2, 2)
        cluster_of = jnp.arange(args.workers, dtype=jnp.int32) % n_clusters
        tx_cfg = TxControlConfig(
            delta_threshold=getattr(args, "txctl_threshold", 0.5),
            slope_mode=getattr(args, "txctl_mode", "fairness"))
        step_impl = getattr(args, "step_impl", "auto")
        q_max = float(capacity)
        # netsim's active-cluster sliding window (virtual time)
        active_window = 1.0

        def ps_step(queue, params, opt_state, tx, aom, last_seen, key, med,
                    now, clusters, workers, times, rewards, payloads, losses,
                    active):
            """txctl_gate → olaf_step → weighted apply, all on the device.

            The §5 send gate runs first (per-burst-row Bernoulli from the
            worker's last piggybacked queue feedback); the surviving rows go
            through the single-launch fused cycle (``ops.olaf_step`` — the
            Pallas kernel or the fused XLA composition, inlined into this
            jit); the drained block's agg_count-weighted mean gradient is
            applied; finally the AoM sawtooth integral and the per-worker ACK
            feedback (multicast to the drained updates' clusters) are folded
            in.
            Nothing in here touches the host.
            """
            key, sub = jax.random.split(key)
            send, _ = jax_txctl_gate(tx, sub, now, tx_cfg.delta_threshold,
                                     tx_cfg.v, worker_ids=workers)
            if screen_on:
                # device ingress screen: non-finite rows and norm outliers vs
                # the running robust scale estimate are withheld before the
                # queue (deferred rows neither screen nor move the estimate)
                screen, med = jax_screen_mask(payloads, med,
                                              factor=screen_factor, mask=send)
                n_screen = (send & screen).sum()
            else:
                screen = None
                n_screen = jnp.int32(0)
            # each popped payload is the mean of agg_count raw gradients; the
            # applied gradient is their exact weighted mean
            queue, out = ops.olaf_step(queue, clusters, workers, times,
                                       rewards, payloads, jnp.inf, send, None,
                                       active, screen, k=drain_k,
                                       impl=step_impl)
            if stale_bound is not None:
                # hard staleness bound at the PS: drained rows whose update
                # age exceeds the bound are rejected before the apply
                fresh = jax_staleness_mask(now, out["gen_time"], stale_bound)
                valid = out["valid"] & fresh
                n_stale = (out["valid"] & ~fresh).sum()
                out = dict(out, valid=valid, n_valid=valid.sum())
            else:
                n_stale = jnp.int32(0)
            wts = out["valid"] * out["agg_count"].astype(jnp.float32)
            g_mean = jnp.einsum("k,kd->d", wts, out["payload"],
                                precision=jax.lax.Precision.HIGHEST) \
                / jnp.maximum(wts.sum(), 1.0)
            if screen_on:
                # robust fallback: when the screen flags more than
                # --robust-threshold of this burst, distrust the drained
                # block too and apply the winsorized combine instead of the
                # plain mean
                frac = n_screen.astype(jnp.float32) \
                    / jnp.maximum(send.sum().astype(jnp.float32), 1.0)
                g_flat = jnp.where(frac > robust_threshold,
                                   jax_trimmed_combine(out["payload"], wts),
                                   g_mean)
            else:
                g_flat = g_mean
            g = unflatten_like(g_flat, params)
            params, opt_state = apply_updates(params, g, opt_state, opt)
            # device AoM accumulator: drained rows delivered at virtual `now`
            aom = jax_aom_update_block(
                aom, jnp.full(out["valid"].shape, now, jnp.float32),
                out["gen_time"], out["valid"])
            # reverse-path feedback: N is the number of clusters active in
            # the sliding window (netsim's active_clusters — contending
            # flows, NOT occupancy, which is capped at Q_max and could never
            # congest); every worker in a drained update's cluster receives
            # {N, Q_max}
            last_seen = last_seen.at[clusters].max(
                jnp.where(send, times, -jnp.inf))
            n_active = ((now - last_seen) <= active_window).sum() \
                .astype(jnp.float32)
            acked = jnp.any((cluster_of[:, None] == out["cluster"][None, :])
                            & out["valid"][None, :], axis=1)
            tx = jax_txctl_ack(tx, acked, now, n_active, q_max)
            stats = dict(loss=jnp.mean(losses), applied=out["n_valid"],
                         combined=wts.sum(), agg_total=queue.n_agg,
                         deferred=(~send).sum(), stale=n_stale,
                         screened=n_screen,
                         occupancy=(queue.cluster >= 0).sum())
            return (queue, params, opt_state, tx, aom, last_seen, key, med,
                    stats)

        # donated buffers: the O(Q·D) queue payload, the params/opt trees and
        # the feedback states are updated in place instead of copied every
        # step
        ps_step = jax.jit(ps_step, donate_argnums=(0, 1, 2, 3, 4, 5, 6))

        def worker_grad(p, b):
            return api.loss_fn(p, b, cfg)

        # a named function, so its executable is ``jit_worker_grad``
        grad_fn = jax.jit(jax.value_and_grad(worker_grad))
        rng = np.random.default_rng(args.seed)
        worker_speed = 1.0 + 0.5 * rng.random(args.workers)
        worker_next = np.zeros(args.workers)
        worker_step = np.zeros(args.workers, int)
        burst_size = max(1, args.burst_size)
        # the membership mask is materialized only under churn so
        # fault-free runs keep the legacy 4-leaf txctl pytree
        # (bitwise-identical traces)
        tx = jax_txctl_init(args.workers, track_active=churn)
        active_np = np.ones(args.workers, bool)
        aom = jax_aom_init()
        last_seen = jnp.full((n_clusters,), -jnp.inf, jnp.float32)
        med = jnp.zeros((), jnp.float32)  # screen's running scale estimate
        step_key = jax.random.key(args.seed + 101)

        def snapshot_aux():
            # the whole async training plane: device queue/txctl/AoM/
            # feedback state, the PRNG key, and the float64 host scheduling
            # counters (restored exactly -> resume is bitwise)
            return dict(queue=queue, tx=tx, aom=aom, last_seen=last_seen,
                        med=med, key=jax.random.key_data(step_key),
                        worker_next=worker_next, worker_step=worker_step,
                        active=active_np)

        start_it = 0
        if args.ckpt and getattr(args, "resume", False) \
                and latest_step(args.ckpt) is not None:
            start_it, params, opt_state, aux = restore_checkpoint(
                args.ckpt, params_like=jax.eval_shape(lambda: params),
                opt_like=jax.eval_shape(lambda: opt_state),
                aux_like=snapshot_aux())
            queue, tx, aom = aux["queue"], aux["tx"], aux["aom"]
            last_seen, med = aux["last_seen"], aux["med"]
            step_key = jax.random.wrap_key_data(aux["key"])
            worker_next = aux["worker_next"]
            worker_step = aux["worker_step"]
            active_np = aux["active"]
            print(f"resumed olaf-async from step {start_it}")

        pending = []  # device-side per-step stats, drained in batches
        # host-side (step, loss, combined, applied) after each flush
        log_rows = []
        deferred_total = [0]  # txctl-gated (deferred) burst rows
        stale_total = [0]  # PS-rejected rows past the staleness bound
        # ingress-screened (integrity-rejected) burst rows
        screened_total = [0]
        # logging disabled -> one flush at the end, never a mid-loop sync
        flush_every = args.log_every if args.log_every > 0 \
            else max(args.steps, 1)

        def flush():
            # one host sync for the whole batch of buffered scalars
            with jax.profiler.TraceAnnotation("olaf/flush"):
                rows = jax.device_get(pending)
            for row in rows:
                step = len(log_rows) + 1
                log_rows.append((step, float(row["loss"]),
                                 int(row["combined"]), int(row["applied"])))
                deferred_total[0] += int(row["deferred"])
                stale_total[0] += int(row["stale"])
                screened_total[0] += int(row["screened"])
            del pending[:]

    for it in range(start_it, args.steps):
        with jax.profiler.StepTraceAnnotation("olaf/step", step_num=it):
            if churn and it == crash_at:
                # crashed workers stop scheduling (inf next-finish time keeps
                # them out of the argmin) and their queued updates expire
                worker_next[crash_set] = np.inf
                active_np[crash_set] = False
                tx = jax_txctl_set_active(tx, jnp.asarray(active_np))
                if args.log_every:
                    print(f"crash at {it}: workers {crash_set} down")
            if churn and restart_at >= 0 and it == restart_at:
                # elastic rejoin: fresh controller state, next finish one
                # compute interval past the surviving frontier
                frontier = worker_next[np.isfinite(worker_next)].max()
                for w in crash_set:
                    worker_next[w] = frontier + worker_speed[w]
                active_np[crash_set] = True
                tx = jax_txctl_set_active(tx, jnp.asarray(active_np))
                if args.log_every:
                    print(f"restart at {it}: workers {crash_set} rejoin")
            # congested PS: a burst of updates arrives between drains, so
            # same-cluster updates meet in the queue and combine (the paper's
            # opportunistic window) — pushed through the fused burst fast
            # path.
            burst = dict(c=[], w=[], t=[], r=[], p=[])
            burst_losses = []
            for _ in range(burst_size):
                w = int(np.argmin(worker_next))  # next to finish (async)
                with jax.profiler.TraceAnnotation("olaf/batch", worker=w):
                    batch = {k: jnp.asarray(v) for k, v in
                             shards[w].batch(worker_step[w]).items()}
                with jax.profiler.TraceAnnotation("olaf/grad", worker=w):
                    loss, grads = grad_fn(params, batch)
                burst["c"].append(w % n_clusters)
                burst["w"].append(w)
                burst["t"].append(worker_next[w])
                burst["r"].append(-loss)
                with jax.profiler.TraceAnnotation("olaf/pack", worker=w):
                    burst["p"].append(flatten(grads))
                burst_losses.append(loss)
                worker_step[w] += 1
                worker_next[w] += worker_speed[w]
            with jax.profiler.TraceAnnotation("olaf/ps_step"):
                (queue, params, opt_state, tx, aom, last_seen, step_key, med,
                 stats) = ps_step(
                    queue, params, opt_state, tx, aom, last_seen, step_key,
                    med, jnp.float32(max(burst["t"])),
                    jnp.asarray(burst["c"], jnp.int32),
                    jnp.asarray(burst["w"], jnp.int32),
                    jnp.asarray(burst["t"], jnp.float32),
                    jnp.stack(burst["r"]).astype(jnp.float32),
                    jnp.stack(burst["p"]), jnp.stack(burst_losses),
                    jnp.asarray(active_np) if churn else None)
            pending.append(stats)
            if len(pending) >= flush_every:
                flush()
                if args.log_every:
                    step, loss_v, combined, _ = log_rows[-1]
                    print(f"applied {step}: loss {loss_v:.4f} "
                          f"(combined {combined} updates)")
            if args.ckpt and args.ckpt_every \
                    and (it + 1) % args.ckpt_every == 0:
                with jax.profiler.TraceAnnotation("olaf/ckpt"):
                    save_checkpoint(args.ckpt, it + 1, params, opt_state,
                                    aux=snapshot_aux())
    with jax.profiler.TraceAnnotation("olaf/finish"):
        flush()
        if args.ckpt:
            with jax.profiler.TraceAnnotation("olaf/ckpt"):
                save_checkpoint(args.ckpt, args.steps, params, opt_state,
                                aux=snapshot_aux())
        horizon = float(worker_next[np.isfinite(worker_next)].max())
        meta = {f: np.asarray(getattr(queue, f)) for f in (
            "cluster", "worker", "seq", "agg_count", "replaceable",
            "next_seq", "n_dropped", "n_agg", "n_repl", "n_screened")}
        res = OlafAsyncResult(
            losses=[l for _, l, _, _ in log_rows],
            applied=[a for _, _, _, a in log_rows],
            combined=[c for _, _, c, _ in log_rows], queue=meta,
            deferred=deferred_total[0], stale=stale_total[0],
            screened=screened_total[0],
            avg_aom=float(jax_aom_average(aom, horizon)))
    if res.losses:
        print(f"final loss {res.losses[-1]:.4f} "
              f"(first {res.losses[0]:.4f}); "
              f"queue aggregations {int(meta['n_agg'])}; "
              f"txctl deferred {res.deferred}; "
              f"stale rejected {res.stale}; "
              f"screened {res.screened}; "
              f"avg AoM {res.avg_aom:.3f} (virtual)")
    return res

def run_scenario(args):
    """Replay a network topology scenario through the multi-switch hybrid
    data plane with the selected simulator backend (``--sim-impl``).

    ``event`` replays the metadata trace one event at a time, ``window``
    batches it per transmission window, and ``vectorized`` retires the
    host loop entirely: the whole scenario advances as one jitted
    ``lax.scan`` on device (``repro.core.vecsim``) with a single staged
    payload upload.
    """
    from repro.core.hybrid import run_hybrid_multihop
    from repro.core.topology import fattree_cfg, multirack_cfg

    if args.topology == "fattree":
        sim_cfg = fattree_cfg(args.fattree_k, seed=args.seed,
                              spec_kw=dict(spines=args.fattree_spines))
    elif args.topology == "multirack":
        sim_cfg = multirack_cfg(seed=args.seed)
    else:
        sim_cfg = None  # §8.3 SW1/SW2/SW3 multihop default
    sim_dt = args.sim_dt
    if sim_dt not in (None, "auto"):
        sim_dt = float(sim_dt)
    sim_mesh = None
    if args.sim_shards > 1 or args.sim_worker_shards > 1:
        from repro.distributed.sharding import vecsim_mesh
        need = args.sim_shards * args.sim_worker_shards
        if need > len(jax.devices()):
            raise SystemExit(
                f"--sim-shards {args.sim_shards} x --sim-worker-shards "
                f"{args.sim_worker_shards} needs {need} devices, only "
                f"{len(jax.devices())} present")
        n_sw = len(sim_cfg.switches) if sim_cfg is not None else 3
        sim_mesh = vecsim_mesh(min(n_sw, args.sim_shards),
                               worker_shards=args.sim_worker_shards)
    t0 = time.time()
    hyb, cfg = run_hybrid_multihop(args.sim_dim, seed=args.seed,
                                   sim_cfg=sim_cfg,
                                   sim_impl=args.sim_impl,
                                   sim_dt=sim_dt, sim_mesh=sim_mesh)
    wall = time.time() - t0
    enq = sum(qs["enqueued"] for qs in hyb.queue_stats.values())
    agg = sum(qs["aggregations"] for qs in hyb.queue_stats.values())
    drp = sum(qs["dropped"] for qs in hyb.queue_stats.values())
    impl = args.sim_impl or "window"
    print(f"scenario {args.topology} [{impl}]: "
          f"{len(hyb.delivered)} delivered, {hyb.forwarded} forwarded, "
          f"{enq} enqueued / {agg} aggregated / {drp} dropped; "
          f"{hyb.launches} combine launches, "
          f"{hyb.h2d_transfers} h2d transfers; {wall:.2f}s wall")
    return hyb


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="model config name (required outside --mode "
                         "scenario)")
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (CPU-runnable)")
    ap.add_argument("--mode", default="sync",
                    choices=["sync", "olaf-async", "scenario"])
    ap.add_argument("--sim-impl", default=None,
                    choices=["event", "window", "vectorized"],
                    help="network simulator backend for --mode scenario: "
                         "per-event replay, per-window batched replay, or "
                         "the device-resident vectorized scan "
                         "(repro.core.vecsim)")
    ap.add_argument("--topology", default="multihop",
                    choices=["multihop", "fattree", "multirack"],
                    help="scenario topology preset (--mode scenario)")
    ap.add_argument("--fattree-k", type=int, default=2,
                    help="fat-tree arity for --topology fattree")
    ap.add_argument("--fattree-spines", type=int, default=1,
                    help="core switches for --topology fattree "
                         "(k=8 --fattree-spines 8 is the 80-switch pod)")
    ap.add_argument("--sim-dt", default=None,
                    help="uniform step for --sim-impl vectorized: a float "
                         "or 'auto' (largest dt within the AoM tolerance, "
                         "bisected against the exact grid on a prefix); "
                         "skips the host oracle trace entirely")
    ap.add_argument("--sim-shards", type=int, default=1,
                    help="shard the vectorized scan's switch axis over "
                         "this many devices (repro.distributed.sharding"
                         ".vecsim_mesh)")
    ap.add_argument("--sim-worker-shards", type=int, default=1,
                    help="shard the worker/cluster axis over this many "
                         "devices (multiplies --sim-shards)")
    ap.add_argument("--sim-dim", type=int, default=64,
                    help="payload row width for --mode scenario")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--burst-size", type=int, default=2,
                    help="updates arriving per PS drain (olaf-async)")
    ap.add_argument("--drain-k", type=int, default=4,
                    help="queue slots drained per jitted PS step (olaf-async)")
    ap.add_argument("--queue-slots", type=int, default=0,
                    help="device OlafQueue capacity Q_max (0: max(workers, "
                         "4)); below the cluster count arms the txctl "
                         "congestion gate")
    ap.add_argument("--step-impl", default="auto",
                    choices=["auto", "xla", "pallas"],
                    help="fused olaf_step cycle: Pallas kernel or XLA "
                         "composition (auto: kernel when compiled)")
    ap.add_argument("--txctl-threshold", type=float, default=0.5,
                    help="Δ̄_T for the device txctl gate (virtual time)")
    ap.add_argument("--txctl-mode", default="fairness",
                    choices=["fairness", "urgency"],
                    help="txctl staleness slope: v=Δ̄_T or v=1/Δ̄_T")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true",
                    help="resume from the latest checkpoint in --ckpt; in "
                         "olaf-async the full training plane (queue, txctl, "
                         "AoM, PRNG key, host counters) restores bitwise")
    ap.add_argument("--crash-workers", default="",
                    help="comma-separated worker ids crashed at --crash-at "
                         "(olaf-async node churn)")
    ap.add_argument("--crash-at", type=int, default=-1,
                    help="PS step at which --crash-workers go down")
    ap.add_argument("--restart-at", type=int, default=-1,
                    help="PS step at which crashed workers rejoin as fresh "
                         "members (elastic membership)")
    ap.add_argument("--staleness-bound", type=float, default=0.0,
                    help="hard PS admission bound on update age in virtual "
                         "time (0: disabled)")
    ap.add_argument("--ingress-screen", action="store_true",
                    help="device ingress integrity screen: withhold "
                         "non-finite / norm-outlier burst rows before the "
                         "queue (olaf-async)")
    ap.add_argument("--screen-factor", type=float, default=16.0,
                    help="screen rejects rows with L2 norm above factor x "
                         "the running robust scale estimate")
    ap.add_argument("--robust-threshold", type=float, default=0.25,
                    help="screened burst fraction above which the PS "
                         "applies the winsorized (trimmed) combine instead "
                         "of the plain weighted mean")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    enable_compile_cache()
    if args.mode == "scenario":
        run_scenario(args)
        return
    if args.arch is None:
        ap.error("--arch is required unless --mode scenario")
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if cfg.family in ("vlm", "encdec"):
        raise SystemExit("use the family-specific example drivers for "
                         "stub-frontend archs")
    if args.mode == "sync":
        run_sync(cfg, args)
    else:
        run_olaf_async(cfg, args)


if __name__ == "__main__":
    main()
