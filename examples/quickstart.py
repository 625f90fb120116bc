"""Quickstart: the OLAF core in 60 seconds.

1. Opportunistic aggregation in the OlafQueue (Algorithm 1);
2. the Age-of-Model metric on a FIFO-vs-Olaf microbenchmark;
3. the Z3 verifier accepting an AoM-fairness objective;
4. the Pallas olaf_combine kernel vs its jnp oracle.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core import PyOlafQueue, Update
from repro.core.netsim import NetworkSimulator, microbench_cfg
from repro.core.verifier import VerifierConfig, uniform_schedule, verify_aom_fairness


def demo_queue():
    print("== OlafQueue: opportunistic aggregation ==")
    q = PyOlafQueue(capacity=4)
    q.enqueue(Update(cluster_id=0, worker_id=0, gen_time=0.0, reward=1.0,
                     payload=np.array([1.0, 1.0])))
    q.enqueue(Update(cluster_id=0, worker_id=1, gen_time=0.1, reward=1.1,
                     payload=np.array([3.0, 3.0])))  # same cluster -> merge
    q.enqueue(Update(cluster_id=1, worker_id=9, gen_time=0.2, reward=0.5,
                     payload=np.array([7.0, 7.0])))
    out = q.dequeue()
    print(f"  first departure: cluster {out.cluster_id}, "
          f"payload {out.payload} (mean of 2 updates), "
          f"agg_count={out.agg_count}")
    assert np.allclose(out.payload, [2.0, 2.0])


def demo_aom():
    print("== FIFO vs Olaf under congestion (microbench, 20 Gbps out) ==")
    for queue in ("fifo", "olaf"):
        res = NetworkSimulator(microbench_cfg(queue, 20.0, n_updates=300)).run()
        print(f"  {queue:>4}: loss {res.loss_pct:5.1f}%  "
              f"avg AoM {res.avg_aom()*1e6:7.2f} us  "
              f"delivered {res.received_at_ps}")


def demo_verifier():
    print("== Z3 AoM-fairness verification (paper Sec. 6) ==")
    try:
        import z3  # noqa: F401
    except ImportError:
        print("  (skipped: z3-solver not installed — "
              "pip install -r requirements-dev.txt)")
        return
    res = verify_aom_fairness(
        [uniform_schedule(0.1, 6), uniform_schedule(0.1, 6)],
        VerifierConfig(p_over_c=0.002, epsilon=0.25))
    print(f"  two 100ms clusters, eps=0.25: {res.status} "
          f"in {res.solve_time_s:.2f}s")


def demo_kernel():
    import jax
    import jax.numpy as jnp
    print(f"== Pallas olaf_combine kernel ({jax.default_backend()}: compiled "
          f"on tpu, interpreted elsewhere) ==")
    from repro.kernels import ops, ref
    slots = jnp.zeros((4, 256))
    counts = jnp.zeros((4,), jnp.int32)
    upd = jnp.ones((8, 256))
    clusters = jnp.arange(8, dtype=jnp.int32) % 4
    gate = jnp.ones((8,), jnp.int32)
    got, cnt = ops.olaf_combine(slots, counts, upd, clusters, gate, tile_d=128)
    want, want_cnt = ref.olaf_combine_ref(slots, counts, upd, clusters, gate)
    print(f"  kernel == oracle: {bool(jnp.allclose(got, want))} and "
          f"{bool(jnp.array_equal(cnt, want_cnt))}; "
          f"slot counts {np.asarray(cnt).tolist()}")


if __name__ == "__main__":
    demo_queue()
    demo_aom()
    demo_verifier()
    demo_kernel()
    print("quickstart OK")
