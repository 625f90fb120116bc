"""Pallas TPU kernels (pl.pallas_call + BlockSpec VMEM tiling).

  olaf_combine     — the paper's data-plane burst combine (masked segment
                     running-mean into cluster slots as a one-hot MXU
                     matmul; per-update integer aggregation weights; fused
                     slot counts; optional multi-queue axis)
  olaf_enqueue     — fused burst enqueue: Algorithm 1 gating as an
                     in-kernel resolve over SMEM per-update scalars
                     plus the telescoped-mean payload pass on the VPU,
                     one launch per burst (oracle:
                     olaf_queue.jax_enqueue_burst)
  olaf_step        — the fused full-cycle data plane: burst resolve (with
                     a per-update transmission-control send gate), drain-k
                     oldest-valid selection, payload combine + drained-row
                     gather on one (S × D-tile × Q-tile) grid, its D-tile
                     sized from the shapes — one launch per PS step;
                     leading S axis batches switches (oracle:
                     olaf_queue.jax_olaf_step)
  flash_attention  — online-softmax attention, (BH, q_blocks, kv_blocks)
                     grid with VMEM scratch accumulators
  decode_attention — single-token GQA attention streaming a (possibly
                     sequence-sharded) KV cache

ops.py exposes jit'd wrappers (compiled on a TPU backend, interpreted on
any other); ref.py holds the pure-jnp oracles the test sweep asserts
against.
"""
