"""Per-kernel allclose sweeps: Pallas (interpreted off a TPU) vs the pure-jnp
oracles in ``repro.kernels.ref``, over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

def rand(rng, shape, dtype):
    x = rng.normal(size=shape).astype(np.float32)
    return jnp.asarray(x, dtype)


class TestOlafCombine:
    @pytest.mark.parametrize("Q,U,D", [(4, 3, 128), (8, 16, 512), (2, 1, 1024),
                                       (16, 32, 256), (8, 256, 256),
                                       (32, 257, 128), (3, 5, 200)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, Q, U, D, dtype):
        rng = np.random.default_rng(Q * 101 + U)
        slots = rand(rng, (Q, D), dtype)
        counts = jnp.asarray(rng.integers(0, 5, (Q,)), jnp.int32)
        updates = rand(rng, (U, D), dtype)
        clusters = jnp.asarray(rng.integers(0, Q, (U,)), jnp.int32)
        gate = jnp.asarray(rng.integers(0, 2, (U,)), jnp.int32)
        got, got_counts = ops.olaf_combine(slots, counts, updates, clusters,
                                           gate, tile_d=min(128, D))
        want, want_counts = ref.olaf_combine_ref(slots, counts, updates,
                                                 clusters, gate)
        tol = 1e-5 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)
        # counts come fused from the same kernel launch
        np.testing.assert_array_equal(np.asarray(got_counts),
                                      np.asarray(want_counts))
        onehot = np.zeros((U, Q), np.int32)
        for u in range(U):
            onehot[u, int(clusters[u])] = int(gate[u])
        np.testing.assert_array_equal(np.asarray(got_counts),
                                      np.asarray(counts) + onehot.sum(0))

    @pytest.mark.parametrize("S,Q,U,D", [(3, 8, 16, 256), (2, 5, 7, 128)])
    def test_multi_queue_axis(self, S, Q, U, D):
        """A leading switch axis batches independent queues in one launch."""
        rng = np.random.default_rng(S * 7 + Q)
        slots = rand(rng, (S, Q, D), jnp.float32)
        counts = jnp.asarray(rng.integers(0, 5, (S, Q)), jnp.int32)
        updates = rand(rng, (S, U, D), jnp.float32)
        clusters = jnp.asarray(rng.integers(0, Q, (S, U)), jnp.int32)
        gate = jnp.asarray(rng.integers(0, 2, (S, U)), jnp.int32)
        got, got_counts = ops.olaf_combine_multi(slots, counts, updates,
                                                 clusters, gate,
                                                 tile_d=min(128, D))
        for s in range(S):
            want, want_counts = ref.olaf_combine_ref(
                slots[s], counts[s], updates[s], clusters[s], gate[s])
            np.testing.assert_allclose(np.asarray(got[s]), np.asarray(want),
                                       rtol=1e-5, atol=1e-5)
            np.testing.assert_array_equal(np.asarray(got_counts[s]),
                                          np.asarray(want_counts))

    def test_vmap_multi_queue(self):
        """jax.vmap over the combine maps onto the multi-queue grid axis."""
        rng = np.random.default_rng(11)
        S, Q, U, D = 3, 4, 6, 128
        slots = rand(rng, (S, Q, D), jnp.float32)
        counts = jnp.asarray(rng.integers(0, 3, (S, Q)), jnp.int32)
        updates = rand(rng, (S, U, D), jnp.float32)
        clusters = jnp.asarray(rng.integers(0, Q, (S, U)), jnp.int32)
        gate = jnp.ones((S, U), jnp.int32)
        got, got_counts = jax.vmap(
            lambda sl, ct, up, cl, ga: ops.olaf_combine(sl, ct, up, cl, ga,
                                                        tile_d=128)
        )(slots, counts, updates, clusters, gate)
        want, want_counts = ref.olaf_combine_ref(slots, counts, updates,
                                                 clusters, gate)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(got_counts),
                                      np.asarray(want_counts))

    def test_empty_slot_mean(self):
        # combining into an empty slot (count 0) must give the plain mean
        slots = jnp.zeros((2, 128))
        counts = jnp.zeros((2,), jnp.int32)
        updates = jnp.stack([jnp.full((128,), 2.0), jnp.full((128,), 4.0)])
        clusters = jnp.array([0, 0], jnp.int32)
        gate = jnp.array([1, 1], jnp.int32)
        got, cnt = ops.olaf_combine(slots, counts, updates, clusters, gate,
                                    tile_d=128)
        np.testing.assert_allclose(np.asarray(got[0]), 3.0, rtol=1e-6)
        assert int(cnt[0]) == 2 and int(cnt[1]) == 0

    def test_weighted_gate(self):
        """gate > 1 contributes with that aggregation weight: combining a
        pre-combined packet (the mean of w raws) stays an exact weighted
        mean of the raw updates — the multi-hop SW1/SW2 -> SW3 case."""
        rng = np.random.default_rng(21)
        Q, U, D = 4, 6, 128
        slots = rand(rng, (Q, D), jnp.float32)
        counts = jnp.asarray(rng.integers(0, 4, (Q,)), jnp.int32)
        updates = rand(rng, (U, D), jnp.float32)
        clusters = jnp.asarray(rng.integers(0, Q, (U,)), jnp.int32)
        gate = jnp.asarray(rng.integers(0, 5, (U,)), jnp.int32)  # weights
        got, got_counts = ops.olaf_combine(slots, counts, updates, clusters,
                                           gate, tile_d=128)
        want, want_counts = ref.olaf_combine_ref(slots, counts, updates,
                                                 clusters, gate)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(got_counts),
                                      np.asarray(want_counts))
        # hand-check one slot: new = (slot*n + sum w_u upd_u) / (n + sum w_u)
        q = int(clusters[0])
        sel = np.asarray(clusters) == q
        w = np.asarray(gate, np.float64)[sel]
        if w.sum() > 0:
            n = float(counts[q])
            manual = ((np.asarray(slots[q], np.float64) * n
                       + (w[:, None] * np.asarray(updates, np.float64)[sel])
                       .sum(0)) / (n + w.sum()))
            np.testing.assert_allclose(np.asarray(got[q]), manual,
                                       rtol=1e-5, atol=1e-5)

    def test_matches_jax_queue_aggregation(self):
        """Kernel burst-combine == sequential JaxQueue aggregation."""
        from repro.core.olaf_queue import jax_enqueue, jax_queue_init
        rng = np.random.default_rng(7)
        Q, U, D = 4, 6, 128
        updates = rand(rng, (U, D), jnp.float32)
        clusters = jnp.asarray(rng.integers(0, Q, (U,)), jnp.int32)
        state = jax_queue_init(Q, D)
        for u in range(U):
            # distinct workers -> pure aggregation path
            state = jax_enqueue(state, clusters[u], jnp.int32(100 + u),
                                jnp.float32(u), jnp.float32(0.0), updates[u])
        slots0 = jnp.zeros((Q, D))
        counts0 = jnp.zeros((Q,), jnp.int32)
        got, _ = ops.olaf_combine(slots0, counts0, updates, clusters,
                                  jnp.ones((U,), jnp.int32), tile_d=128)
        # map queue slots to cluster ids
        for slot in range(Q):
            c = int(state.cluster[slot])
            if c < 0:
                continue
            np.testing.assert_allclose(np.asarray(got[c]),
                                       np.asarray(state.payload[slot]),
                                       rtol=1e-5, atol=1e-6)


class TestFlashAttention:
    @pytest.mark.parametrize("S,Dh,blk", [(128, 64, 64), (256, 128, 128),
                                          (512, 64, 256)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_ref(self, S, Dh, blk, dtype, causal):
        rng = np.random.default_rng(S + Dh)
        BH = 3
        q = rand(rng, (BH, S, Dh), dtype)
        k = rand(rng, (BH, S, Dh), dtype)
        v = rand(rng, (BH, S, Dh), dtype)
        from repro.kernels.flash_attention import flash_attention_pallas
        got = flash_attention_pallas(q, k, v, causal=causal, block_q=blk,
                                     block_k=blk, interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=causal)
        tol = 2e-5 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)

    def test_local_window(self):
        rng = np.random.default_rng(0)
        q = rand(rng, (2, 256, 64), jnp.float32)
        k = rand(rng, (2, 256, 64), jnp.float32)
        v = rand(rng, (2, 256, 64), jnp.float32)
        from repro.kernels.flash_attention import flash_attention_pallas
        got = flash_attention_pallas(q, k, v, causal=True, window=64,
                                     block_q=64, block_k=64, interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=True, window=64)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_model_layout_wrapper(self):
        rng = np.random.default_rng(1)
        B, S, H, Dh = 2, 128, 4, 64
        q = rand(rng, (B, S, H, Dh), jnp.float32)
        k = rand(rng, (B, S, H, Dh), jnp.float32)
        v = rand(rng, (B, S, H, Dh), jnp.float32)
        from repro.models.layers import full_attention
        got = ops.flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
        want = full_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


class TestDecodeAttention:
    @pytest.mark.parametrize("S,KV,rep,Dh,blk", [
        (256, 2, 3, 64, 64), (512, 1, 8, 128, 256), (128, 4, 1, 64, 128)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, S, KV, rep, Dh, blk, dtype):
        rng = np.random.default_rng(S + KV)
        B = 3
        q = rand(rng, (B, KV, rep, Dh), dtype)
        kc = rand(rng, (B, S, KV, Dh), dtype)
        vc = rand(rng, (B, S, KV, Dh), dtype)
        pos = jnp.asarray(rng.integers(1, S, (B,)), jnp.int32)
        got = ops.decode_attention(q, kc, vc, pos, block_s=blk)
        want = ref.decode_attention_ref(q, kc, vc, pos)
        tol = 2e-5 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)

    def test_partial_cache(self):
        """Only positions <= pos contribute (fresh cache slots are junk)."""
        rng = np.random.default_rng(3)
        B, S, KV, rep, Dh = 2, 128, 2, 2, 64
        q = rand(rng, (B, KV, rep, Dh), jnp.float32)
        kc = rand(rng, (B, S, KV, Dh), jnp.float32)
        vc = rand(rng, (B, S, KV, Dh), jnp.float32)
        pos = jnp.array([5, 60], jnp.int32)
        got = ops.decode_attention(q, kc, vc, pos, block_s=64)
        # poison the masked region; result must not change
        kc2 = kc.at[:, 100:].set(1e4)
        vc2 = vc.at[:, 100:].set(-1e4)
        got2 = ops.decode_attention(q, kc2, vc2, pos, block_s=64)
        np.testing.assert_allclose(np.asarray(got), np.asarray(got2))
