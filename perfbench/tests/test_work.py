"""Work counts against hand counts, and the peaks table."""
import pytest

from perfbench import harness, work

SMOLLM4 = harness.config("smollm-360m-async")
TINY = dict(SMOLLM4, hidden_size=8, intermediate_size=16,
            num_attention_heads=2, num_key_value_heads=1,
            num_hidden_layers=2, vocab_size=32)


def test_param_count_tiny_by_hand():
    # per layer: norms 2*8, q 8*2*4, k and v 2*8*1*4, o 2*4*8, mlp 3*8*16
    per_layer = 16 + 64 + 64 + 64 + 384
    assert work.lm_param_count(TINY) == 32 * 8 + 8 + 2 * per_layer


def test_param_count_smollm_four_layers():
    assert work.lm_param_count(SMOLLM4) == 86_516_160


def test_param_count_matches_the_program():
    import dataclasses

    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.models import api
    cfg = dataclasses.replace(
        get_config("smollm-360m"), n_layers=2, d_model=8, d_ff=16,
        n_heads=2, n_kv_heads=1, vocab=32)
    shapes = jax.eval_shape(lambda: api.init_model(jax.random.key(0), cfg))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert n == work.lm_param_count(TINY)


def test_flops_per_token_by_hand():
    # 6 per parameter, plus causal attention 6 * layers * heads * dh * seq
    seq = 10
    assert work.lm_train_flops_per_token(TINY, seq) == \
        6 * work.lm_param_count(TINY) + 6 * 2 * 2 * 4 * seq
    # smollm, 4 layers, seq 2048: 8,192 tokens a worker step
    per_step = 8192 * work.lm_train_flops_per_token(SMOLLM4, 2048)
    assert per_step == pytest.approx(
        8192 * (6 * 86_516_160 + 6 * 4 * 15 * 64 * 2048))


def test_ps_step_bytes_by_hand():
    D = 86_516_160
    # queue 2*4 rows, burst 4 + drained 2 rows, grad 1 row (f32);
    # bf16 params read+written, two f32 moments read+written
    want = (8 + 6 + 1) * D * 4 + 2 * D * 2 + 4 * D * 4
    assert work.ps_step_bytes(D, 4, 4, 2, 2) == want
    assert work.ps_step_bytes(3, 1, 1, 1, 4) == (2 + 2 + 1) * 12 + 24 + 48


def test_peaks_of_v5e_and_unknown_kind():
    p = work.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        work.peaks("TPU v99")
