"""The reference's inputs follow the program's recipes exactly: the same
seed gives the same weights and tokens."""
import dataclasses

import jax
import numpy as np

from perfbench import harness
from perfbench.drivers.lm_async import tree_paths
from perfbench.reference import lm_async as lref


def test_lm_weights_and_tokens_follow_the_program():
    from repro.configs import get_config
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.models import api
    m = harness.config("smollm-360m-async")
    m.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
             num_key_value_heads=2, num_hidden_layers=3, vocab_size=256)
    cfg = dataclasses.replace(get_config("smollm-360m"), n_layers=3,
                              d_model=64, d_ff=128, n_heads=4, n_kv_heads=2,
                              vocab=256)
    seed = 2 ** 31 + 77
    prog = tree_paths(api.init_model(jax.random.key(seed), cfg))
    mine = lref.init_params(seed, m)
    assert sorted(prog) == sorted(mine)
    for k in prog:
        assert prog[k].dtype == mine[k].dtype, k
        np.testing.assert_array_equal(np.asarray(prog[k], np.float32),
                                      np.asarray(mine[k], np.float32), k)
    data = SyntheticLM(DataConfig(vocab=256, seq_len=16, global_batch=8,
                                  n_shards=4, shard_id=3, seed=seed))
    b = data.batch(5)
    toks, labs = lref.token_batch(seed, 5, 4, 3, 2, 16, 256)
    np.testing.assert_array_equal(b["tokens"], toks)
    np.testing.assert_array_equal(b["labels"], labs)


def test_lm_reference_loss_matches_the_program_in_float32():
    from repro.configs import get_config
    from repro.models import api
    m = harness.config("smollm-360m-async")
    m.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
             num_key_value_heads=2, num_hidden_layers=2, vocab_size=256,
             torch_dtype="float32")
    cfg = dataclasses.replace(get_config("smollm-360m"), n_layers=2,
                              d_model=64, d_ff=128, n_heads=4, n_kv_heads=2,
                              vocab=256, dtype="float32")
    params = api.init_model(jax.random.key(3), cfg)
    toks, labs = lref.token_batch(3, 0, 1, 0, 2, 16, 256)
    with jax.default_matmul_precision("highest"):
        want = float(api.loss_fn(params, dict(tokens=toks, labels=labs),
                                 cfg))
    got = float(lref.loss(lref.init_params(3, m), toks, labs, m))
    np.testing.assert_allclose(got, want, rtol=1e-5)
