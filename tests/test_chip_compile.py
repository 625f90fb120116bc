"""Compile the data-plane kernels for a described TPU v5e, at the widths
the main path runs them.

Nothing here runs a kernel: each test lowers a kernel (or the jitted entry
that calls it) for one chip of a ``v5e:2x2`` topology that is described,
not attached, and asks the TPU compiler to compile it. That catches what
the Pallas interpreter accepts and Mosaic refuses — unaligned tiles,
vector loads from SMEM, too much fast memory — without a chip. The
widths are the main path's:

* the paper's update width: the flattened PPO actor-critic for the
  CartPole environment, exactly as ``run_hybrid_ppo`` sizes it — the
  hybrid window combine and forward (``S = 3`` switches of the §8.3
  fan-in) and the multi-queue ``olaf_step``;
* the LM gradient: ``smollm-360m`` at its published widths, flattened
  whole, through ``ops.olaf_step``'s single-queue form.

The topology is described inside a fixture, so only the test worker that
runs this file loads the TPU compiler, and the tests skip where it cannot
be described.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.olaf_combine import olaf_combine_pallas
from repro.kernels.olaf_step import olaf_step_pallas

S, Q, U = 3, 8, 16  # §8.3 fan-in: SW1/SW2/SW3, Qmax = 8, one window
LM_LAYERS, LM_Q, LM_U, LM_K = 4, 4, 4, 2  # LM phase: depth cut and queue


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler can be loaded here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def ppo_dim():
    from repro.configs.olaf_ppo import PPOConfig
    from repro.models.rlnets import init_actor_critic
    from repro.rl.async_trainer import flatten_params
    from repro.rl.env import make_env
    env = make_env("cartpole")
    cfg = dataclasses.replace(PPOConfig(), obs_dim=env.obs_dim,
                              n_actions=env.n_actions)
    flat, _ = flatten_params(init_actor_critic(jax.random.key(0), cfg))
    return int(np.asarray(flat).size)


@pytest.fixture(scope="module")
def lm_dim():
    from repro.configs import get_config
    from repro.models import api
    cfg = dataclasses.replace(get_config("smollm-360m"), n_layers=LM_LAYERS)
    shapes = jax.eval_shape(lambda: api.init_model(jax.random.key(0), cfg))
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))


@pytest.fixture
def on_tpu(monkeypatch):
    """The ``ops`` wrappers read the backend at trace time; here the CPU
    is the backend, so make them trace their TPU branch."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _check(lowered):
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    print(compiled.memory_analysis())
    return compiled


def test_olaf_combine_multi_queue(one_chip, ppo_dim):
    f32, i32 = jnp.float32, jnp.int32
    fn = jax.jit(lambda sl, ct, up, cl, ga: olaf_combine_pallas(
        sl, ct, up, cl, ga, interpret=False))
    _check(fn.lower(_spec(one_chip, (S, Q, ppo_dim), f32),
                    _spec(one_chip, (S, Q), i32),
                    _spec(one_chip, (S, U, ppo_dim), f32),
                    _spec(one_chip, (S, U), i32),
                    _spec(one_chip, (S, U), i32)))


def test_olaf_forward_window(one_chip, ppo_dim, on_tpu):
    f32, i32 = jnp.float32, jnp.int32
    _check(ops.olaf_forward.lower(
        _spec(one_chip, (S, Q, ppo_dim), f32), _spec(one_chip, (S, Q), i32),
        _spec(one_chip, (S, U, ppo_dim), f32), _spec(one_chip, (S, U), i32),
        _spec(one_chip, (S, U), i32), _spec(one_chip, (S, Q), jnp.bool_),
        _spec(one_chip, (1,), i32), _spec(one_chip, (1,), i32),
        _spec(one_chip, (1,), i32)))


def _queue_specs(sharding, lead, q, d):
    f32, i32 = jnp.float32, jnp.int32
    vec = lead + (q,)
    return ([_spec(sharding, vec, i32)] * 3
            + [_spec(sharding, vec, f32)] * 2
            + [_spec(sharding, vec, i32), _spec(sharding, vec, jnp.bool_)]
            + [_spec(sharding, lead, i32)] * 4
            + [_spec(sharding, lead + (q, d), f32)])


def _burst_specs(sharding, lead, u, d):
    f32, i32 = jnp.float32, jnp.int32
    return ([_spec(sharding, lead + (u,), i32)] * 2
            + [_spec(sharding, lead + (u,), f32)] * 2
            + [_spec(sharding, lead + (u, d), f32)])


def test_olaf_step_multi_queue(one_chip, ppo_dim):
    fn = jax.jit(lambda *a: olaf_step_pallas(*a, k=1, interpret=False))
    _check(fn.lower(*_queue_specs(one_chip, (S,), Q, ppo_dim),
                    *_burst_specs(one_chip, (S,), U, ppo_dim)))


def test_olaf_step_lm_gradient_auto_is_kernel(one_chip, lm_dim, on_tpu):
    """``impl="auto"`` takes the kernel on a TPU, at the width of a whole
    flattened ``smollm-360m`` gradient (one queue row per update)."""
    from repro.core.olaf_queue import JaxQueueState
    names = [f.name for f in dataclasses.fields(JaxQueueState)]
    fields = dict(zip(
        ["cluster", "worker", "seq", "gen_time", "reward", "agg_count",
         "replaceable", "next_seq", "n_dropped", "n_agg", "n_repl",
         "payload"], _queue_specs(one_chip, (), LM_Q, lm_dim)))
    fields["n_screened"] = _spec(one_chip, (), jnp.int32)
    state = JaxQueueState(**{n: fields[n] for n in names})
    burst = _burst_specs(one_chip, (), LM_U, lm_dim)
    send = _spec(one_chip, (LM_U,), jnp.bool_)
    compiled = _check(ops.olaf_step.lower(
        state, *burst, jnp.inf, send, k=LM_K))
    # the donated queue is updated in place: one (Q, D) payload, not two
    assert compiled.memory_analysis().alias_size_in_bytes >= \
        LM_Q * lm_dim * 4


def test_pack_update_lm_gradient_without_relayout_loop(one_chip):
    """The trainer's packing of a burst of whole ``smollm-360m`` gradients,
    in the layouts the worker step returns them (bf16, some leaves
    transposed), into the ``(burst, dim)`` f32 payloads ``ps_step`` reads
    compiles without the relayout ``while`` loops that XLA builds for a
    plain ``concatenate`` of raveled leaves, and with less scratch than the
    burst itself."""
    from repro.configs import get_config
    from repro.launch.train import pack_update
    from repro.models import api

    cfg = dataclasses.replace(get_config("smollm-360m"), n_layers=LM_LAYERS)
    shapes = jax.eval_shape(lambda: api.init_model(jax.random.key(0), cfg))
    params = jax.tree.map(lambda s: _spec(one_chip, s.shape, s.dtype),
                          shapes)
    batch = {k: _spec(one_chip, (8, 64), jnp.int32)
             for k in ("tokens", "labels")}
    grad_formats = jax.jit(jax.value_and_grad(
        lambda p, b: api.loss_fn(p, b, cfg))).lower(
            params, batch).compile().output_formats[1]
    grads = jax.tree.map(lambda s, f: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=f), shapes, grad_formats)
    dim = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    compiled = pack_update.lower([grads] * LM_U).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < LM_U * dim * 4
    assert " while(" not in compiled.as_text()
