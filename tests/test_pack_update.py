"""``pack_update`` packs a burst's worker updates into the ``(burst, dim)``
float32 payloads ``ps_step`` reads, with one jitted executable. Its rows
are bitwise the plain float32 concatenation of each gradient's leaves in
``tree_paths`` order, a small ``run_olaf_async`` call ends in the same
state, bit for bit, as with the eager flatten-and-stack packing it
replaced, and a call compiles no eager op over the update's width."""
import argparse
import dataclasses
import json
import logging
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.launch.train as train
from repro.configs import get_config
from repro.models.module import tree_paths, unflatten_like

ROOT = Path(__file__).resolve().parents[1]


def _tree(seed):
    """A nested gradient-shaped tree with bf16 and f32 leaves, its keys in
    sorted order, as a jitted step returns a gradient. Some leaves are less
    than an (8, 128) tile of float32 values, one is a whole tile, and two
    are whole tiles and a rest."""
    k = jax.random.split(jax.random.key(seed), 6)
    return {"embed": jax.random.normal(k[0], (40, 37)).astype(
                jnp.bfloat16),
            "layers": {"b": jax.random.normal(k[2], (7,)).astype(
                           jnp.bfloat16),
                       "u": jax.random.normal(k[4], (8, 128)).astype(
                           jnp.bfloat16),
                       "w": jax.random.normal(k[1], (2, 3, 4))},
            "norm": jax.random.normal(k[3], (3,)),
            "out": jax.random.normal(k[5], (2, 1100))}


def _plain_row(tree):
    return np.concatenate([np.ravel(np.asarray(v, np.float32))
                           for v in tree_paths(tree).values()])


def _bits(x):
    x = np.asarray(x)
    return x.dtype, x.shape, x.tobytes()


def test_pack_update_rows_are_the_plain_concatenation_for_every_slot():
    trees = [_tree(s) for s in range(3)]
    payloads = train.pack_update(trees)
    assert payloads.shape == (3, _plain_row(trees[0]).size)
    for slot, tree in enumerate(trees):
        assert _bits(payloads[slot]) == _bits(_plain_row(tree))
    assert _bits(train.pack_update(trees[::-1])) == \
        _bits(np.stack([_plain_row(t) for t in trees[::-1]]))


def test_unflatten_like_reads_the_order_pack_update_writes():
    tree = _tree(7)
    back = unflatten_like(train.pack_update([tree])[0], tree)
    for (k, a), (k2, b) in zip(tree_paths(tree).items(),
                               tree_paths(back).items()):
        assert k == k2 and _bits(a) == _bits(b)


def _eager_packing(grads):
    """The trainer's packing before ``pack_update``: each worker's update
    flattened eagerly into a row of its own, the rows stacked eagerly."""
    return jnp.stack([jnp.concatenate([jnp.ravel(v).astype(jnp.float32)
                                       for v in tree_paths(g).values()])
                      for g in grads])


def _small_cfg(d_model=64, vocab=256):
    return dataclasses.replace(get_config("smollm-360m"), n_layers=2,
                               d_model=d_model, d_ff=2 * d_model, n_heads=4,
                               n_kv_heads=2, vocab=vocab)


def _args(**kw):
    base = dict(seq=16, batch=8, seed=5, lr=1e-3, workers=4, queue_slots=4,
                burst_size=4, drain_k=2, steps=4, log_every=0,
                ckpt="in-memory", ckpt_every=0, step_impl="auto")
    base.update(kw)
    return argparse.Namespace(**base)


def _run(monkeypatch, cfg, args, pack=None):
    """One call; returns its result and the state its final checkpoint
    hands out (parameters, Adam state, queue with its payload, txctl, AoM,
    PRNG key), copied to the host."""
    saved = {}

    def record(path, step, params, opt_state, aux=None):
        saved[step] = jax.device_get((params, opt_state, aux))

    with monkeypatch.context() as m:
        m.setattr(train, "save_checkpoint", record)
        if pack is not None:
            m.setattr(train, "pack_update", pack)
        res = train.run_olaf_async(cfg, args)
    return res, saved[args.steps]


FAULTS = dict(
    plain={},
    churn_screen_stale=dict(crash_workers="1", crash_at=1, restart_at=3,
                            ingress_screen=True, staleness_bound=3.0))


@pytest.mark.parametrize("faults", sorted(FAULTS))
def test_run_is_bitwise_the_eager_packing(monkeypatch, faults):
    cfg, args = _small_cfg(), _args(**FAULTS[faults])
    got, got_state = _run(monkeypatch, cfg, args)
    want, want_state = _run(monkeypatch, cfg, args, pack=_eager_packing)
    assert got.losses == want.losses
    assert got.applied == want.applied
    assert got.combined == want.combined
    assert (got.deferred, got.stale, got.screened) == \
        (want.deferred, want.stale, want.screened)
    assert got.avg_aom == want.avg_aom
    assert sorted(got.queue) == sorted(want.queue)
    for k in got.queue:
        assert _bits(got.queue[k]) == _bits(want.queue[k]), k
    a, b = jax.tree.leaves(got_state), jax.tree.leaves(want_state)
    assert jax.tree.structure(got_state) == jax.tree.structure(want_state)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert _bits(x) == _bits(y)


_COMPILING = re.compile(r"Compiling jit\((\w+)\) with global shapes and "
                        r"types \((.*)\)\. Argument mapping")
_SHAPE = re.compile(r"\w+\[([\d,]*)\]")


def _compiles(fn):
    """(name, elements over all arguments) of each executable compiled
    while ``fn`` runs, as ``jax.log_compiles`` reports them."""
    seen = []

    class Grab(logging.Handler):
        def emit(self, record):
            m = _COMPILING.search(record.getMessage())
            if m:
                sizes = [int(np.prod([int(d) for d in s.split(",") if d]))
                         for s in _SHAPE.findall(m.group(2))]
                seen.append((m.group(1), sum(sizes)))

    log = logging.getLogger("jax")
    grab = Grab(level=logging.WARNING)
    log.addHandler(grab)
    try:
        with jax.log_compiles():
            fn()
    finally:
        log.removeHandler(grab)
    return seen


def test_a_call_compiles_pack_update_once_and_no_wide_eager_op(monkeypatch):
    # widths no other test uses, so nothing is cached from before
    cfg, args = _small_cfg(d_model=40, vocab=232), _args(steps=2)
    params = jax.eval_shape(lambda: train.api.init_model(jax.random.key(0),
                                                         cfg))
    dim = sum(x.size for x in jax.tree.leaves(params))
    eager = ("concatenate", "broadcast_in_dim", "convert_element_type",
             "reshape", "stack")
    for call in range(2):
        seen = _compiles(lambda: _run(monkeypatch, cfg, args))
        wide = [(n, e) for n, e in seen if n in eager and e >= dim]
        assert wide == [], wide
        once = [n for n, _ in seen if n == "pack_update"]
        assert len(once) == (1 if call == 0 else 0), seen


def _reader():
    from importlib.util import module_from_spec, spec_from_file_location
    spec = spec_from_file_location(
        "pack_roofline", ROOT / "perfbench" / "metrics" / "pack_roofline.py")
    mod = module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ctx(module_s, module_n, burst=4):
    config = json.loads((ROOT / "perfbench" / "configs" /
                         "smollm-360m-async.json").read_text())
    peaks = json.loads((ROOT / "perfbench" / "peaks.json").read_text())
    return dict(trace=dict(module_s=module_s, module_n=module_n),
                traffic=dict(burst=burst), config=config,
                peaks=peaks["TPU v5 lite"])


def test_pack_roofline_reads_the_packing_executable_per_call():
    mod = _reader()
    ctx = _ctx({"jit_pack_update": 0.2, "jit_ps_step": 9.0},
               {"jit_pack_update": 25, "jit_ps_step": 25})
    least = 4 * 86_516_160 * (2 + 4) / ctx["peaks"]["hbm_bytes_per_s"]
    assert mod.read(ctx) == pytest.approx(100 * least / 0.008)


def test_pack_roofline_is_none_without_the_executable():
    mod = _reader()
    assert mod.read(_ctx({"jit_ps_step": 9.0}, {"jit_ps_step": 25})) is None
    assert mod.read(dict(_ctx({"jit_pack_update": 0.2},
                              {"jit_pack_update": 25}), peaks=None)) is None


def test_pack_roofline_counts_every_update_of_the_burst():
    mod = _reader()
    times = {"jit_pack_update": 0.2}, {"jit_pack_update": 25}
    assert mod.read(_ctx(*times, burst=2)) == \
        pytest.approx(mod.read(_ctx(*times, burst=4)) / 2)
