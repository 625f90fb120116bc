"""OLAF-async LM training cells: ``run_olaf_async`` on real model gradients.

Set-up makes one warm-up call of the trainer, recorder included, which
compiles (or loads from the persistent cache) every program the window
runs, and times its steady steps. The window is one call whose step
count that rate sizes to fill ``seconds``; it starts from the seed's
weights, so its first three steps are the ones the reference follows.

The trainer builds its jitted steps inside each call and keeps its state
to itself. Its checkpoint hook is the one place it hands the state out, so
for the duration of a call the hook is pointed at an in-memory recorder
(``_Hook``): it copies Adam's first moment after step 1 and the
parameters after step 3 on the device, and writes nothing to disk.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Dict

import numpy as np

from perfbench.reference import lm_async as ref

B1 = 0.9  # Adam's first-moment decay in the trainer's optimizer


def tree_paths(tree, prefix: str = "") -> Dict:
    if isinstance(tree, dict):
        out = {}
        for k, v in tree.items():
            out.update(tree_paths(v, f"{prefix}/{k}" if prefix else k))
        return out
    return {prefix: tree}


class _Hook:
    """Stands in for the trainer's ``save_checkpoint`` during one call."""

    def __init__(self, capture: bool = False, block_at=()):
        self.capture, self.block_at = capture, set(block_at)
        self.times: Dict[int, float] = {}
        self.m1 = self.p3 = None

    def __call__(self, path, step, params, opt_state, aux=None):
        import jax
        import jax.numpy as jnp
        if step in self.block_at:
            jax.block_until_ready(params)
            self.times[step] = time.perf_counter()
        if self.capture and step == 1:
            self.m1 = jax.tree.map(jnp.copy, opt_state.m)
        if self.capture and step == 3:
            self.p3 = jax.tree.map(jnp.copy, params)


class Driver:
    WARM_STEPS = 6

    def __init__(self, model: Dict, traffic: Dict, seed: int, chips: int,
                 log=print):
        self.m, self.t, self.seed, self.chips = model, traffic, seed, chips
        self.log = log

    def _arch(self):
        """The program's architecture at the sizes the configuration file
        states; the settings the file cannot set must already agree."""
        from repro.configs import get_config
        m = self.m
        cfg = dataclasses.replace(
            get_config(m["repro_arch"]), n_layers=m["num_hidden_layers"],
            d_model=m["hidden_size"], d_ff=m["intermediate_size"],
            n_heads=m["num_attention_heads"],
            n_kv_heads=m["num_key_value_heads"], vocab=m["vocab_size"])
        stated = dict(rope_theta=m["rope_theta"],
                      tie_embeddings=m["tie_word_embeddings"],
                      dtype=m["torch_dtype"], family="dense", act="silu",
                      norm="rmsnorm", rope_style="standard", head_dim=None)
        run = {k: getattr(cfg, k) for k in stated}
        if run != stated:
            raise ValueError(f"{m['repro_arch']} runs {run}, the "
                             f"configuration states {stated}")
        return cfg

    def _args(self, steps: int) -> argparse.Namespace:
        t = self.t
        return argparse.Namespace(
            seq=t["seq"], batch=t["batch_per_worker"] * t["workers"],
            seed=self.seed, lr=t["lr"], workers=t["workers"],
            queue_slots=t["queue_slots"], burst_size=t["burst"],
            drain_k=t["drain_k"], steps=steps, log_every=0,
            ckpt="in-memory", ckpt_every=1, step_impl="auto")

    def _call(self, steps: int, hook: _Hook):
        import repro.launch.train as trainer
        saved = trainer.save_checkpoint
        trainer.save_checkpoint = hook
        try:
            return trainer.run_olaf_async(self.cfg, self._args(steps))
        finally:
            trainer.save_checkpoint = saved

    def setup(self) -> None:
        self.cfg = self._arch()
        n = self.WARM_STEPS
        hook = _Hook(capture=True, block_at=(2, n))
        self._call(n, hook)
        self.step_s = (hook.times[n] - hook.times[2]) / (n - 2)

    def window(self, seconds: float, span) -> Dict:
        t = self.t
        steps = max(4, round(seconds / self.step_s))
        self.hook = _Hook(capture=True)
        t0 = time.perf_counter()
        with span("train_call"):
            self.res = self._call(steps, self.hook)
        elapsed = time.perf_counter() - t0
        worker_steps = steps * t["burst"]
        tokens = worker_steps * t["batch_per_worker"] * t["seq"]
        return dict(elapsed=elapsed, attempted=steps,
                    metrics={"tokens_per_s": tokens / elapsed},
                    work=dict(ps_steps=steps, worker_steps=worker_steps,
                              tokens=tokens))

    def check(self) -> Dict[str, float]:
        """The window's first three steps against the reference's."""
        m1 = {k: np.asarray(v) / (1 - B1)
              for k, v in tree_paths(self.hook.m1).items()}
        p3 = {k: np.asarray(v, np.float32)
              for k, v in tree_paths(self.hook.p3).items()}
        self.hook = None  # frees the device copies before the reference
        got = dict(losses=self.res.losses, applied=self.res.applied,
                   combined=self.res.combined, grad1=m1, p_end=p3)
        numbers, leaves = ref.compare(
            got, ref.run(self.seed, self.m, self.t, steps=3))
        self.log(f"worst leaves: {leaves}")
        return numbers
