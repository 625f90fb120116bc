"""Drive one benchmark cell on the CPU at a small size, optionally with a
fault planted under the timed path, and print the result line.

    JAX_PLATFORMS=cpu python perfbench/tests/cpu_cell.py <cell> <fault>

The harness runs as in a chip run except that it skips its look for a TPU
and takes the small ``config``/``traffic`` below in place of the cell's
files (the cell's own limits stay). Faults:

* ``none``: the sound program;
* ``stale``: a step returns its state unchanged;
* ``half``: half of the batch is left out and the mean taken over the rest;
* ``altered``: an answer is altered where it is produced.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from perfbench import harness  # noqa: E402

SEED = 2 ** 31 + 12345


def small(cell: str):
    c = harness.cell(cell)
    model, traf = harness.config(c["config"]), harness.traffic(c["traffic"])
    model.update(hidden_size=64, intermediate_size=128,
                 num_attention_heads=4, num_key_value_heads=2,
                 num_hidden_layers=2, vocab_size=256)
    traf.update(seq=16, batch_per_worker=2)
    return dict(config=model, traffic=traf)


def plant_lm(fault: str) -> None:
    import repro.launch.train as trainer
    import repro.models.api as api
    if fault == "half":
        real_loss = api.loss_fn

        def half_loss(params, batch, cfg):
            n = batch["tokens"].shape[0] // 2
            return real_loss(params, {k: v[:n] for k, v in batch.items()},
                             cfg)
        api.loss_fn = half_loss
        return
    real_apply = trainer.apply_updates

    def faulty(params, grads, state, opt):
        if fault == "stale":
            return params, state
        # the drained gradient's embedding block doubled on its way in
        grads = dict(grads, embedding={
            k: 2 * g for k, g in grads["embedding"].items()})
        return real_apply(params, grads, state, opt)
    trainer.apply_updates = faulty


def drive(cell: str, fault: str, tmp_path) -> dict:
    """Run this script for ``cell`` and ``fault`` in a fresh CPU process
    (its own compile cache under ``tmp_path``) and return the result."""
    import os
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(Path(tmp_path) / "jax_cache"))
    p = subprocess.run([sys.executable, __file__, cell, fault],
                       cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=600)
    if p.returncode:
        raise RuntimeError(p.stderr[-3000:])
    return json.loads(p.stdout.strip().splitlines()[-1])


def main(cell: str, fault: str) -> int:
    over = small(cell)
    if fault != "none":
        plant_lm(fault)
    line = harness.run(cell, SEED, 1.0, False, require_tpu=False,
                       overrides=over, log=lambda s: None)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:3]))
