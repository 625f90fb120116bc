#!/usr/bin/env python3
"""Readings that set a cell's correctness limits (not part of a run).

    python3 perfbench/readings.py --workload <cell> --seeds 1,2,3

For each seed, at the cell's own size on the chip, the numbers
``compare`` gives for the reference in float8 matmuls (the control: one
precision below the bfloat16 the configuration states) and for the
reference with half of each worker's rows left out (a planted fault),
each against the float32 reference. A step that returns its state
unchanged reads 1 on ``grad_gap`` and ``delta_gap`` by construction and
needs no run.

The program's own readings (the lower ones) are the ``checks`` of ordinary
runs of ``run.py``. Prints one JSON object per seed.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    from perfbench import harness
    c = harness.cell(args.workload)
    model, traf = harness.config(c["config"]), harness.traffic(c["traffic"])
    sys.path.insert(0, str(harness.ROOT / "src"))
    harness.devices(c["chips"], True)
    harness.enable_compile_cache()
    from perfbench.reference import lm_async as ref
    for seed in (int(s) for s in args.seeds.split(",")):
        want = ref.run(seed, model, traf, steps=3)
        out = {}
        for mode in ("fp8", "half_batch"):
            numbers, leaves = ref.compare(
                ref.run(seed, model, traf, steps=3, mode=mode), want)
            out[mode] = dict(numbers, worst_leaves=leaves)
        print(json.dumps(dict(workload=args.workload, seed=seed, **out)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
