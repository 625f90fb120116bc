"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (one per artifact), writes the
full structured results to experiments/bench_results.json, and persists the
per-benchmark microseconds of each module to experiments/BENCH_<module>.json
(e.g. BENCH_queue.json, BENCH_kernels.json) so the perf trajectory is
tracked across PRs — see benchmarks/README.md for how to read them. A
suite that raises is reported as ``<suite>_ERROR``; every suite's JSON is
still written, and the harness then exits nonzero.
"""
from __future__ import annotations

import json
import sys
import time
import traceback
from pathlib import Path

OUT_DIR = Path(__file__).resolve().parents[1] / "experiments"


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (bench_failures, bench_kernels, bench_multihop,
                            bench_queue, bench_roofline, bench_step,
                            bench_train, bench_training, bench_vecsim,
                            bench_verifier)
    results = {}
    print("name,us_per_call,derived")

    timings: dict = {}

    def report(name: str, us: float, derived: str) -> None:
        timings[name] = {"us": round(us, 1), "derived": derived}
        print(f"{name},{us:.1f},\"{derived}\"")
        sys.stdout.flush()

    modules = [
        ("queue", bench_queue), ("multihop", bench_multihop),
        ("train", bench_train), ("step", bench_step),
        # vecsim also carries the multi-device vecsim_scale rows (fat-tree
        # k=4/k=8 sharded over 8 forced host devices in a child process)
        ("vecsim", bench_vecsim),
        ("training", bench_training),
        ("verifier", bench_verifier), ("kernels", bench_kernels),
        ("roofline", bench_roofline),
        # link failure + node churn + payload corruption all ride the one
        # failures suite (BENCH_failures.json carries every gated row)
        ("failures", bench_failures),
    ]
    only = sys.argv[1] if len(sys.argv) > 1 else None
    if only and only not in {n for n, _ in modules}:
        sys.exit(f"unknown suite {only!r}; pick one of "
                 f"{', '.join(n for n, _ in modules)}")
    OUT_DIR.mkdir(exist_ok=True)
    failed = []
    for name, mod in modules:
        if only and only != name:
            continue
        timings = {}
        t0 = time.time()
        try:
            results[name] = mod.main(report)
        except Exception as e:  # noqa: BLE001 — keep the suite running
            traceback.print_exc()
            report(f"{name}_ERROR", 0.0, f"{type(e).__name__}: {e}")
            results[name] = {"error": str(e)}
            failed.append(name)
        report(f"{name}_total", (time.time() - t0) * 1e6, "suite wall time")
        (OUT_DIR / f"BENCH_{name}.json").write_text(
            json.dumps(timings, indent=1) + "\n")
    out = OUT_DIR / "bench_results.json"
    if only and out.exists():
        # single-suite runs merge into the existing structured results
        # instead of clobbering every other suite's entry
        try:
            prev = json.loads(out.read_text())
        except json.JSONDecodeError:
            prev = {}
        prev.update(results)
        results = prev
    out.write_text(json.dumps(results, indent=1, default=str))
    if failed:
        sys.exit(f"suites raised: {', '.join(failed)}")


if __name__ == '__main__':
    main()
