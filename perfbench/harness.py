"""One run of one benchmark cell, driven by data.

``BENCHMARK.json`` names each cell (``workloads``) with its configuration
and traffic. The harness finds everything else by name:

* ``configs/<config>.json``: the deployment or model as it is run, with its
  ``driver`` (a module in ``drivers/``);
* ``traffic/<traffic>.json``: the parameters the driver's generator reads;
* ``limits/<cell>.json``: the limit of each number the correctness check
  compares;
* ``metrics/<metric>.py``: one reader per per-layer metric, ``read(ctx)``
  returning the value or ``None`` where the trace holds nothing to read.

A metric named ``<quantity>.<part>`` (``mfu.long``) is one quantity split
by the end-to-end metric it moves: without a file of its own it takes the
reader ``metrics/<quantity>.py``, and an end-to-end one takes the driver's
value of ``<quantity>``.

A run sets up (loads, builds, warms up every shape), measures one window
with the profiler off (``trace=0``: the end-to-end metrics) or on
(``trace=1``: the per-layer metrics), reads the device's peak memory,
checks the window's results against the plain reference, and returns the
result line.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(path: Path) -> Dict:
    return json.loads(Path(path).read_text())


def benchmark() -> Dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell(name: str) -> Dict:
    for w in benchmark()["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def cells() -> List[str]:
    return [w["name"] for w in benchmark()["workloads"]]


def config(name: str) -> Dict:
    return load_json(HERE / "configs" / f"{name}.json")


def traffic(name: str) -> Dict:
    return load_json(HERE / "traffic" / f"{name}.json")


def limits(name: str) -> Dict:
    return load_json(HERE / "limits" / f"{name}.json")


def quantity(name: str) -> str:
    """The quantity a metric measures: its name up to the first dot."""
    return name.split(".", 1)[0]


def metric_reader(name: str) -> Callable:
    path = HERE / "metrics" / f"{name}.py"
    if not path.exists():
        path = HERE / "metrics" / f"{quantity(name)}.py"
    spec = importlib.util.spec_from_file_location(f"perfbench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_of(cell_name: str, trace: bool) -> List[Dict]:
    """The cell's end-to-end metrics (``trace=0``) or per-layer ones."""
    b = benchmark()
    out = []
    for m in b["per_layer" if trace else "end_to_end"]:
        if "workloads" in m and cell_name not in m["workloads"]:
            continue
        out.append(m)
    return out


class CompileCounter:
    """Counts compile requests and persistent-cache misses as JAX reports
    them; a request the persistent cache serves is a hit, not a compile."""

    def __init__(self):
        import jax
        self.requests = self.misses = self.hits = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.requests += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_misses":
                self.misses += 1
            elif event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> Dict[str, int]:
        return dict(requests=self.requests, misses=self.misses,
                    hits=self.hits)


def devices(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:chips]


def memory_peak(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


def _driver(kind: str):
    return importlib.import_module(f"perfbench.drivers.{kind}").Driver


def enable_compile_cache() -> str:
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    # every program the window runs must come from the cache, small ones
    # included
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t0: Optional[float] = None, require_tpu: bool = True,
        overrides: Optional[Dict] = None, log=print) -> Dict:
    """One run of ``workload``; returns the result line as a dict.

    ``overrides`` (tests only) replaces the cell's ``config``, ``traffic``
    or ``limits`` dicts, to drive the same path at a size a CPU holds."""
    t0 = time.perf_counter() if t0 is None else t0
    sys.path.insert(0, str(ROOT / "src"))
    over = overrides or {}
    c = cell(workload)
    model = over.get("config") or config(c["config"])
    traf = over.get("traffic") or traffic(c["traffic"])
    lims = over.get("limits") or limits(workload)
    devs = devices(c["chips"], require_tpu)
    import jax
    cache_dir = enable_compile_cache()
    counter = CompileCounter()

    driver = _driver(model["driver"])(model, traf, seed, c["chips"],
                                      log=log)
    driver.setup()
    setup_counts = counter.snapshot()
    t_setup = time.perf_counter() - t0
    log(f"set-up: {t_setup:.3f} s; {setup_counts['requests']} compile "
        f"requests, {setup_counts['hits']} persistent-cache hits, "
        f"{setup_counts['misses']} misses ("
        f"{'cold' if setup_counts['misses'] else 'warm'} cache at "
        f"{cache_dir})")

    seconds_w = min(seconds, traf.get("trace_seconds", seconds)) \
        if trace else seconds
    reduced = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="perfbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)

    def span(name):
        return jax.profiler.TraceAnnotation(f"pb:{name}")

    try:
        with span("window"):
            win = driver.window(seconds_w, span)
    finally:
        if trace:
            t_stop = time.perf_counter()
            jax.profiler.stop_trace()
            log(f"trace: written in {time.perf_counter() - t_stop:.3f} s")
    if trace:
        from perfbench.trace_reduce import reduce_trace
        t_read = time.perf_counter()
        reduced = reduce_trace(sorted(Path(tdir).rglob("*.xplane.pb"))[-1])
        shutil.rmtree(tdir, ignore_errors=True)
        log(f"trace: stopped and reduced in "
            f"{time.perf_counter() - t_read:.3f} s")
    in_window = {k: v - setup_counts[k]
                 for k, v in counter.snapshot().items()}
    log(f"window: {win['elapsed']:.3f} s, {win['attempted']} attempted; "
        f"{in_window['requests']} compile requests, {in_window['hits']} "
        f"persistent-cache hits, {in_window['misses']} misses inside it")
    dev = dict(platform=devs[0].platform, kind=devs[0].device_kind,
               count=len(devs), memory_peak_bytes=memory_peak(devs))

    t_check = time.perf_counter()
    checks = driver.check()
    log(f"check: {time.perf_counter() - t_check:.3f} s")
    correct = all(checks[k] <= lims[k] for k in lims) and \
        set(checks) == set(lims)

    if trace:
        from perfbench import work
        ctx = dict(trace=reduced, work=win["work"], config=model,
                   traffic=traf, chips=len(devs),
                   peaks=work.peaks(dev["kind"]) if require_tpu else None)
        metrics = {}
        for m in metrics_of(workload, True):
            value = metric_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = dict(value=value, unit=m["unit"])
        dev.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
    else:
        values = win["metrics"]
        metrics = {m["name"]: dict(value=values[quantity(m["name"])],
                                   unit=m["unit"])
                   for m in metrics_of(workload, False)
                   if m["name"] != "setup_s"}
        metrics["setup_s"] = dict(value=t_setup, unit="s")
    line = dict(correct=bool(correct), attempted=win["attempted"],
                failed=0 if correct else win["attempted"],
                metrics=metrics, device=dev)
    if trace:
        line["breakdown"] = dict(device_ops=reduced["device_ops"],
                                 idle_gaps=reduced["idle_gaps"])
    line["checks"] = {k: dict(value=checks[k], limit=lims.get(k))
                      for k in checks}
    return line
