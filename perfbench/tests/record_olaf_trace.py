"""Record ``data/olaf_small.xplane.pb``: one traced call of the trainer at
the small size of ``cpu_cell.py`` (2 layers, seq 16), 3 PS steps in bursts
of 4, inside the harness's ``pb:window`` and ``pb:train_call`` spans as in
a ``--trace 1`` run, after one untraced warm-up call that compiles.

    python3 perfbench/tests/record_olaf_trace.py <out.xplane.pb>

On a TPU; elsewhere the trace holds no device plane to test against. The
whole trace is about 2.7 MB, so it is cut to what the readers use
(``slim``): the ``pb:`` and ``olaf/`` host spans with their stats, and
the device planes' ``XLA Modules`` and ``XLA Ops`` events, each op named
by its HLO instruction alone. Cutting reads the trace's protobuf with the
``xplane.proto`` schema that the installed ``tensorflow`` package carries.
"""
from __future__ import annotations

import importlib.util
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from perfbench.drivers.lm_async import Driver, _Hook  # noqa: E402
from perfbench.tests.cpu_cell import SEED, small  # noqa: E402

STEPS = 3
KEEP_LINES = ("XLA Modules", "XLA Ops")
KEEP_SPANS = ("pb:", "olaf/")


def _xplane_pb2():
    """The generated ``xplane_pb2`` module, loaded without importing the
    package that carries it."""
    spec = importlib.util.find_spec("tensorflow")
    if spec is None:
        raise RuntimeError("cutting a trace needs the tensorflow package's "
                           "xplane.proto schema")
    path = Path(spec.submodule_search_locations[0], "tsl", "profiler",
                "protobuf", "xplane_pb2.py")
    mod_spec = importlib.util.spec_from_file_location("xplane_pb2", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def slim(src: str, out: str) -> None:
    """Write ``src`` to ``out`` with only what the readers use."""
    pb = _xplane_pb2()
    space = pb.XSpace()
    space.ParseFromString(Path(src).read_bytes())
    kept = pb.XSpace()
    for plane in space.planes:
        device = plane.name.startswith("/device:TPU:")
        if not device and not plane.name.startswith("/host:CPU"):
            continue
        new = kept.planes.add(id=plane.id, name=plane.name)
        used = set()
        for line in plane.lines:
            if device and line.name not in KEEP_LINES:
                continue
            events = [ev for ev in line.events if device or plane.event_metadata[
                ev.metadata_id].name.startswith(KEEP_SPANS)]
            if not events:
                continue
            nl = new.lines.add(id=line.id, display_id=line.display_id,
                               name=line.name,
                               timestamp_ns=line.timestamp_ns,
                               duration_ps=line.duration_ps)
            for ev in events:
                ne = nl.events.add(metadata_id=ev.metadata_id,
                                   offset_ps=ev.offset_ps,
                                   duration_ps=ev.duration_ps)
                if not device:
                    ne.stats.extend(ev.stats)
                    used.update(st.metadata_id for st in ev.stats)
        for line in new.lines:
            for ev in line.events:
                name = plane.event_metadata[ev.metadata_id].name
                new.event_metadata[ev.metadata_id].id = ev.metadata_id
                new.event_metadata[ev.metadata_id].name = \
                    name.split(" = ")[0] if device else name
        for i in used:
            new.stat_metadata[i].CopyFrom(plane.stat_metadata[i])
    Path(out).write_bytes(kept.SerializeToString())


def main(out: str) -> int:
    import jax

    from perfbench.harness import enable_compile_cache
    enable_compile_cache()  # the traced call loads its programs, as a run's
    over = small("smollm-360m-async.short")
    drv = Driver(over["config"], over["traffic"], SEED, 1)
    drv.cfg = drv._arch()
    drv._call(STEPS, _Hook())
    tdir = tempfile.mkdtemp(prefix="olaf-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1  # the annotations, not the runtime's events
    jax.profiler.start_trace(tdir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("pb:window"), \
                jax.profiler.TraceAnnotation("pb:train_call"):
            drv._call(STEPS, _Hook())
    finally:
        jax.profiler.stop_trace()
    slim(sorted(Path(tdir).rglob("*.xplane.pb"))[-1], out)
    shutil.rmtree(tdir, ignore_errors=True)
    print(f"{out}: {Path(out).stat().st_size} bytes on "
          f"{jax.devices()[0].device_kind}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
