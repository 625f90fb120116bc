"""The PS step's share of its roofline (%): the least time its HBM traffic
takes at peak bandwidth (it is bound by bandwidth), over the device time of
the ``ps_step`` executable per call. It times the whole executable, so it
reads the same work whether the step takes the Pallas kernel or the XLA
composition."""
from perfbench import work

EXECUTABLE = "jit_ps_step"  # launch/train.py run_olaf_async's ps_step


def read(ctx):
    tr, t, m = ctx["trace"], ctx["traffic"], ctx["config"]
    seconds, calls = tr["module_s"].get(EXECUTABLE), tr["module_n"].get(
        EXECUTABLE)
    if not seconds or not calls or ctx["peaks"] is None:
        return None
    dim = work.lm_param_count(m)
    param_bytes = 2 if m["torch_dtype"] == "bfloat16" else 4
    least = work.ps_step_bytes(dim, t["queue_slots"], t["burst"],
                               t["drain_k"], param_bytes) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (seconds / calls)
