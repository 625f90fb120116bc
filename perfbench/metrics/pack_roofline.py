"""The update packing's share of its roofline (%): the least time its HBM
traffic takes at peak bandwidth (it is bound by bandwidth), over the device
time of the packing executable per call. One call packs a burst: each of
its updates' gradient read once, ``dim`` values in the parameters' dtype,
and the update's float32 row of the burst written once."""
from perfbench import work

EXECUTABLE = "jit_pack_update"  # launch/train.py pack_update


def pack_bytes(dim: int, burst: int, param_bytes: int) -> int:
    """Least HBM traffic of one packing call: ``burst`` gradients read once
    and their float32 rows written once."""
    return burst * dim * (param_bytes + 4)


def read(ctx):
    tr, t, m = ctx["trace"], ctx["traffic"], ctx["config"]
    seconds, calls = tr["module_s"].get(EXECUTABLE), tr["module_n"].get(
        EXECUTABLE)
    if not seconds or not calls or ctx["peaks"] is None:
        return None
    param_bytes = 2 if m["torch_dtype"] == "bfloat16" else 4
    least = pack_bytes(work.lm_param_count(m), t["burst"], param_bytes) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (seconds / calls)
