"""Model FLOP utilization of the worker gradient step itself (%): the
forward and backward FLOPs the window's worker steps require (the count
``mfu`` takes) over the device time of the step's executable,
``jit_worker_grad``, at the chips' bf16 peak. Unlike ``mfu`` it leaves out
the set-up, the PS step and the eager ops between steps."""
from perfbench import work

EXECUTABLE = "jit_worker_grad"  # launch/train.py run_olaf_async's grad_fn


def read(ctx):
    w, t = ctx["work"], ctx["traffic"]
    seconds = ctx["trace"]["module_s"].get(EXECUTABLE)
    if not seconds or not w.get("tokens") or ctx["peaks"] is None:
        return None
    flops = w["tokens"] * work.lm_train_flops_per_token(ctx["config"],
                                                        t["seq"])
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * flops / (seconds * peak)
