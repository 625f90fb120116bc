"""Model FLOP utilization of the window (%): the forward and backward
FLOPs its worker gradient steps require (6 per parameter per token plus
causal attention, from shapes), per second of the traced window, over the
chips' bf16 peak."""
from perfbench import work


def read(ctx):
    w, t = ctx["work"], ctx["traffic"]
    if not w.get("tokens") or ctx["peaks"] is None:
        return None
    flops = w["tokens"] * work.lm_train_flops_per_token(ctx["config"],
                                                        t["seq"])
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * flops / (ctx["trace"]["window_s"] * peak)
