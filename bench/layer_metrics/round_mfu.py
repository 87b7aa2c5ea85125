"""The whole round's model FLOP utilization: the training FLOPs of the
window's rounds (6 P per sample, distinct clients x N x B samples a round;
padded slots train with weight 0 and do not count) over the traced window
and the chip's bf16 peak."""
from counts import round_train_flops


def read(ctx):
    sh = ctx.shapes
    flops = sum(round_train_flops(sh["n_params"], r.n_distinct_clients,
                                  sh["n_local_steps"], sh["batch_size"]) for r in ctx.records)
    if not flops:
        return None
    return flops / ctx.summary["window_s"] / ctx.peaks["bf16_flops_per_s"] * 100.0
