"""The whole round's model FLOP utilization: the training FLOPs of the
window's rounds (the model kind's ``train_flops_per_client`` for each
distinct client of a round; padded slots train with weight 0 and do not
count; 6 P N B for the MLP) over the traced window and the chip's bf16 peak."""


def read(ctx):
    per_client = ctx.shapes["train_flops_per_client"]
    flops = sum(per_client * r.n_distinct_clients for r in ctx.records)
    if not flops:
        return None
    return flops / ctx.summary["window_s"] / ctx.peaks["bf16_flops_per_s"] * 100.0
