"""Share of its roofline that the fused similarity kernel
(``pairwise_kernel_fused``) reaches: the least time the chip could take for
the kernel's operations and bytes, worked out from the store's shape
(``counts.py``), over the kernel's device time in the trace. At the paper's
sizes the memory bound sets the least time."""
from counts import roofline_seconds, similarity_bytes, similarity_flops


def read(ctx):
    if ctx.shapes["store"] is None:
        return None
    seconds, calls = ctx.device_seconds("pairwise_kernel_fused", modules=False)
    if not seconds:
        return None
    n, d = ctx.shapes["store"]
    least, _ = roofline_seconds(similarity_flops(n, d), similarity_bytes(n, d),
                                ctx.peaks["bf16_flops_per_s"], ctx.peaks["hbm_bytes_per_s"])
    return calls * least / seconds * 100.0
