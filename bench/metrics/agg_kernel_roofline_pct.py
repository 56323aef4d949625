"""The aggregation kernels' least time over their device time. The least
time sums, over the kernel calls the window made, the larger of FLOPs over
the bf16 peak and bytes over HBM bandwidth, from the least work any
implementation must do (the model module's ``aggregation_least_work``;
bench/counts.py). The device time is that of the Pallas kernel events in
the trace: the ops that are Mosaic custom calls (``tpu_custom_call``), the
fused layer, the transposed aggregation and the edge dot alike. The XLA row
gather in front of each call is not counted. None where no kernel ran."""
from bench import counts, trace

KERNELS = r"tpu_custom_call"


def read(ctx):
    seconds = trace.op_seconds(ctx.trace, KERNELS, ctx.lo, ctx.hi)
    if seconds <= 0:
        return None
    c = ctx.cell.config
    n, e = ctx.layout.n_nodes, ctx.layout.n_arcs
    epochs = ctx.window.epochs
    passes = epochs // ctx.cell.traffic["epochs_per_call"]
    work = (ctx.model.aggregation_least_work(c, n, e, True) * epochs
            + ctx.model.aggregation_least_work(c, n, e, False) * passes)
    least = counts.least_seconds(work, ctx.peaks["bf16_flops_per_s"],
                                 ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
