"""The model's FLOPs per epoch (its model module's ``flops_per_epoch``)
times the epochs per second of the window, over the chips' bf16 peak."""


def read(ctx):
    c = ctx.cell.config
    flops = ctx.model.flops_per_epoch(c, ctx.layout.n_nodes,
                                      ctx.layout.n_arcs, c["num_classes"])
    rate = ctx.window.epochs / ctx.window.seconds
    return 100.0 * flops * rate / (ctx.chips
                                   * ctx.peaks["bf16_flops_per_s"])
