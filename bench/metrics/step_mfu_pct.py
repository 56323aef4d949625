"""The model's FLOPs per epoch (bench/counts.py) times the epochs per
second of the window, over the chips' bf16 peak."""
from bench import counts


def read(ctx):
    c = ctx.cell.config
    layers = counts.layer_widths(c["feature_dim"], c["hidden_dim"],
                                 c["embed_dim"], c["num_layers"])
    flops = counts.model_flops_per_epoch(ctx.layout.n_nodes,
                                         ctx.layout.n_arcs, layers,
                                         c["num_classes"])
    rate = ctx.window.epochs / ctx.window.seconds
    return 100.0 * flops * rate / (ctx.chips
                                   * ctx.peaks["bf16_flops_per_s"])
