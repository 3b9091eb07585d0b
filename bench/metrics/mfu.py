"""The whole step's share of the chips' peak: the least time the cell's
chips could take for a step (the larger of its FLOPs over their peak FLOP/s
and its bytes over their HBM bandwidth) over the traced window's time per
step. FLOPs bound it for Cannon (2 m k n)."""


def read(ctx):
    least_s = max(ctx.work["flops"] / ctx.peaks["bf16_flops_per_s"],
                  ctx.work["hbm_bytes"] / ctx.peaks["hbm_bytes_per_s"]) / ctx.chips
    return 100.0 * least_s / (ctx.window_s / ctx.steps)
