"""mfu: model FLOPs of forward and backward over the traced window, as a
share of the chips' bf16 peak. Layer: jitted step."""


def read(ctx):
    flops = ctx.facts.get("flops_per_step")
    if not flops or not ctx.steps:
        return None
    peak = ctx.peaks["bf16_flops"] * ctx.chips
    return 100.0 * flops * ctx.steps / (ctx.window_s * peak)
