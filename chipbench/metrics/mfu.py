"""Train step: model FLOP/s utilisation (%) over the traced window: FLOPs
per token x tokens trained in the window / window seconds, over the
chips' bf16 peak."""


def read(r):
    flops = r.flops_per_token * sum(r.tokens)
    return 100.0 * flops / r.window_s / (r.chips * r.peaks["bf16_flops_per_s"])
