"""The whole round's share of the chips' bf16 peak: the FLOPs that every
client's sub-model needs for its local steps (forward and backward, at its
width class and depths; masked padding and recomputation not counted),
summed over the rounds of the window, over the window's wall time, the
chips and the peak."""


def read(ctx):
    win = ctx["window"]
    flops = sum(ctx["flops_per_round"](r) for r in win["round_ids"])
    chips = ctx["cell"].chips
    return 100.0 * flops / win["elapsed"] / (chips * ctx["peaks"]["bf16_flops"])
