"""The whole search step's share of the chip's bf16 peak: the FLOPs one
episode needs (the validation forward of its policy, its share of the
DDPG update steps and of the rollout's actor passes) times the episodes
completed in the traced window, over the window and the peak."""


def read(ctx):
    n = ctx.counters.get("traced_episodes", 0)
    if not n or ctx.trace.window_s <= 0:
        return None
    flops = ctx.counters["flops_per_episode"] * n
    return 100.0 * flops / ctx.trace.window_s / ctx.peaks["bf16_flops"]
