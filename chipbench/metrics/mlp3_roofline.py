"""The fused actor/critic trunk kernel's share of its roofline in the
DDPG update scan: the FLOPs of the trunk calls the traced update steps
need (two actor and three critic forwards per step at the minibatch
size, logical widths) at the bf16 peak, over the summed device time of
the kernel's calls. Bytes are left out: the compiler stages the
kernel's operands in on-chip memory by copies outside its events, so
the kernel's own time holds no HBM traffic to bound. A call is the
kernel's when it is a Pallas call with seven operands (the input and
three weight and bias pairs) and three results (the output and the two
hidden activations)."""


def _is_mlp3(results, operands):
    return len(operands) == 7 and len(results) == 3


def read(ctx):
    calls = ctx.trace.kernel_calls(_is_mlp3)
    updates = ctx.counters.get("traced_updates", 0)
    if not calls or not updates:
        return None
    ag, c = ctx.traffic["agent"], ctx.counters
    dims = ctx.costs.mlp3_dims(c["state_dim"], c["action_dim"],
                               ag["hidden"])
    flops = updates * sum(
        n * ctx.costs.mlp3_flops(dims[k], ag["batch_size"])
        for k, n in ctx.costs.MLP3_CALLS_PER_UPDATE.items())
    least = flops / ctx.peaks["bf16_flops"]
    return 100.0 * least / sum(c[0] for c in calls)
