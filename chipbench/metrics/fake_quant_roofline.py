"""The fake-quantization kernel's share of its roofline in the search's
validation: the least time the traced validation batches need for their
fake-quantization at the HBM bandwidth (each distinct tensor read once,
once per policy where the policies differ, each policy's result written
once), over the summed device time of the kernel's calls (both passes)
in the trace. A call is the kernel's when it is a Pallas call that
either reduces one tensor to per-channel min and max rows (the range
pass) or takes an int32 width and a tensor with its two range rows (the
quantize pass)."""


def _is_fake_quant(results, operands):
    range_pass = (len(operands) == 1 and len(results) == 2
                  and all(r[1][-2:-1] == (1,) for r in results))
    quant_pass = (len(operands) == 4 and operands[0][0] == "s32"
                  and len(results) == 1)
    return range_pass or quant_pass


def read(ctx):
    calls = ctx.trace.kernel_calls(_is_fake_quant)
    batches = ctx.counters.get("traced_batches", 0)
    if not calls or not batches:
        return None
    tensors = ctx.family.fake_quant_tensors(ctx.config, ctx.traffic)
    nbytes = ctx.costs.fake_quant_bytes(tensors,
                                        ctx.traffic["episodes_per_batch"])
    least = batches * nbytes / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / sum(c[0] for c in calls)
