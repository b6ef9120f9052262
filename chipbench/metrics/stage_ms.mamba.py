"""Device milliseconds per traced epoch under the ``mamba`` scope of the
search's epoch program and under neither ``ssd`` nor ``fake_quant``:
the Mamba-2 mixers' projections, conv, gates and gated norm in
validation, a part of ``stage_ms.forward``. Each op is charged to the
innermost of the stage scopes, ``mamba`` and ``ssd`` in its name stack
by the self-time rule of ``Trace.op_seconds`` (``chipbench/stages.py``);
None for a program that marks no ``mamba``."""
from chipbench import stages

SCOPES = ("mamba", "ssd")


def read(ctx):
    batches = ctx.counters.get("traced_batches", 0)
    per_epoch = ctx.traffic.get("batches_per_epoch")
    scopes = stages.scopes_for(ctx)
    if not batches or not per_epoch or not scopes:
        return None
    sec = stages.stage_seconds(ctx.trace, scopes,
                               stages=stages.STAGES + SCOPES)
    if not sec["mamba"]:
        return None
    return 1e3 * sec["mamba"] / (batches / per_epoch)
