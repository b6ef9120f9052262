"""Device milliseconds per traced epoch under the ``fake_quant`` scope
of the search's epoch program: validation's fake quantization (the
kernels or the reference arithmetic, the straight-through select, the
casts). Each op is charged to the innermost stage scope in its name
stack by the self-time rule of ``Trace.op_seconds``
(``chipbench/stages.py``)."""
from chipbench import stages


def read(ctx):
    ms = stages.epoch_stages_ms(ctx)
    return None if ms is None else ms["fake_quant"]
