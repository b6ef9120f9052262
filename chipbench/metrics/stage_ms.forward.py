"""Device milliseconds per traced epoch under the ``validation`` scope
of the search's epoch program and not under ``fake_quant``: the
policies' cspec build and the model's forward. Each op is charged to
the innermost stage scope in its name stack by the self-time rule of
``Trace.op_seconds`` (``chipbench/stages.py``)."""
from chipbench import stages


def read(ctx):
    ms = stages.epoch_stages_ms(ctx)
    return None if ms is None else ms["forward"]
