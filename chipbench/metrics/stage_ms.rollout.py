"""Device milliseconds per traced epoch under the ``rollout`` scope of
the search's epoch program: the actor's rollout scan and the
normalizer's advance. Each op is charged to the innermost stage scope
in its name stack by the self-time rule of ``Trace.op_seconds``
(``chipbench/stages.py``)."""
from chipbench import stages


def read(ctx):
    ms = stages.epoch_stages_ms(ctx)
    return None if ms is None else ms["rollout"]
