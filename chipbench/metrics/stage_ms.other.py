"""Device milliseconds per traced epoch of the search's epoch program
under no other stage: the ``reward`` and ``replay_push`` scopes and the
ops that carry no listed scope. With the other four ``stage_ms.*`` it
sums to the device's busy time per epoch (``chipbench/stages.py``)."""
from chipbench import stages


def read(ctx):
    ms = stages.epoch_stages_ms(ctx)
    return None if ms is None else ms["other"]
