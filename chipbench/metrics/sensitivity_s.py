"""Seconds of the set-up's sensitivity analysis: the newest
``sensitivity`` span in the program's span record (host clock; its
compilation included)."""
from chipbench import stages


def read(ctx):
    rec = stages.recorded_spans(ctx) or []
    got = [s for s in rec if s[0] == "sensitivity"]
    return (got[-1][3] - got[-1][2]) * 1e-9 if got else None
