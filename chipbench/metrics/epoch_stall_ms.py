"""The slowest epoch of the window less the median epoch, in
milliseconds, over the window's epochs that ran after the profiler
stopped, from the program's always-on span record (``search.epoch``
spans on the host clock). Prints one line on standard error naming the
slowest epoch's phases and any generation-2 collection inside it."""
import sys

from chipbench import stages


def read(ctx):
    got = stages.stall(ctx)
    if got is None:
        return None
    print(got[1], file=sys.stderr, flush=True)
    return got[0]
