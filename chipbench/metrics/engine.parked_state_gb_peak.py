"""The most device memory, in GB (1e9 bytes), that started requests not
running held while the window was open: the largest reading of the
program's ``engine.parked_bytes:<n>`` gauge, the sum of the arrays of
every such request's execution state."""
from chipbench.harness import spans


def read(run):
    if run.trace is None:
        return None
    peak = spans.parked_peak(run.trace)
    return None if peak is None else peak / 1e9
