"""50th percentile, in s, of the wall latency of the interactive requests
due in the window, from due time to their ``complete`` event; a request
not complete at the close is censored there (``harness/stats.py``)."""
from chipbench.harness import stats


def read(run):
    return stats.end_to_end(run.window, run.cell, run.mix)["hi_latency_p50_s"]
