"""Share, in %, of the interactive requests due in the window that
completed within it and within 8 times their isolated wall time."""
from chipbench.harness import stats


def read(run):
    return stats.end_to_end(run.window, run.cell, run.mix)["hi_sla_share"]
