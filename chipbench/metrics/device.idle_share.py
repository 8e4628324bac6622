"""Share, in %, of the traced window in which no program ran on the
device (one minus the union of program runs, averaged over devices)."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
