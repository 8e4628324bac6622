"""Mean device time, in ms, of one run of the ``_decode`` program in the
traced window."""


def read(run):
    if run.trace is None:
        return None
    t = [r.seconds for r in run.trace.runs if r.program == "_decode"]
    return 1e3 * sum(t) / len(t) if t else None
