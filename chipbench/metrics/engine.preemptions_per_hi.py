"""``preempt`` events in the window per interactive request due in it."""


def read(run):
    hi = run.due_in_window(run.hi_priority)
    if not hi:
        return None
    return sum(r.preempts for r in run.window.records.values()) / len(hi)
