"""Mean wall time, in ms, of the program's ``engine.preempt`` spans that
start in the window: from the PREEMPT decision to the new request's
start or resume, the victim's checkpoint (or kill) included."""
from chipbench.harness import spans


def read(run):
    if run.trace is None:
        return None
    return spans.mean_ms(run.trace, run.window, spans.PREEMPT)
