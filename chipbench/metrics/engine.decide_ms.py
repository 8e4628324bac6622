"""Mean wall time, in ms, of the program's ``engine.decide`` spans that
start in the window: the arbiter's wake, pick and arbitrate (and the
placement) at a step boundary or on an idle device."""
from chipbench.harness import spans


def read(run):
    if run.trace is None:
        return None
    return spans.mean_ms(run.trace, run.window, spans.DECIDE)
