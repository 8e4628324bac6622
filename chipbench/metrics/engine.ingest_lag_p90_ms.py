"""90th percentile, in ms, of how late the engine took in an interactive
request: the wall stamp of its ``submit`` event minus its due time.  A
request not taken in by the close counts as late until the close."""
from chipbench.harness.stats import percentile


def read(run):
    hi = run.due_in_window(run.hi_priority)
    lags = [(min(r.submit, run.window.end) if r.submit is not None
             else run.window.end) - r.sent for r in hi]
    p = percentile(lags, 90)
    return None if p is None else 1e3 * p
