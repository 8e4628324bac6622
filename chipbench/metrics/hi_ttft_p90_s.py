"""90th percentile, in s, of the time to first token of the interactive
requests due in the window: from the due time to the end of the
request's first ``executor.first_token`` span, when its first token
reached the host; a request with none by the close is censored there."""
from chipbench.harness import spans, stats


def read(run):
    if run.trace is None:
        return None
    times = spans.token_times(run.trace, run.window)
    if times is None:
        return None
    hi = run.due_in_window(run.hi_priority)
    first = [times[r.req.rid][0] if r.req.rid in times else None for r in hi]
    lat = stats.censored_latencies([r.sent for r in hi], first, run.window.end)
    return stats.percentile(lat, 90)
