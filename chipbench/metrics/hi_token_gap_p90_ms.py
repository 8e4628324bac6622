"""90th percentile, in ms, of the wall time between consecutive tokens of
one interactive request reaching the host (the ends of its
``executor.first_token`` and then of each ``executor.decode`` span) in
the window; the time a request spends preempted falls in its gaps."""
from chipbench.harness import spans, stats


def read(run):
    if run.trace is None:
        return None
    times = spans.token_times(run.trace, run.window)
    if times is None:
        return None
    w = run.window
    gaps = []
    for rid, ts in times.items():
        rec = w.records.get(rid)
        if rec is None or rec.req.priority != run.hi_priority:
            continue
        ts = [t for t in ts if w.start <= t <= w.end]
        gaps += [b - a for a, b in zip(ts, ts[1:])]
    p = stats.percentile(gaps, 90)
    return None if p is None else 1e3 * p
