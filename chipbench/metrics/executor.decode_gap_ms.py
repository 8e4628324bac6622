"""Mean device-idle time, in ms, between two consecutive ``_decode`` runs
of one request with no other request dispatched between them: the host's
sampling, sync and engine loop between tokens."""


def read(run):
    if run.trace is None:
        return None
    gaps, prev, busy = [], None, 0.0
    for r in run.trace.runs:
        if r.program == "_decode":
            if prev is not None and prev.rid == r.rid:
                gaps.append(max(0.0, r.start - prev.end - busy))
            prev, busy = r, 0.0
        elif prev is not None:
            if r.rid != prev.rid or r.program in ("_period_prefill", "_embed"):
                prev = None
            else:
                busy += r.seconds
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
