"""Share, in %, of the decode tokens read to the host one step late: the
program's ``executor.read_deferred`` spans (a token read after the next
step was dispatched) over those and its ``executor.sync`` spans (a read
that waits on the step just dispatched), among the spans that start in
the window.  None where the trace has no ``executor.read_deferred`` span
at all: a program that reads every token in the step that made it."""
from chipbench.harness import spans

DEFERRED = "executor.read_deferred"
SYNC = "executor.sync"


def read(run):
    if run.trace is None:
        return None
    w = run.window
    late = spans.program_spans(run.trace, w, DEFERRED)
    if not late:
        return None
    n_late, n_sync = (
        sum(w.start <= sp.start < w.end for sp in found)
        for found in (late, spans.program_spans(run.trace, w, SYNC)))
    return 100.0 * n_late / (n_late + n_sync) if n_late + n_sync else None
