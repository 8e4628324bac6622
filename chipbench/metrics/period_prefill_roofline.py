"""Share, in %, of the roofline that the ``_period_prefill`` runs of the
traced window reached: the least time of their needed FLOPs (the causal
half of attention) and bytes at the chip's peaks, over their device
time."""
from chipbench.harness import counts, trace


def read(run):
    if run.trace is None:
        return None
    least = spent = 0.0
    for r, seq in trace.step_positions(run.trace, run.prompt_len):
        if r.program == "_period_prefill":
            least += counts.least_time(*counts.prefill_period(run.spec, seq),
                                       run.peaks)[0]
            spent += r.seconds
    return 100.0 * least / spent if spent else None
