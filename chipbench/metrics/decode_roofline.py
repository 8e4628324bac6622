"""Share, in %, of the roofline that the ``_decode`` runs of the traced
window reached: the least time their needed FLOPs and bytes take at the
chip's peaks (weights, and the cache up to each step's position, not the
masked capacity), over their device time."""
from chipbench.harness import counts, trace


def read(run):
    if run.trace is None:
        return None
    least = spent = 0.0
    for r, pos in trace.step_positions(run.trace, run.prompt_len):
        if r.program == "_decode":
            least += counts.least_time(*counts.decode_step(run.spec, pos),
                                       run.peaks)[0]
            spent += r.seconds
    return 100.0 * least / spent if spent else None
