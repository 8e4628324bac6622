"""Model FLOP/s utilisation, in %: the forward FLOPs that the prompt and
generated tokens of every request completed in the window need, over the
window's length times the chip's peak."""
from chipbench.harness import counts


def read(run):
    w = run.window
    flops = sum(counts.request_flops(run.spec, r.req.prompt_len,
                                     r.tokens.shape[1])
                for r in w.records.values()
                if r.complete is not None and r.complete <= w.end)
    return 100.0 * flops / (w.seconds * run.peaks["flops_bf16"] * run.n_chips)
