"""Fix a cell's numbers from readings on the chip, once, when the cell is
defined; the benchmark's own runs never run this.

    python3 chipbench/calibrate.py --workload <cell> [--seconds 20] [--seeds 101,...]

In one process, for the cell's configuration and traffic:

1. ``isolated_s``: each request size of the open tenant served alone on
   the chip, after the same warm-up as a run (median of 3 wall times).
2. The rate: the open tenant's mean rate is set to half the chip, 0.5 over
   the mean isolated time of its size cycle.
3. A sweep: 20-second windows at 0.3, 0.5, 0.7 and 0.9 of the chip, with
   how far latency grows from the window's first quarter to its last.
4. The control: for each seed a window of ``--seconds`` at the cell's
   rate; on the same sample of served requests the widest logit gap of the
   program and of the reference with fp8 weights in its place
   (``harness/check.py``).  The lower reading is the program's largest,
   the upper the control's smallest; where the upper is 3 times the lower
   or more, the limit is ``lower**(1/3) * upper**(2/3)``, nearer the upper.

It writes ``chipbench/cells/<cell>.json`` and prints one JSON line per
reading.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

from run import ROOT, chips, enable_compile_cache, warm_up

FRACTIONS = (0.3, 0.5, 0.7, 0.9)


def isolated(model, params, cycle, reps: int = 3):
    """Median wall time of each (prompt_len, output) in ``cycle`` served
    alone, from the prompt's upload to the last token on the host."""
    import numpy as np
    from chipbench.harness import stats
    from repro.serving import PreemptibleExecutor
    ex = PreemptibleExecutor(model, params)
    out = {}
    for plen, n in sorted(set(cycle)):
        batch = {"tokens": np.zeros((1, plen), np.int32)}
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            st = ex.run_uninterrupted(batch, max_new_tokens=n)
            np.asarray(st.tokens_out[-1])
            times.append(time.perf_counter() - t0)
        out[stats.size_key(plen, n)] = float(f"{np.median(times):.4g}")
        print(json.dumps({"isolated": [plen, n], "all": times}), flush=True)
    return out


def main() -> int:
    import numpy as np
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seeds", default="101,102,103,104,105,106,107,108,109,"
                                      "110,111,2147483749")
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    enable_compile_cache(ROOT)
    from chipbench.harness import catalog, check, model as model_mod, serve, stats
    from chipbench.harness.traffic import Traffic
    cell = catalog.find(ROOT, args.workload)
    chips(cell.chips)
    model, cfg = model_mod.build_model(cell.config_name, cell.spec)
    hi = max(cell.mix["tenants"], key=lambda t: t["priority"])

    def traffic(p, seed, seconds):
        return Traffic(cell.mix, p, cfg.vocab_size, seed, seconds)

    params = model_mod.make_weights(cell.spec, 7)
    probe = traffic(dict(cell.params, rates_per_s={hi["name"]: 1.0}), 7, 1.0)
    t0 = time.perf_counter()
    warm_up(model, params, probe.shapes())
    print(json.dumps({"warm_up_s": time.perf_counter() - t0}), flush=True)
    cycle = probe.cycles[hi["name"]]
    p = dict(cell.params, isolated_s=isolated(model, params, cycle))
    mean_iso = float(np.mean([stats.isolated_s(p, b, o) for b, o in cycle]))
    for frac in FRACTIONS:
        q = dict(p, rates_per_s={hi["name"]: frac / mean_iso})
        try:
            w = serve.serve(cell, model, params, traffic(q, 7, 20.0), 20.0)
        except Exception as e:      # e.g. the chip's memory runs out
            print(json.dumps({"fraction": frac, "error": str(e)[:300]}),
                  flush=True)
            continue
        recs = sorted((r.sent, r.req.rid, r) for r in w.records.values()
                      if r.req.priority == hi["priority"] and r.sent < w.end)
        lat = stats.censored_latencies([s for s, _, _ in recs],
                                       [r.complete for _, _, r in recs], w.end)
        k = max(1, len(lat) // 4)
        print(json.dumps({"fraction": frac, **stats.end_to_end(w, q, cell.mix),
                          "latency_first_quarter": float(np.mean(lat[:k])),
                          "latency_last_quarter": float(np.mean(lat[-k:]))}),
              flush=True)
    del params

    p["rates_per_s"] = {hi["name"]: float(f"{0.5 / mean_iso:.4g}")}
    lim = p["correct"]
    gaps = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        params = model_mod.make_weights(cell.spec, seed)
        w = serve.serve(cell, model, params, traffic(p, seed, args.seconds),
                        args.seconds)
        recs = check.sample(list(w.records.values()), seed,
                            lim["sample_tokens"], lim["sample_requests"])
        gaps.append((check.widest_gap(cell.spec, params, recs),
                     check.widest_gap(cell.spec, params, recs,
                                      quantize="float8_e4m3fn")))
        print(json.dumps({"seed": seed, "program_gap": gaps[-1][0],
                          "control_gap": gaps[-1][1],
                          "tokens": sum(r.tokens.shape[1] for r in recs),
                          **stats.end_to_end(w, p, cell.mix)}), flush=True)
        del params
    lower, upper = max(g for g, _ in gaps), min(c for _, c in gaps)
    if upper < 3 * lower:
        print(json.dumps({"lower": lower, "upper": upper,
                          "error": "the control does not separate"}))
        return 1
    lim["max_logit_gap"] = float(f"{lower ** (1 / 3) * upper ** (2 / 3):.3g}")
    print(json.dumps({"lower": lower, "upper": upper,
                      "limit": lim["max_logit_gap"]}), flush=True)
    path = ROOT / "chipbench" / "cells" / f"{args.workload}.json"
    path.write_text(json.dumps(p, indent=1) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
