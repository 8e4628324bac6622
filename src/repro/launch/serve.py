"""Serving launcher: bring up a PREMA engine over registered models and
replay a request trace (synthetic or from a JSON file).  The engine's
hardware model is the one :mod:`repro.hw` lists for the device JAX runs on;
a device it does not list is an error.

    PYTHONPATH=src python -m repro.launch.serve --archs olmo-1b qwen3-8b \
        --n-requests 12 --policy prema --mechanism dynamic
"""
from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from repro.hw import for_device_kind
from repro.launch.compile_cache import enable_compile_cache
from repro.models import get_model
from repro.serving import EngineConfig, InferenceRequest, ServingEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--archs", nargs="+", default=["olmo-1b", "qwen3-8b"])
    ap.add_argument("--policy", default="prema",
                    choices=["fcfs", "rrb", "hpf", "sjf", "token", "prema"])
    ap.add_argument("--mechanism", default="dynamic",
                    choices=["checkpoint", "kill", "drain", "dynamic"])
    ap.add_argument("--non-preemptive", action="store_true")
    ap.add_argument("--n-requests", type=int, default=12)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default=None, help="JSON request trace")
    ap.add_argument("--out", default=None, help="write results JSON here")
    args = ap.parse_args()

    enable_compile_cache()
    hw = for_device_kind(jax.devices()[0].device_kind)
    key = jax.random.PRNGKey(0)
    models = {}
    for name in args.archs:
        m = get_model(name, tiny=True)
        models[name] = (m, m.init_params(key))
    engine = ServingEngine(models, cfg=EngineConfig(
        hw=hw, policy=args.policy, preemptive=not args.non_preemptive,
        mechanism=args.mechanism))
    for name in args.archs:
        engine.fit_length_regressor(name, [(6, 3), (8, 4), (12, 6), (16, 8)])

    rng = np.random.default_rng(args.seed)
    if args.trace:
        with open(args.trace) as f:
            spec = json.load(f)
        reqs = [InferenceRequest(
            rid=i, arch=r["arch"],
            prompt=np.asarray(r["prompt"], np.int32)[None],
            max_new_tokens=r.get("max_new_tokens", 8),
            priority=r.get("priority", 3),
            arrival=r.get("arrival", 0.0)) for i, r in enumerate(spec)]
    else:
        reqs = []
        for i in range(args.n_requests):
            arch = args.archs[int(rng.integers(len(args.archs)))]
            plen = int(rng.integers(6, 16))
            reqs.append(InferenceRequest(
                rid=i, arch=arch,
                prompt=rng.integers(1, 250, (1, plen)).astype(np.int32),
                max_new_tokens=8, priority=int(rng.choice([1, 3, 9])),
                arrival=float(rng.uniform(0, 2e-4)),
                true_decode_len=int(rng.integers(3, 9))))

    results = engine.run(reqs)
    s = engine.summary()
    print(f"{len(results)} requests | ANTT {s['antt']:.2f} | "
          f"STP {s['stp']:.2f} | fairness {s['fairness']:.3f} | "
          f"tail95(high) {s['tail95_high']:.2f} | "
          f"SLA met {s['sla_met_rate']:.0%} | "
          f"preemptions {int(s['preemptions'])}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump([{
                "rid": r.rid, "arch": r.arch, "ntt": r.ntt,
                "ttft": r.ttft, "tokens": r.tokens.tolist(),
                "preemptions": r.n_preemptions} for r in results], f,
                indent=1)


if __name__ == "__main__":
    main()
