"""Quickstart: serve two models on one engine under PREMA scheduling.

Runs reduced configs on JAX's default device: the CPU with
``JAX_PLATFORMS=cpu``, else the TPU chip.  ``chip_smoke.py`` drives the same
path at the full width of olmo-1b.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import numpy as np

from repro.models import get_model
from repro.serving import EngineConfig, InferenceRequest, ServingEngine


def main():
    key = jax.random.PRNGKey(0)
    models = {}
    for name in ("olmo-1b", "qwen3-8b"):
        m = get_model(name, tiny=True)
        models[name] = (m, m.init_params(key))

    engine = ServingEngine(models,
                           cfg=EngineConfig(policy="prema", mechanism="dynamic"))
    # teach the decode-length LUT (the paper's Fig-9 regression) a profile
    engine.fit_length_regressor("olmo-1b", [(8, 4), (8, 6), (16, 8)])
    engine.fit_length_regressor("qwen3-8b", [(8, 5), (16, 10)])

    rng = np.random.default_rng(0)
    reqs = []
    for i in range(8):
        arch = ("olmo-1b", "qwen3-8b")[i % 2]
        plen = int(rng.integers(6, 16))
        reqs.append(InferenceRequest(
            rid=i, arch=arch,
            prompt=rng.integers(1, 250, (1, plen)).astype(np.int32),
            max_new_tokens=8,
            priority=int(rng.choice([1, 3, 9])),
            arrival=float(rng.uniform(0, 1e-4)),
            true_decode_len=int(rng.integers(3, 9))))

    results = engine.run(reqs)
    print(f"{'rid':>3} {'arch':12} {'prio':>4} {'ntt':>6} {'ttft_us':>8} "
          f"{'preempts':>8} tokens")
    for r in sorted(results, key=lambda r: r.rid):
        print(f"{r.rid:>3} {r.arch:12} {r.priority:>4} {r.ntt:>6.2f} "
              f"{r.ttft*1e6:>8.1f} {r.n_preemptions:>8} "
              f"{r.tokens[0][:6].tolist()}")
    s = engine.summary()
    print(f"\nANTT={s['antt']:.2f}  STP={s['stp']:.2f}  "
          f"fairness={s['fairness']:.3f}  SLA met={s['sla_met_rate']:.0%}")


if __name__ == "__main__":
    main()
