"""Smoke test of the serving path on one TPU chip, at the full width of olmo-1b.

    python3 chip_smoke.py

Builds olmo-1b at its published width (16 layers, d_model 2048, 16 heads,
d_ff 8192, vocab 50304) with bf16 weights from a fixed seed, and serves a
few requests through ``ServingEngine`` under PREMA: a backlog of priority-1
requests, with priority-9 requests arriving while one of them runs.  It
checks that

* every request completes and at least one is preempted;
* each request's tokens equal an uninterrupted run of the same prompt
  through ``PreemptibleExecutor.run_uninterrupted`` on the same chip;
* the weights and the KV cache live on the chip;
* the chip's prefill logits agree with a float32 ``transformer.prefill`` of
  the same weights on the host CPU, within ``REF_TOL``.

The lines it prints before the last are informational (compile seconds,
phase wall times, counts), not benchmark numbers.  The last line, printed
only when every check passed, is one JSON object naming the device.  Any
failed check raises, so the script exits non-zero; so does a run on any
platform other than a TPU.
"""
from __future__ import annotations

import collections
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import arch_ops
from repro.core.predictor import network_time
from repro.hw import HardwareModel, for_device_kind
from repro.launch.compile_cache import enable_compile_cache
from repro.models import get_model
from repro.models.registry import Model
from repro.serving import (EngineConfig, InferenceRequest,
                           PreemptibleExecutor, ServingEngine)

ARCH = "olmo-1b"
SEED = 0
MAX_NEW_TOKENS = 16
# priority -> (requests, prompt length).  The priority-1 backlog has the
# longer prompts, so an arriving priority-9 request is predicted shorter
# than the one running and PREMA checkpoints it rather than draining it.
MIX = {1: (4, 512), 9: (4, 256)}
# Relative L2 error of bf16 prefill logits against the float32 reference.
REF_TOL = 0.05


class SmokeFailure(RuntimeError):
    """A check of the smoke test failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def build_params(model: Model, seed: int = SEED):
    """Random weights in the config's dtype, generated on the default
    device by one compiled program."""
    def init_params(key):
        return model.init_params(key, dtype=jnp.dtype(model.cfg.dtype))
    return jax.block_until_ready(jax.jit(init_params)(jax.random.PRNGKey(seed)))


def make_requests(model: Model, hw: HardwareModel, seed: int = SEED):
    """The priority-1 backlog arrives at t=0; priority-9 request i arrives a
    quarter of the way into the predicted prefill of the i-th priority-1
    request to run (times are the engine's Algorithm-1 predictions)."""
    cfg = model.cfg
    rng = np.random.default_rng(seed)

    def predicted(plen):
        prefill = network_time(arch_ops.prefill_ops(cfg, plen, 1), hw)
        step = network_time(arch_ops.decode_step_ops(cfg, plen, 1), hw)
        return prefill, prefill + step * (MAX_NEW_TOKENS - 1)

    (n_lo, len_lo), (n_hi, len_hi) = MIX[1], MIX[9]
    lo_prefill, lo_total = predicted(len_lo)
    _, hi_total = predicted(len_hi)
    arrivals = [(1, len_lo, 0.0)] * n_lo + [
        (9, len_hi, i * (lo_total + hi_total) + lo_prefill / 4)
        for i in range(n_hi)]
    return [InferenceRequest(
        rid=rid, arch=cfg.name,
        prompt=rng.integers(1, cfg.vocab_size, (1, plen)).astype(np.int32),
        max_new_tokens=MAX_NEW_TOKENS, priority=prio, arrival=at)
        for rid, (prio, plen, at) in enumerate(arrivals)]


def _on(tree, device) -> bool:
    return all(leaf.devices() == {device} for leaf in jax.tree.leaves(tree))


def serve_check(model: Model, params, hw: HardwareModel) -> dict:
    """Serve the mix through the engine and check it; returns counts."""
    device = jax.devices()[0]
    check(_on(params, device), f"weights are not all on {device}")
    reqs = make_requests(model, hw)
    engine = ServingEngine({model.cfg.name: (model, params)}, cfg=EngineConfig(
        hw=hw, policy="prema", mechanism="dynamic"))
    t0 = time.perf_counter()
    results = engine.run(reqs)
    t_serve = time.perf_counter() - t0
    check(sorted(r.rid for r in results) == [r.rid for r in reqs],
          f"{len(results)} of {len(reqs)} requests completed")
    n_pre = sum(r.n_preemptions for r in results)
    check(n_pre >= 1, "no request was preempted")

    t0 = time.perf_counter()
    ex = PreemptibleExecutor(model, params)
    by_rid = {r.rid: r for r in reqs}
    for r in results:
        check(r.tokens.shape == (1, MAX_NEW_TOKENS),
              f"request {r.rid} produced {r.tokens.shape[1]} tokens")
        iso = ex.run_uninterrupted({"tokens": jnp.asarray(by_rid[r.rid].prompt)},
                                   max_new_tokens=MAX_NEW_TOKENS)
        check(np.array_equal(np.stack(iso.tokens_out, 1), r.tokens),
              f"request {r.rid}: served tokens differ from the "
              "uninterrupted run")
    check(_on(iso.cache, device), f"KV cache is not all on {device}")
    t_iso = time.perf_counter() - t0
    return {"requests": len(results), "preemptions": n_pre,
            "preempted_requests": sum(r.n_preemptions > 0 for r in results),
            "serve_s": t_serve, "uninterrupted_s": t_iso}


def reference_check(model: Model, params, prompt: np.ndarray,
                    tol: float = REF_TOL) -> dict:
    """Prefill logits of the executor on the default device against a
    float32 ``transformer.prefill`` of the same weights on the CPU."""
    ex = PreemptibleExecutor(model, params)
    st = ex.start({"tokens": jnp.asarray(prompt)})
    while st.phase == "prefill":
        st = ex.step_prefill(st)
    got = np.asarray(st.last_logits, np.float32)

    cpu = jax.devices("cpu")[0]
    params32 = jax.tree.map(lambda x: x.astype(jnp.float32),
                            jax.device_put(params, cpu))
    def reference_prefill(params, batch):
        return model.prefill(params, batch)[0]
    with jax.default_matmul_precision("highest"):
        want = jax.jit(reference_prefill)(
            params32, {"tokens": jax.device_put(jnp.asarray(prompt), cpu)})
    want = np.asarray(want, np.float32)
    check(got.shape == want.shape, f"logits {got.shape} vs {want.shape}")
    check(bool(np.isfinite(got).all()), "non-finite logits")
    err = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    check(err <= tol, f"logits relative L2 error {err} > {tol}")
    return {"rel_l2": err, "max_abs": float(np.abs(got - want).max()),
            "same_argmax": bool(got.argmax() == want.argmax()), "tol": tol}


def main() -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    platforms = jax.config.jax_platforms
    if platforms and "cpu" not in platforms.split(","):
        # the float32 reference runs on the host CPU next to the chip
        jax.config.update("jax_platforms", platforms + ",cpu")
    cache_dir = enable_compile_cache()
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's default device is "
              f"{device.platform}", file=sys.stderr)
        return 1
    hw = for_device_kind(device.device_kind)

    compiles = collections.defaultdict(list)

    def on_duration(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles[kw.get("fun_name", "?")].append(secs)
    jax.monitoring.register_event_duration_secs_listener(on_duration)

    print(f"device: {device.device_kind} ({device.platform}), "
          f"{len(jax.devices())} device(s); compile cache: {cache_dir}")
    model = get_model(ARCH)
    t0 = time.perf_counter()
    params = build_params(model)
    leaves = jax.tree.leaves(params)
    print(f"build: {ARCH}, {sum(x.size for x in leaves)} params, "
          f"{sum(x.nbytes for x in leaves) / 2**30:.2f} GiB "
          f"{model.cfg.dtype}, {time.perf_counter() - t0:.2f} s")

    served = serve_check(model, params, hw)
    print("serve: {requests} requests completed, {preemptions} preemptions "
          "over {preempted_requests} requests, tokens equal the "
          "uninterrupted runs; engine run {serve_s:.2f} s, uninterrupted "
          "runs {uninterrupted_s:.2f} s".format(**served))

    prompt = make_requests(model, hw)[-1].prompt
    t0 = time.perf_counter()
    ref = reference_check(model, params, prompt)
    print("reference: prefill logits vs float32 on the CPU: relative L2 "
          "{rel_l2:.3e} (tolerance {tol}), max abs {max_abs:.3e}, same "
          "argmax {same_argmax}; ".format(**ref)
          + f"{time.perf_counter() - t0:.2f} s")

    n = sum(len(v) for v in compiles.values())
    print(f"compiles: {n} programs, "
          f"{sum(map(sum, compiles.values())):.2f} s")
    for name, secs in sorted(compiles.items(), key=lambda kv: -sum(kv[1])):
        print(f"  {name}: {len(secs)} x, {sum(secs):.2f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
