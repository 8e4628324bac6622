import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each runnable cell this script builds abstract inputs
(ShapeDtypeStruct with attached NamedShardings — no allocation), lowers the
appropriate step function

    train_4k    → train_step  (loss + grad + AdamW update)
    prefill_32k → prefill     (encoder forward for encoder-only archs)
    decode_32k  → serve_step  (one token against a full KV/SSM cache)
    long_500k   → serve_step  (524k context; sub-quadratic archs only)

onto the production mesh (single-pod 16x16 or multi-pod 2x16x16),
compiles it, and records ``memory_analysis()`` (proves it fits) and
``cost_analysis()`` + collective bytes parsed from the compiled HLO
(feeds EXPERIMENTS.md §Roofline).

Usage:
    python -m repro.launch.dryrun --arch olmo-1b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all --out results/dryrun.json
"""
import argparse
import functools
import json
import re
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.configs import SHAPES, ArchConfig, Shape, applicable
from repro.core import arch_ops
from repro.distributed import sharding as shd
from repro.distributed.context import use_rules
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh
from repro.models import transformer
from repro.training.optimizer import OptConfig, init_opt_state
from repro.training.train_step import TrainConfig, make_train_step

HBM_PER_CHIP = 16 * 1024 ** 3

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_SHAPE_RE = re.compile(r"(f64|f32|f16|bf16|s64|u64|s32|u32|s16|u16|s8|u8|"
                       r"pred|f8e4m3fn|f8e5m2)\[([0-9,]*)\]")


def _buffer_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum result-buffer bytes of every collective op instance (per-device
    HLO, so these are per-device bytes)."""
    out = {c: 0 for c in COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        stripped = line.strip()
        for coll in COLLECTIVES:
            # match "<result-type> <coll>(" or "<coll>-start("
            m = re.search(rf"= (.+?) {coll}(-start)?\(", stripped)
            if m:
                out[coll] += _buffer_bytes(m.group(1))
                out["count"] += 1
                break
    out["total"] = sum(out[c] for c in COLLECTIVES)
    return out


# --------------------------------------------------------------------------
# Per-cell abstract inputs
# --------------------------------------------------------------------------
def _sds(shape, dtype, sharding=None):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def input_specs(cfg: ArchConfig, shape: Shape, mesh) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    b = shape.global_batch
    s = shape.seq_len
    specs = shd.batch_specs(cfg, shape, mesh)
    ns = lambda p: jax.NamedSharding(mesh, p)
    out = {}
    if cfg.embedding_inputs:
        out["frames"] = _sds((b, s, cfg.d_model), jnp.bfloat16,
                             ns(specs["frames"]))
    else:
        seq = s if shape.kind != "decode" else 1
        out["tokens"] = _sds((b, seq), jnp.int32, ns(specs["tokens"]))
    if shape.kind == "train":
        out["labels"] = _sds((b, s), jnp.int32, ns(specs["labels"]))
    if cfg.img_tokens:
        out["img_embeds"] = _sds((b, cfg.img_tokens, cfg.d_vision),
                                 jnp.bfloat16, ns(specs["img_embeds"]))
    return out


def abstract_params(cfg: ArchConfig, mesh, dtype=jnp.bfloat16):
    p_shape = jax.eval_shape(
        functools.partial(transformer.init_params, cfg=cfg, dtype=dtype),
        jax.random.PRNGKey(0))
    p_spec = shd.param_specs(p_shape, cfg, mesh)
    p_sds = jax.tree.map(
        lambda s, sp: _sds(s.shape, s.dtype, jax.NamedSharding(mesh, sp)),
        p_shape, p_spec,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    return p_sds, p_spec


def abstract_cache(cfg: ArchConfig, shape: Shape, mesh, dtype=jnp.bfloat16):
    c_shape = transformer.cache_spec(cfg, shape.global_batch, shape.seq_len,
                                     dtype)
    c_spec = shd.cache_specs(cfg, shape, mesh)

    def attach(sds_tree, spec_tree):
        return jax.tree.map(
            lambda s, sp: _sds(s.shape, s.dtype, jax.NamedSharding(mesh, sp)),
            sds_tree, spec_tree,
            is_leaf=lambda x: isinstance(x, (jax.ShapeDtypeStruct,)))

    out = {}
    for slot, sub in c_shape.items():
        out[slot] = jax.tree.map(
            lambda s, sp: _sds(s.shape, s.dtype, jax.NamedSharding(mesh, sp)),
            sub, c_spec[slot],
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    return out


def train_config_for(cfg: ArchConfig, mesh=None,
                     global_batch: int = 256) -> TrainConfig:
    """Per-arch training memory policy (rationale in EXPERIMENTS §Dry-run):
    microbatching bounds live activations; >100B models additionally use
    bf16 optimizer moments and a bf16 gradient accumulator so the state
    (params 2B + m 2B + v 2B + accum 2B per param) fits a single v5e pod.

    grad_accum is clamped so each microbatch still divides the
    batch-sharding degree (microbatch < #data-shards would force batch
    replication — measured 10x flop inflation on the multi-pod mesh)."""
    n = cfg.param_count()
    big = n > 1e11
    ga = 4 if n < 2e9 else (8 if n < 1.5e10 else 16)
    if mesh is not None:
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        batch_shards = sizes.get("pod", 1) * sizes.get("data", 1)
        ga = min(ga, max(1, global_batch // batch_shards))
    remat = os.environ.get("REPRO_REMAT", "full")
    ga = int(os.environ.get("REPRO_GRAD_ACCUM", ga))
    return TrainConfig(
        opt=OptConfig(moment_dtype="bfloat16" if big else "float32"),
        remat=remat, grad_accum=ga,
        accum_dtype="bfloat16" if big else "float32")


# --------------------------------------------------------------------------
# Lower + compile one cell
# --------------------------------------------------------------------------
def deploy_overrides(cfg: ArchConfig, shape: Shape, tp: int = 16) -> Dict:
    """Deployment config transforms (§Perf): query heads pad up to the TP
    multiple when they don't divide it (padded heads carry zero output
    weights — numerics preserved), replacing sequence-parallel attention
    whose resharding was measured at 8x the collective bytes.

    GQA keeps the group integral (pad to lcm-style multiple); MHA must pad
    KV too, so it only pads for train/prefill — inflating the decode KV
    cache by the pad ratio would cost more HBM than qseq costs ICI."""
    out: Dict = {}
    if cfg.n_heads % tp != 0:
        mha = cfg.n_kv_heads == cfg.n_heads
        if mha and shape.kind == "decode":
            return out
        m = -(-cfg.n_heads // tp) * tp
        while (m % tp != 0) or (not mha and m % cfg.n_kv_heads != 0):
            m += tp
        out["n_heads"] = m
        if mha:
            out["n_kv_heads"] = m
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             verbose: bool = True,
             cfg_overrides: Optional[Dict] = None,
             deploy_pads: bool = True) -> Dict:
    import dataclasses
    cfg = configs.get_config(arch)
    shape = SHAPES[shape_name]
    applied: Dict = {}
    if deploy_pads:
        applied.update(deploy_overrides(cfg, shape))
    if cfg_overrides:
        applied.update(cfg_overrides)
    if applied:
        cfg = dataclasses.replace(cfg, **applied)
    ok, why = applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped", "reason": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(mesh.devices.shape))
    rules = shd.logical_rules(cfg, shape, mesh)
    t0 = time.time()

    with use_rules(mesh, rules):
        p_sds, p_spec = abstract_params(cfg, mesh)
        batch_sds = input_specs(cfg, shape, mesh)

        if shape.kind == "train":
            tcfg = train_config_for(cfg, mesh, shape.global_batch)
            step = make_train_step(cfg, tcfg)
            o_shape = jax.eval_shape(
                functools.partial(init_opt_state, cfg=tcfg.opt), p_sds)
            o_spec = shd.opt_specs(o_shape, p_spec, p_sds, mesh)
            o_sds = jax.tree.map(
                lambda s, sp: _sds(s.shape, s.dtype,
                                   jax.NamedSharding(mesh, sp)),
                o_shape, o_spec,
                is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
            # donate params+opt: the update happens "in place", so old and
            # new state never coexist in HBM
            fn = jax.jit(step, out_shardings=(
                shd.as_shardings(p_spec, mesh),
                shd.as_shardings(o_spec, mesh), None),
                donate_argnums=(0, 1))
            lowered = fn.lower(p_sds, o_sds, batch_sds)
        elif shape.kind == "prefill":
            # pin the returned KV cache to its decode-sharding layout
            cache_out = None
            if not cfg.encoder_only:
                dec_shape = Shape("cache", "decode", shape.seq_len,
                                  shape.global_batch)
                cache_out = shd.as_shardings(
                    shd.cache_specs(cfg, dec_shape, mesh), mesh)
            fn = jax.jit(functools.partial(transformer.prefill, cfg=cfg),
                         out_shardings=(None, cache_out))
            lowered = fn.lower(p_sds, batch_sds)
        else:  # decode: donate the cache (in-place KV append)
            fn = jax.jit(functools.partial(transformer.decode_step, cfg=cfg),
                         donate_argnums=(1,))
            cache_sds = abstract_cache(cfg, shape, mesh)
            pos_sds = _sds((), jnp.int32,
                           jax.NamedSharding(mesh, jax.sharding.PartitionSpec()))
            lowered = fn.lower(p_sds, cache_sds, batch_sds["tokens"], pos_sds)

        compiled = lowered.compile()

    t_compile = time.time() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo_text = compiled.as_text()
    coll_raw = collective_bytes(hlo_text)
    corr = hlo_analysis.analyze(hlo_text)   # trip-count-corrected
    peak = int(getattr(mem, "peak_memory_in_bytes", 0))
    arg = int(mem.argument_size_in_bytes)
    temp = int(mem.temp_size_in_bytes)
    outb = int(mem.output_size_in_bytes)

    # analytic flops for the MODEL_FLOPS ratio (per device)
    if shape.kind == "train":
        fwd = arch_ops.flops(cfg, shape.seq_len, shape.global_batch,
                             "prefill")
        analytic = 4.0 * fwd / n_chips      # fwd + 2x bwd + remat fwd
    elif shape.kind == "prefill":
        analytic = float(arch_ops.flops(cfg, shape.seq_len,
                                        shape.global_batch, "prefill")) / n_chips
    else:
        analytic = float(arch_ops.flops(cfg, shape.seq_len,
                                        shape.global_batch, "decode")) / n_chips
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    n_active = cfg.active_param_count()
    model_flops = (6.0 if shape.kind == "train" else 2.0) * n_active * tokens

    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "n_chips": n_chips,
        "status": "ok",
        "deploy_overrides": applied,
        "compile_s": round(t_compile, 1),
        "flops_per_device": corr["flops"],
        "flops_per_device_raw": float(cost.get("flops", 0.0)),
        "analytic_flops_per_device": analytic,
        "model_flops_global": model_flops,
        "bytes_per_device_raw": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes_per_device": corr["collective_bytes"],
        "collective_bytes_raw": coll_raw["total"],
        "collectives": {k: v for k, v in corr.items()
                        if k.startswith("coll_")},
        "n_collectives": corr["n_collectives"],
        "memory": {"argument": arg, "output": outb, "temp": temp,
                   "peak": peak},
        "fits_hbm": bool(max(arg + temp, peak) <= HBM_PER_CHIP),
    }
    if verbose:
        print(f"[{result['mesh']}] {arch} x {shape_name}: "
              f"compile {t_compile:.0f}s  "
              f"flops/dev {corr['flops']:.3e} (analytic {analytic:.3e})  "
              f"coll {corr['collective_bytes']/1e6:.1f} MB  "
              f"mem arg {arg/1e9:.2f} + temp {temp/1e9:.2f} GB  "
              f"fits={result['fits_hbm']}", flush=True)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun.json")
    args = ap.parse_args()

    if args.all:
        archs = configs.ARCH_NAMES
        shapes = list(SHAPES)
        meshes = [False, True]
    else:
        archs = [args.arch] if args.arch else configs.ARCH_NAMES
        shapes = [args.shape] if args.shape else list(SHAPES)
        meshes = {"single": [False], "multi": [True],
                  "both": [False, True]}[args.mesh]

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    results = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)

    for multi in meshes:
        for arch in archs:
            for shape in shapes:
                key = f"{arch}|{shape}|{'multi' if multi else 'single'}"
                if results.get(key, {}).get("status") in ("ok", "skipped"):
                    print(f"cached: {key}", flush=True)
                    continue
                try:
                    results[key] = run_cell(arch, shape, multi)
                except Exception as e:  # record failures, keep going
                    results[key] = {
                        "arch": arch, "shape": shape,
                        "mesh": "multi" if multi else "single",
                        "status": "error", "error": str(e)[:2000]}
                    print(f"ERROR {key}: {str(e)[:300]}", flush=True)
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)

    n_ok = sum(1 for r in results.values() if r["status"] == "ok")
    n_skip = sum(1 for r in results.values() if r["status"] == "skipped")
    n_err = sum(1 for r in results.values() if r["status"] == "error")
    print(f"\ndry-run: {n_ok} ok, {n_skip} skipped, {n_err} errors "
          f"→ {args.out}")


if __name__ == "__main__":
    main()
