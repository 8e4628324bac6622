"""Expert-parallel MoE via shard_map + all_to_all (the production path).

Under GSPMD, data-dependent scatter/gather dispatch gets rewritten by the
SPMD partitioner into one-hot dot products and huge cross-shard transfers
(measured: ~10x flop inflation and 85 GB/device of scatter traffic on
phi3.5-moe prefill — see EXPERIMENTS.md §Perf).  This module instead
expresses the dispatch exactly the way GShard/DeepSpeed-MoE do:

1. tokens are sharded over *every* mesh axis; routing and capacity-bounded
   dispatch into per-(source-shard, expert) queues are local ops — the SPMD
   partitioner never sees them;
2. one ``all_to_all`` over the 'model' (EP) axis moves queues to their
   expert owners;
3. expert FFNs run as local batched einsums (expert weights are stored
   FSDP-sharded on d_model and all-gathered just-in-time, one local expert
   group at a time — 398B-scale expert tables never materialize);
4. the reverse ``all_to_all`` + a local gather combine the results.

Everything inside the shard_map is local or an explicit collective, so the
flop count is exactly the active-expert compute and the wire traffic is
2 x token bytes (the a2a pair) + the FSDP weight gathers.

Differentiable end-to-end (a2a/all_gather have exact transposes), so the
same path serves train_4k.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs import ArchConfig
from repro.distributed.context import ShardCtx

TP = "model"


def _fsdp_axes(mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def sharded_applicable(cfg: ArchConfig, ctx: ShardCtx, n_tokens: int) -> bool:
    if ctx is None:
        return False
    mesh = ctx.mesh
    if TP not in mesh.axis_names:
        return False
    n_dev = mesh.devices.size
    n_tp = dict(zip(mesh.axis_names, mesh.devices.shape))[TP]
    return (cfg.n_experts % n_tp == 0 and n_tokens % n_dev == 0
            and n_tokens // n_dev >= cfg.n_experts // n_tp)


def psum_applicable(cfg: ArchConfig, ctx: ShardCtx, n_tokens: int) -> bool:
    """Small-token EP path (decode steps): experts shard over 'model',
    tokens shard over the fsdp axes only (or replicate when indivisible)."""
    if ctx is None:
        return False
    mesh = ctx.mesh
    if TP not in mesh.axis_names:
        return False
    n_tp = dict(zip(mesh.axis_names, mesh.devices.shape))[TP]
    return cfg.n_experts % n_tp == 0


def moe_ffn_psum(x2d: jax.Array, p: dict, cfg: ArchConfig,
                 ctx: ShardCtx) -> Tuple[jax.Array, jax.Array]:
    """EP-without-a2a for small token counts (one decode step).

    Tokens replicate over fsdp but their *d_model slices* stay
    fsdp-sharded, so expert weights are never gathered (gathering them
    costs ~43 GB/step at jamba scale — measured and refuted, §Perf cell-3
    iteration 1b): the first expert einsum contracts the local d-slice and
    psums the (tiny) hidden activations over fsdp; the second produces
    local d-slices directly; the per-expert partial outputs combine with
    one token-sized psum over the EP axis."""
    mesh = ctx.mesh
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_tp = sizes[TP]
    fsdp = _fsdp_axes(mesh)
    t_global, d = x2d.shape
    e, k = cfg.n_experts, cfg.top_k
    e_l = e // n_tp
    has_gate = "w_gate" in p
    d_shards = 1
    for a in fsdp:
        d_shards *= sizes[a]
    if d % max(d_shards, 1) != 0:
        fsdp = ()

    def local(x_l, router_l, w_in_l, w_gate_l, w_out_l):
        # x_l: (T, d_l) — all tokens, local d slice
        t_l = x_l.shape[0]
        logits = jnp.einsum("td,de->te", x_l.astype(jnp.float32), router_l)
        if fsdp:
            logits = jax.lax.psum(logits, fsdp)             # (T, E) tiny
        probs = jax.nn.softmax(logits, axis=-1)
        gw, idx = jax.lax.top_k(probs, k)
        gw = gw / jnp.sum(gw, axis=-1, keepdims=True)
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(idx[:, 0], e, dtype=jnp.float32), axis=0)
        aux = jax.lax.pmean(e * jnp.sum(me * ce), tuple(mesh.axis_names))

        rank = jax.lax.axis_index(TP)
        local_idx = idx - rank * e_l                        # (T, k)
        valid = (local_idx >= 0) & (local_idx < e_l)
        flat_e = jnp.where(valid, local_idx, 0).reshape(t_l * k)
        pos = jnp.arange(t_l * k)
        x_rep = jnp.repeat(x_l, k, axis=0)
        upd = jnp.where(valid.reshape(t_l * k, 1), x_rep, 0).astype(x_l.dtype)
        buf = jnp.zeros((e_l, t_l * k, x_l.shape[1]),
                        x_l.dtype).at[flat_e, pos].add(upd)

        # expert FFN on sharded d: contract local slice, psum the hidden
        h = jnp.einsum("esd,edf->esf", buf, w_in_l)
        if has_gate:
            g = jnp.einsum("esd,edf->esf", buf, w_gate_l)
            if fsdp:
                h = jax.lax.psum(h, fsdp)
                g = jax.lax.psum(g, fsdp)
            h = jax.nn.silu(g) * h
        else:
            if fsdp:
                h = jax.lax.psum(h, fsdp)
            h = jax.nn.gelu(h)
        out_e = jnp.einsum("esf,efd->esd", h, w_out_l)      # (e_l, s, d_l)
        out_rep = out_e[flat_e, pos] * (
            gw.reshape(t_l * k, 1) * valid.reshape(t_l * k, 1)
        ).astype(out_e.dtype)
        y = jnp.sum(out_rep.reshape(t_l, k, x_l.shape[1]), axis=1)
        return jax.lax.psum(y, TP), aux                     # (T, d_l)

    w_gate = p.get("w_gate", p["w_in"])
    fs = fsdp if fsdp else None
    y, aux = jax.shard_map(
        local, mesh=mesh, check_vma=False,
        in_specs=(P(None, fs), P(fs, None),
                  P(TP, fs, None), P(TP, fs, None), P(TP, None, fs)),
        out_specs=(P(None, fs), P()),
    )(x2d, p["router"], p["w_in"], w_gate, p["w_out"])
    return y, aux


def moe_ffn_sharded(x2d: jax.Array, p: dict, cfg: ArchConfig,
                    ctx: ShardCtx) -> Tuple[jax.Array, jax.Array]:
    """x2d: (T, D) global → (out (T, D), aux)."""
    mesh = ctx.mesh
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_tp = sizes[TP]
    fsdp = _fsdp_axes(mesh)
    all_axes = tuple(mesh.axis_names)
    n_dev = mesh.devices.size
    t_global, d = x2d.shape
    t_l = t_global // n_dev
    e, k = cfg.n_experts, cfg.top_k
    e_l = e // n_tp
    # per-(source shard, expert) queue capacity
    cap = max(4, -(-math.ceil(t_l * k * cfg.capacity_factor / e) // 4) * 4)
    has_gate = "w_gate" in p

    def local(x_l, router, w_in_l, w_gate_l, w_out_l):
        # ---- routing (local) ----
        logits = jnp.einsum("td,de->te", x_l.astype(jnp.float32), router)
        probs = jax.nn.softmax(logits, axis=-1)
        gw, idx = jax.lax.top_k(probs, k)
        gw = gw / jnp.sum(gw, axis=-1, keepdims=True)
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(idx[:, 0], e, dtype=jnp.float32), axis=0)
        aux = e * jnp.sum(me * ce)

        # ---- local capacity-bounded dispatch ----
        flat_e = idx.reshape(t_l * k)
        onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
        pos = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - 1,
                                  flat_e[:, None], axis=1)[:, 0]
        keep = pos < cap
        pos_c = jnp.where(keep, pos, cap - 1)
        x_rep = jnp.repeat(x_l, k, axis=0)
        upd = jnp.where(keep[:, None], x_rep, 0).astype(x_l.dtype)
        buf = jnp.zeros((e, cap, d), x_l.dtype).at[flat_e, pos_c].add(upd)

        # ---- a2a to expert owners over the EP axis ----
        buf = buf.reshape(n_tp, e_l, cap, d)
        recv = jax.lax.all_to_all(buf, TP, 0, 0, tiled=True)
        # (n_src*e_l, cap, d) grouped [src, e_l]: regroup per local expert
        recv = recv.reshape(n_tp, e_l, cap, d).transpose(1, 0, 2, 3)
        toks = recv.reshape(e_l, n_tp * cap, d)

        # ---- expert FFN (gather FSDP-sharded weights just in time) ----
        if fsdp:
            gather = lambda w, ax: jax.lax.all_gather(
                w, fsdp, axis=ax, tiled=True)
        else:
            gather = lambda w, ax: w
        w_in = gather(w_in_l, 1)                     # (e_l, D, F)
        h = jnp.einsum("esd,edf->esf", toks, w_in)
        if has_gate:
            w_gate = gather(w_gate_l, 1)
            h = jax.nn.silu(jnp.einsum("esd,edf->esf", toks, w_gate)) * h
        else:
            h = jax.nn.gelu(h)
        w_out = gather(w_out_l, 2)                   # (e_l, F, D)
        out = jnp.einsum("esf,efd->esd", h, w_out)

        # ---- reverse a2a + local combine ----
        out = out.reshape(e_l, n_tp, cap, d).transpose(1, 0, 2, 3)
        back = jax.lax.all_to_all(out.reshape(n_tp, e_l, cap, d),
                                  TP, 0, 0, tiled=True)
        back = back.reshape(e, cap, d)
        out_rep = back[flat_e, pos_c]
        out_rep = out_rep * (gw.reshape(t_l * k, 1)
                             * keep[:, None]).astype(out_rep.dtype)
        y = jnp.sum(out_rep.reshape(t_l, k, d), axis=1)
        return y, jax.lax.pmean(aux, all_axes)

    w_gate = p.get("w_gate", p["w_in"])
    tok_spec = P(all_axes, None)
    y, aux = jax.shard_map(
        local, mesh=mesh, check_vma=False,
        in_specs=(tok_spec, P(None, None),
                  P(TP, fsdp if fsdp else None, None),
                  P(TP, fsdp if fsdp else None, None),
                  P(TP, None, fsdp if fsdp else None)),
        out_specs=(tok_spec, P()),
    )(x2d, p["router"], p["w_in"], w_gate, p["w_out"])
    return y, aux
