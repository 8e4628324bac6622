"""Preemptible executor: runs a real JAX model with preemption points at
super-block (period) boundaries during prefill and token boundaries during
decode.

This is the TPU analogue of the paper's tile-boundary CHECKPOINT: the
execution context held at a boundary — hidden activations, accumulated KV /
SSM cache slices, generated tokens — is an explicit, device-independent
pytree (:class:`ExecState`).  Suspend/resume is exact: a preempted-then-
resumed run produces bit-identical outputs to an uninterrupted one
(tests/test_serving.py).

The per-period function is jitted once per config and reused across periods
(parameters for period *i* are sliced out of the stacked pytree and passed
in), so repeated preemption never triggers recompilation.

Decode keeps each request's token and position on the device: ``_decode``
takes its greedy next token itself, and the next step takes that token as
its input.  The host reads a token one step late, after it has dispatched
the next step, so its work between tokens overlaps the device's step.  A
request with an ``eos_id`` needs every token's value for its stop check
and reads each one in the step that made it; the step that makes a
request's last token reads it too, as do :meth:`checkpoint` and
:meth:`read_pending`.

Each step is a ``jax.profiler.TraceAnnotation`` span on the host thread
that runs it (``executor.prefill``, ``executor.first_token``,
``executor.decode``, ``executor.grow_cache``, ``executor.sync``,
``executor.read_deferred``, ``executor.checkpoint``): recorded on the
device trace's clock while a profiler trace is being taken, and about a
microsecond each otherwise.  ``executor.first_token`` ends when the first
token is on the host, ``executor.decode`` when the token of the step
before is (and, in a request's last step, its own).  ``executor.sync`` is
a read that waits on the step just dispatched, ``executor.read_deferred``
the read of the previous step's token made after the next dispatch.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs import ArchConfig
from repro.models import transformer
from repro.models.registry import Model
from repro.models.layers import apply_norm, unembed

Params = Dict[str, Any]


@dataclasses.dataclass
class ExecState:
    """Checkpointable execution context (the CHECKPOINT payload)."""
    phase: str                       # prefill | decode | done
    period_idx: int = 0
    h: Optional[jax.Array] = None    # hidden activations at the boundary
    img_h: Optional[jax.Array] = None
    cache_slices: Optional[List] = None   # per completed period (prefill)
    cache: Optional[Any] = None      # stacked cache (decode)
    pos: int = 0                     # tokens in cache
    tokens_out: Optional[List[np.ndarray]] = None
    last_logits: Optional[jax.Array] = None
    tok: Optional[jax.Array] = None      # last token (B, 1): next decode input
    pos_dev: Optional[jax.Array] = None  # ``pos`` on the device
    pending: bool = False            # ``tok`` is not yet in ``tokens_out``
    max_new_tokens: Optional[int] = None   # the request's token budget
    eos_id: Optional[int] = None

    @property
    def n_tokens(self) -> int:
        """Tokens generated: those read to the host and the pending one."""
        return len(self.tokens_out) + int(self.pending)

    def context_bytes(self) -> int:
        """Bytes of the activation boundary alone: the hidden activations,
        the last logits and the decode's next token and position.  The
        caches, which a CHECKPOINT keeps too, are in :meth:`cache_bytes`;
        :meth:`device_bytes` counts both."""
        total = 0
        for arr in (self.h, self.last_logits, self.tok, self.pos_dev):
            if arr is not None:
                total += arr.size * arr.dtype.itemsize
        return int(total)

    def cache_bytes(self) -> int:
        leaves = []
        if self.cache_slices:
            leaves += jax.tree.leaves(self.cache_slices)
        if self.cache is not None:
            leaves += jax.tree.leaves(self.cache)
        return int(sum(a.size * a.dtype.itemsize for a in leaves))

    def device_bytes(self) -> int:
        """Bytes of every device array the state holds: ``h``, ``img_h``,
        the prefill ``cache_slices``, the decode ``cache`` at its grown
        capacity, ``last_logits`` and the last token and position (the
        tokens read are on the host)."""
        leaves = jax.tree.leaves((self.h, self.img_h, self.cache_slices,
                                  self.cache, self.last_logits, self.tok,
                                  self.pos_dev))
        return int(sum(a.size * a.dtype.itemsize for a in leaves))


# The jitted steps take the weights as arguments (never as closed-over
# constants, which XLA would embed in the compiled program) and the config
# as a static argument, so every executor of one config shares one compiled
# program per input shape.
@functools.partial(jax.jit, static_argnames="cfg")
def _embed(params, batch, cfg: ArchConfig):
    return transformer._embed_inputs(params, cfg, batch)


@functools.partial(jax.jit, static_argnames="cfg")
def _period_prefill(slots_slice, h, img_h, cfg: ArchConfig):
    new_cache = {}
    for i in range(cfg.period):
        h, nc, _ = transformer._apply_block(
            i, h, slots_slice[f"slot{i}"], cfg, "prefill", None, None, img_h)
        if nc is not None:
            new_cache[f"slot{i}"] = nc
    return h, new_cache


@functools.partial(jax.jit, static_argnames="cfg")
def _finalize_prefill(params, h, cfg: ArchConfig):
    hn = apply_norm(h, params["final_norm"], cfg)
    if cfg.embedding_inputs:
        return jnp.einsum("bsd,dv->bsv", hn, params["lm_head"]["w"])
    return unembed(hn[:, -1:], params, cfg)


def _greedy(logits):
    """The greedy next token of each row, as the (B, 1) int32 decode input."""
    return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]


_first_token = jax.jit(_greedy)


@functools.partial(jax.jit, static_argnames="cfg")
def _decode(params, cache, tokens, pos, cfg: ArchConfig):
    """One decode step: the logits, the cache, and the next step's token
    and position, all left on the device."""
    logits, cache = transformer.decode_step(params, cache, tokens, pos, cfg)
    return logits, cache, _greedy(logits), pos + 1


def _on_host(tok: jax.Array) -> np.ndarray:
    return np.asarray(tok)[:, 0]


class PreemptibleExecutor:
    """Period/token-granular executor for one model instance."""

    def __init__(self, model: Model, params: Params):
        self.model = model
        self.cfg: ArchConfig = model.cfg
        self.params = params

    # ------------------------------------------------------------------
    @property
    def n_periods(self) -> int:
        return self.cfg.n_periods

    def start(self, batch: Dict[str, jax.Array],
              max_new_tokens: Optional[int] = None,
              eos_id: Optional[int] = None) -> ExecState:
        """Embed the prompt.  ``max_new_tokens`` lets the step that makes
        the last token read it; ``eos_id`` makes every step read its own
        token, for the stop check."""
        h, img_h = _embed(self.params, batch, self.cfg)
        return ExecState(phase="prefill", period_idx=0, h=h, img_h=img_h,
                         cache_slices=[], tokens_out=[],
                         pos=int(h.shape[1]), max_new_tokens=max_new_tokens,
                         eos_id=eos_id)

    def _slots_slice(self, i: int):
        return jax.tree.map(lambda x: x[i], self.params["slots"])

    def step_prefill(self, st: ExecState) -> ExecState:
        """Execute one super-block period; boundary afterwards."""
        assert st.phase == "prefill"
        with TraceAnnotation("executor.prefill"):
            h, cache_slice = _period_prefill(
                self._slots_slice(st.period_idx), st.h, st.img_h, self.cfg)
            st.h = h
            st.cache_slices.append(cache_slice)
            st.period_idx += 1
            if st.period_idx < self.n_periods:
                return st
            if self.cfg.encoder_only:
                st.last_logits = _finalize_prefill(self.params, st.h, self.cfg)
                st.phase = "done"
                return st
            with TraceAnnotation("executor.first_token"):
                st.last_logits = _finalize_prefill(self.params, st.h, self.cfg)
                # stack per-period cache slices into the decode cache and
                # greedy-sample the first token, which stays on the device
                # as the first decode step's input
                st.cache = jax.tree.map(
                    lambda *xs: jnp.stack(xs, axis=0), *st.cache_slices)
                st.cache_slices = None
                st.tok = _first_token(st.last_logits)
                st.pos_dev = jnp.int32(st.pos)
                tok = _on_host(st.tok)
            st.tokens_out.append(tok)
            st.phase = "decode"
        return st

    def _grow_cache(self, st: ExecState, extra: int):
        """Extend attention KV ring buffers to hold ``extra`` more tokens."""
        def grow(path_leaf):
            return path_leaf
        cfg = self.cfg

        def grow_slot(slot_name, slot_cache):
            mixer = cfg.block_pattern[int(slot_name[4:])][0]
            if mixer != "attn":
                return slot_cache
            def pad(a):
                pad_width = [(0, 0)] * a.ndim
                pad_width[2] = (0, extra)   # (periods, B, T, H, Dh)
                return jnp.pad(a, pad_width)
            return {k: pad(v) for k, v in slot_cache.items()}

        with TraceAnnotation("executor.grow_cache"):
            st.cache = {k: grow_slot(k, v) for k, v in st.cache.items()}

    def step_decode(self, st: ExecState) -> ExecState:
        """Generate one token; boundary afterwards.  The token stays on the
        device, pending, and the call reads the previous step's token once
        this step is dispatched."""
        assert st.phase == "decode"
        with TraceAnnotation("executor.decode"):
            t_cap = None
            for name, slot in st.cache.items():
                mixer = self.cfg.block_pattern[int(name[4:])][0]
                if mixer == "attn":
                    t_cap = slot["k"].shape[2]
                    break
            if t_cap is not None and st.pos >= t_cap:
                self._grow_cache(st, max(16, t_cap // 4))
            read_now = st.eos_id is not None or (
                st.max_new_tokens is not None
                and st.n_tokens + 1 >= st.max_new_tokens)
            unread = st.tok if st.pending else None
            (st.last_logits, st.cache, st.tok, st.pos_dev) = _decode(
                self.params, st.cache, st.tok, st.pos_dev, self.cfg)
            st.pos += 1
            st.pending = True
            if not read_now:
                st.tok.copy_to_host_async()
            if unread is not None:
                with TraceAnnotation("executor.read_deferred"):
                    st.tokens_out.append(_on_host(unread))
            if read_now:
                self.read_pending(st)
        return st

    def step(self, st: ExecState) -> ExecState:
        if st.phase == "prefill":
            return self.step_prefill(st)
        if st.phase == "decode":
            return self.step_decode(st)
        return st

    # ------------------------------------------------------------------
    def run_uninterrupted(self, batch: Dict[str, jax.Array],
                          max_new_tokens: int,
                          eos_id: Optional[int] = None) -> ExecState:
        st = self.start(batch, max_new_tokens, eos_id)
        while st.phase == "prefill":
            st = self.step_prefill(st)
        while st.phase == "decode" and st.n_tokens < max_new_tokens:
            st = self.step_decode(st)
            if eos_id is not None and bool(np.all(st.tokens_out[-1] == eos_id)):
                break
        st.phase = "done"
        return st

    @staticmethod
    def read_pending(st: ExecState) -> ExecState:
        """Read the pending token, if any, to the host; this waits for the
        step that made it."""
        if st.pending:
            with TraceAnnotation("executor.sync"):
                st.tokens_out.append(_on_host(st.tok))
            st.pending = False
        return st

    @staticmethod
    def checkpoint(st: ExecState) -> ExecState:
        """Block on async dispatch so the checkpoint is a complete,
        consistent snapshot, with every token generated so far on the
        host.  The context stays where it was computed (device memory on a
        chip); nothing else is copied to the host."""
        with TraceAnnotation("executor.checkpoint"):
            PreemptibleExecutor.read_pending(st)
            for leaf in jax.tree.leaves((st.h, st.cache, st.cache_slices,
                                         st.last_logits, st.tok,
                                         st.pos_dev)):
                if hasattr(leaf, "block_until_ready"):
                    leaf.block_until_ready()
        return st

    @staticmethod
    def restore(st: ExecState) -> ExecState:
        return st
