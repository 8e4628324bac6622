"""Jitted wrappers for the preemptible matmul kernel.

* ``matmul(x, y)``                — ordinary full GEMM.
* ``matmul_resumable(...)``       — run a K-tile range; checkpoint =
                                    (accumulator, k_tile).
* ``MatmulCheckpoint``            — the ACCQ-analogue context object.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.preemptible_matmul.kernel import matmul_resumable_raw


def _pad_to(a: jax.Array, mult0: int, mult1: int) -> jax.Array:
    p0 = (-a.shape[0]) % mult0
    p1 = (-a.shape[1]) % mult1
    if p0 or p1:
        a = jnp.pad(a, ((0, p0), (0, p1)))
    return a


@dataclasses.dataclass
class MatmulCheckpoint:
    """Checkpointed GEMM context: partial accumulator + progress index."""
    acc: jax.Array          # (Mp, Np) f32, padded
    k_tile: int             # next K tile to execute
    n_ktiles: int
    shape: Tuple[int, int]  # un-padded (M, N)

    @property
    def done(self) -> bool:
        return self.k_tile >= self.n_ktiles

    def context_bytes(self) -> int:
        return int(self.acc.size * self.acc.dtype.itemsize)


def start(x: jax.Array, y: jax.Array, bm: int = 128, bn: int = 128,
          bk: int = 128) -> MatmulCheckpoint:
    m, n = x.shape[0], y.shape[1]
    kp = x.shape[1] + ((-x.shape[1]) % bk)
    acc = jnp.zeros((m + ((-m) % bm), n + ((-n) % bn)), jnp.float32)
    return MatmulCheckpoint(acc=acc, k_tile=0, n_ktiles=kp // bk,
                            shape=(m, n))


def advance(ck: MatmulCheckpoint, x: jax.Array, y: jax.Array,
            n_tiles: int, bm: int = 128, bn: int = 128, bk: int = 128,
            interpret: bool = False) -> MatmulCheckpoint:
    """Execute up to ``n_tiles`` more K tiles (one scheduling quantum)."""
    xp = _pad_to(x, bm, bk)
    yp = _pad_to(y, bk, bn)
    k_end = min(ck.n_ktiles, ck.k_tile + n_tiles)
    acc = matmul_resumable_raw(xp, yp, ck.acc, ck.k_tile, k_end,
                               bm=bm, bn=bn, bk=bk, interpret=interpret)
    return MatmulCheckpoint(acc=acc, k_tile=k_end, n_ktiles=ck.n_ktiles,
                            shape=ck.shape)


def finish(ck: MatmulCheckpoint, out_dtype=jnp.float32) -> jax.Array:
    assert ck.done
    m, n = ck.shape
    return ck.acc[:m, :n].astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret",
                                             "out_dtype"))
def matmul(x: jax.Array, y: jax.Array, bm: int = 128, bn: int = 128,
           bk: int = 128, interpret: bool = False,
           out_dtype=None) -> jax.Array:
    """Full GEMM through the preemptible kernel (single launch)."""
    m, n = x.shape[0], y.shape[1]
    xp = _pad_to(x, bm, bk)
    yp = _pad_to(y, bk, bn)
    acc = jnp.zeros((xp.shape[0], yp.shape[1]), jnp.float32)
    acc = matmul_resumable_raw(xp, yp, acc, 0, xp.shape[1] // bk,
                               bm=bm, bn=bn, bk=bk, interpret=interpret)
    return acc[:m, :n].astype(out_dtype or x.dtype)
