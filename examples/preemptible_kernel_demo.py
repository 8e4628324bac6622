"""The paper's preemption point at kernel level: a GEMM that stops and
resumes at K-tile boundaries, with the partial accumulator as the
checkpointed ACCQ state.  The Pallas kernel runs compiled on a TPU and in
interpret mode anywhere else.

    PYTHONPATH=src python examples/preemptible_kernel_demo.py
"""
import jax
import jax.numpy as jnp

from repro.kernels.preemptible_matmul import (advance, finish, matmul_ref,
                                              start)


def main():
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    m, k, n = 512, 1024, 384
    x = jax.random.normal(k1, (m, k), jnp.float32)
    y = jax.random.normal(k2, (k, n), jnp.float32)

    interpret = jax.default_backend() != "tpu"
    ck = start(x, y)
    print(f"GEMM {m}x{k}x{n}: {ck.n_ktiles} K-tiles; "
          f"checkpoint = {ck.context_bytes()/1024:.0f} KiB accumulator")

    quantum = 2  # K tiles per scheduling quantum
    step = 0
    while not ck.done:
        ck = advance(ck, x, y, n_tiles=quantum, interpret=interpret)
        step += 1
        print(f"  quantum {step}: k_tile={ck.k_tile}/{ck.n_ktiles} "
              f"(preempt here — context is ACCQ + tile index)")
    out = finish(ck)
    ref = matmul_ref(x, y)
    err = float(jnp.max(jnp.abs(out - ref)))
    print(f"resumed result matches uninterrupted GEMM: max|err|={err:.2e}")
    assert err < 1e-3


if __name__ == "__main__":
    main()
