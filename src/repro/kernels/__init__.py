"""Pallas TPU kernels (compiled for the chip by default; tests pass
``interpret=True`` to run them on the CPU):

* ``preemptible_matmul`` — K-tile-resumable GEMM (the paper's GEMM_OP
  preemption point; checkpoint = partial accumulator + tile index).
* ``flash_attention``    — blockwise online-softmax prefill attention.
* ``decode_attention``   — flash-decoding over long KV caches.
"""
