"""Pallas TPU kernels for the perf-critical hot spots.

Each kernel ships three layers:
  <name>.py  — ``pl.pallas_call`` + explicit BlockSpec VMEM tiling
  ops.py     — jit'd public wrappers (interpret-mode auto-detect)
  ref.py     — pure-jnp oracles; tests sweep shapes/dtypes and assert
               equality (bitwise kernels: exact; flash attention: rtol)

Kernels:
  extend_step      — the paper's hot loop, fully fused (DESIGN.md §6.3):
                     lowest-bit extraction + candidate AND-tree + match
                     flagging in one pallas_call (the engine's
                     step_backend="pallas")
  candidate_mask   — per-lane candidate bitmaps only, via
                     scalar-prefetch-indexed adjacency-row DMA + wide AND
                     (the step_backend="jnp" + use_pallas kerneling point)
  csr_extend       — the sparse expansion step (DESIGN.md §6.4): scalar-
                     prefetched CSR segment bounds, segments DMA'd into
                     SMEM, a scalar-unit sorted intersection instead of the
                     dense AND-tree (the step_backend="csr" + use_pallas
                     kerneling point)
  domain_ac        — RI-DS arc-consistency row filter (SDDMM-shaped)
  popcount_reduce  — per-row popcounts (domain sizes, match stats)
  flash_attention  — fused causal online-softmax attention (beyond-paper;
                     the pure-JAX blockwise form stays the default so XLA
                     cost analysis sees the FLOPs for §Roofline)
"""
