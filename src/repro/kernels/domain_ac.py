"""Pallas TPU kernels for RI-DS arc-consistency filtering (DESIGN.md §5).

One AC test for a single constraint arc ``(p, q, dir, label)`` asks, for
every target node ``t``, whether ``adj_rows[t] ∧ D(q)`` has any set bit —
a ``[n_t, w]`` bitmap AND against a broadcast ``[w]`` mask followed by a
per-row any-reduce.  This is the SDDMM-shaped part of domain preprocessing
(DESIGN.md §2): dense rows stream from HBM once, the mask stays resident in
VMEM.

Two granularities:

* :func:`adjacency_any` — one arc.  Grid over row tiles of ``tr`` rows;
  block ``(tr, w)`` of adjacency rows, mask block ``(1, w)`` pinned (same
  index every step), output ``(tr, 1)`` int32 flags.  ``w`` padded to
  128-word lanes, ``tr`` a multiple of 8 sublanes.  Composes with ``vmap``
  (plain BlockSpecs), which is what the batched domain engine uses.
* :func:`arc_any_sweep` — **all arcs of one AC sweep in a single
  ``pallas_call``**.  Grid ``(n_arcs, row tiles)``; the adjacency operand's
  ``index_map`` reads the scalar-prefetched ``arc_row`` table to pick which
  ``(label, dir)`` plane the pipeline DMAs next — the same
  pointer-chasing-by-prefetch trick as `candidate_mask`.  Used by the
  single-query device fixpoint (`repro.core.domains.device_fixpoint`); the
  scalar-prefetch grid spec has no vmap rule, so the batched path falls
  back to per-arc kernels.
* :func:`csr_arc_sweep` — the same sweep over **CSR planes** (DESIGN.md
  §11): no dense ``[n_planes, n_t, w]`` operand exists, so each grid step
  walks a row tile's neighbor segments on the scalar unit, out of an SMEM
  window of the HBM-resident flat ``indices``, and tests each neighbor's
  bit in the arc's mask.  The per-plane segment bounds arrive as SMEM
  tiles whose ``index_map`` chases the scalar-prefetched ``arc_row``
  table.  Scalar-prefetch again means no vmap rule — batched CSR
  fixpoints use the jnp oracle (`repro.kernels.ref.csr_arc_sweep_ref`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.candidate_mask import pad_words
from repro.kernels.csr_extend import DMA_WORDS, SENTINEL

ROW_TILE = 256


def _kernel(rows_ref, mask_ref, out_ref):
    hit = (rows_ref[...] & mask_ref[...]) != 0  # [tr, w] bool
    out_ref[...] = jnp.any(hit, axis=-1, keepdims=True).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret", "row_tile"))
def adjacency_any(
    rows: jnp.ndarray,  # [n_t, w] uint32
    mask: jnp.ndarray,  # [w] uint32
    *,
    interpret: bool,
    row_tile: int = ROW_TILE,
) -> jnp.ndarray:
    """Per-row any-bit test of ``rows ∧ mask`` -> ``[n_t]`` int32 {0,1}."""
    n_t, w = rows.shape
    wp = pad_words(w)
    tr = row_tile
    n_pad = ((n_t + tr - 1) // tr) * tr
    rows_p = jnp.pad(rows, ((0, n_pad - n_t), (0, wp - w)))
    mask_p = jnp.pad(mask, (0, wp - w))[None, :]

    out = pl.pallas_call(
        _kernel,
        grid=(n_pad // tr,),
        in_specs=[
            pl.BlockSpec((tr, wp), lambda i: (i, 0)),
            pl.BlockSpec((1, wp), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tr, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, 1), jnp.int32),
        interpret=interpret,
    )(rows_p, mask_p)
    return out[:n_t, 0]


def _sweep_kernel(arc_row_ref, adj_ref, mask_ref, out_ref):
    hit = (adj_ref[...] & mask_ref[...]) != 0  # [tr, wp] & [1, wp] -> [tr, wp]
    out_ref[...] = jnp.any(hit, axis=-1)[None, :].astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret", "row_tile"))
def arc_any_sweep(
    adj_flat: jnp.ndarray,  # [n_planes, n_t, w] uint32 (label-major planes)
    arc_row: jnp.ndarray,  # [n_arcs] int32 plane index per arc
    masks: jnp.ndarray,  # [n_arcs, w] uint32 (D(q) bitmap per arc)
    *,
    interpret: bool,
    row_tile: int = ROW_TILE,
) -> jnp.ndarray:
    """All arcs of one AC sweep in one kernel call.

    ``out[a, t] = any(adj_flat[arc_row[a], t] ∧ masks[a])`` — ``[n_arcs,
    n_t]`` int32 {0, 1}.  The adjacency plane per grid step is chosen by the
    scalar-prefetched ``arc_row`` table, so the DMA engine chases the arc
    table while the VPU reduces the previous tile.
    """
    n_arcs, w = masks.shape
    n_t = adj_flat.shape[1]
    wp = pad_words(w)
    tr = min(row_tile, max(8, ((n_t + 7) // 8) * 8))
    n_pad = ((n_t + tr - 1) // tr) * tr
    adj_p = jnp.pad(adj_flat, ((0, 0), (0, n_pad - n_t), (0, wp - w)))
    masks_p = jnp.pad(masks, ((0, 0), (0, wp - w)))

    # masks and flags are viewed as [n_arcs, 1, x] and blocked (squeezed,
    # 1, x): a (1, x) block of an [n_arcs, x] array breaks the TPU rule that
    # a block's last two dims tile by (8, 128) or span the array.
    def adj_map(a, i, arc_row_s):
        return (arc_row_s[a], i, 0)

    def mask_map(a, i, arc_row_s):
        return (a, 0, 0)

    def out_map(a, i, arc_row_s):
        return (a, 0, i)

    out = pl.pallas_call(
        _sweep_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_arcs, n_pad // tr),
            in_specs=[
                pl.BlockSpec((None, tr, wp), adj_map),
                pl.BlockSpec((None, 1, wp), mask_map),
            ],
            out_specs=pl.BlockSpec((None, 1, tr), out_map),
        ),
        out_shape=jax.ShapeDtypeStruct((n_arcs, 1, n_pad), jnp.int32),
        interpret=interpret,
    )(arc_row.astype(jnp.int32), adj_p, masks_p[:, None, :])
    return out[:, 0, :n_t]


def _csr_sweep_kernel(
    arc_row_ref, sst_ref, sln_ref, mask_ref, ind_hbm, out_ref, win, sem,
    *, deg_cap: int,
):
    """One (arc, row tile) step: every row's segment is walked on the scalar
    unit out of ``win``, an SMEM window of the flat ``indices``; the window
    is refilled (one DMA, 1024-word aligned) whenever a row runs past it."""
    tr = out_ref.shape[1]
    cap = win.shape[0]
    n_bits = mask_ref.shape[1] * 32
    row_iota = lax.broadcasted_iota(jnp.int32, (1, tr), 1)

    def row(j, carry):
        acc, lo, hi = carry
        s = sst_ref[0, j]
        e = s + jnp.clip(sln_ref[0, j], 0, deg_cap)
        refill = (e > s) & ((s < lo) | (e > hi))
        line = pl.multiple_of((s // DMA_WORDS) * DMA_WORDS, DMA_WORDS)

        @pl.when(refill)
        def _():
            copy = pltpu.make_async_copy(ind_hbm.at[pl.ds(line, cap)], win, sem)
            copy.start()
            copy.wait()

        lo = jnp.where(refill, line, lo)
        hi = jnp.where(refill, lo + cap, hi)

        def scan(st):
            k, hit = st
            u = win[k - lo]
            ok = (u >= 0) & (u < n_bits)
            word = mask_ref[0, jnp.clip(u, 0, n_bits - 1) // 32]
            return k + 1, ok & (((word >> (u % 32)) & 1) != 0)

        _, hit = lax.while_loop(
            lambda st: (st[0] < e) & jnp.logical_not(st[1]), scan, (s, False)
        )
        return jnp.where(row_iota == j, hit.astype(jnp.int32), acc), lo, hi

    acc, _, _ = lax.fori_loop(
        0, tr, row, (jnp.zeros((1, tr), jnp.int32), jnp.int32(0), jnp.int32(0))
    )
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("deg_cap", "interpret", "row_tile"))
def csr_arc_sweep(
    seg_start: jnp.ndarray,  # [n_planes, n_t] int32 global offsets
    seg_len: jnp.ndarray,  # [n_planes, n_t] int32 row lengths
    indices: jnp.ndarray,  # [n_idx] int32 flat CSR columns (sentinel tail)
    arc_row: jnp.ndarray,  # [n_arcs] int32 plane index per arc
    masks: jnp.ndarray,  # [n_arcs, w] uint32 (D(q) bitmap per arc)
    *,
    deg_cap: int,
    interpret: bool,
    row_tile: int = ROW_TILE,
) -> jnp.ndarray:
    """All arcs of one CSR AC sweep in one kernel call (DESIGN.md §11).

    ``out[a, t] = any(u in row(arc_row[a], t) : bit u set in masks[a])`` —
    ``[n_arcs, n_t]`` int32 {0, 1}, the sparse twin of `arc_any_sweep`;
    each row is consumed for at most ``deg_cap`` entries.  Grid ``(n_arcs,
    row tiles)``; the per-plane ``seg_start`` / ``seg_len`` tiles and the
    arc's mask are SMEM blocks selected by the scalar-prefetched
    ``arc_row`` table, and ``indices`` stays in HBM, windowed into SMEM —
    dense adjacency bitmaps never exist.  Rows of one plane that are
    consecutive in ``indices`` (as CSR lays them out) share a window.
    Oracle: `repro.kernels.ref.csr_arc_sweep_ref`.
    """
    n_arcs, w = masks.shape
    n_planes, n_t = seg_start.shape
    wp = pad_words(w)
    tr = min(row_tile, max(8, ((n_t + 7) // 8) * 8))
    n_pad = ((n_t + tr - 1) // tr) * tr
    n_tiles = n_pad // tr
    sst_p = jnp.pad(seg_start, ((0, 0), (0, n_pad - n_t)))
    sln_p = jnp.pad(seg_len, ((0, 0), (0, n_pad - n_t)))  # pad rows: len 0
    masks_p = jnp.pad(masks, ((0, 0), (0, wp - w)))
    # the window covers any one row (deg_cap words plus its offset into its
    # first 1024-word line); the sentinel tail keeps every refill in bounds
    cap = max(8 * DMA_WORDS, -(-(deg_cap + DMA_WORDS) // DMA_WORDS) * DMA_WORDS)
    n_ind = -(-indices.shape[0] // DMA_WORDS) * DMA_WORDS + cap
    indices = jnp.pad(indices, (0, n_ind - indices.shape[0]),
                      constant_values=SENTINEL)

    def seg_map(a, i, arc_row_s):
        return (arc_row_s[a], i, 0, 0)

    def mask_map(a, i, arc_row_s):
        return (a, 0, 0)

    def out_map(a, i, arc_row_s):
        return (a, 0, i)

    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        functools.partial(_csr_sweep_kernel, deg_cap=deg_cap),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_arcs, n_tiles),
            in_specs=[
                smem((None, None, 1, tr), seg_map),  # seg_start tile
                smem((None, None, 1, tr), seg_map),  # seg_len tile
                smem((None, 1, wp), mask_map),  # the arc's D(q) bitmap
                pl.BlockSpec(memory_space=pltpu.HBM),  # flat CSR indices
            ],
            out_specs=pl.BlockSpec((None, 1, tr), out_map),
            scratch_shapes=[
                pltpu.SMEM((cap,), jnp.int32),
                pltpu.SemaphoreType.DMA(()),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n_arcs, 1, n_pad), jnp.int32),
        interpret=interpret,
    )(
        arc_row.astype(jnp.int32),
        sst_p.astype(jnp.int32).reshape(n_planes, n_tiles, 1, tr),
        sln_p.astype(jnp.int32).reshape(n_planes, n_tiles, 1, tr),
        lax.bitcast_convert_type(masks_p, jnp.int32)[:, None, :],
        indices,
    )
    return out[:, 0, :n_t]
