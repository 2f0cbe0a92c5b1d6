"""Pallas TPU kernel for the engine's hot loop: batched candidate bitmaps.

For a batch of ``b`` search lanes, compute

    cand[l] = dom_bits[pos[l]] ∧ ¬used[l] ∧ ⋀_j adj_rows[row_idx[l, j]]

over packed uint32 bitmaps of ``w`` words.  ``row_idx`` is the flattened
``(edge_label, direction, mapped_parent)`` adjacency row per parent-constraint
slot; unused slots point at a **neutral all-ones row** appended at index
``n_rows`` so the kernel body is branch-free.

TPU mapping
-----------
* Grid ``(b, mp + 1)`` — lane-major, then one step per parent slot plus one
  for the ``dom ∧ ¬used`` initialization.
* The row gathers are expressed through **scalar-prefetched index maps**
  (``pltpu.PrefetchScalarGridSpec``): the BlockSpec ``index_map`` for the
  adjacency operand reads ``row_idx`` to select which ``(1, w)`` row block the
  pipeline DMAs into VMEM next.  This is the TPU-native form of the paper's
  pointer-chasing adjacency-list walk: the DMA engine chases the indices
  while the VPU ANDs the previous row.
* Block shapes are ``(1, w)`` rows of ``[rows, 1, w]`` views with ``w``
  padded to a multiple of 128 lanes (uint32 words), so each AND is a
  full-width VPU op; the running candidate
  bitmap lives in the output block in VMEM across the ``mp`` grid steps
  (same output index for all j ⇒ accumulation without HBM round-trips).

VMEM footprint per grid step: 3 × w × 4 bytes (dom/used-or-row + out) —
≤ ~1.2 MB even for the largest paper target (33k nodes ⇒ w = 1034 → padded
1152 words ⇒ 4.6 KB/row); far below the ~16 MB VMEM budget, leaving the
pipeline free to double-buffer row DMAs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE_WORDS = 128  # pad w to a multiple of the 128-lane VPU width


def pad_words(w: int) -> int:
    return ((w + LANE_WORDS - 1) // LANE_WORDS) * LANE_WORDS


def _kernel(pos_ref, row_idx_ref, dom_ref, row_ref, used_ref, out_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = dom_ref[...] & ~used_ref[...]

    @pl.when(j > 0)
    def _and_row():
        out_ref[...] = out_ref[...] & row_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def candidate_mask(
    rows: jnp.ndarray,  # [n_rows + 1, w] uint32, last row all-ones
    dom_bits: jnp.ndarray,  # [p_pad, w] uint32
    pos: jnp.ndarray,  # [b] int32
    row_idx: jnp.ndarray,  # [b, mp] int32 (unused slots -> n_rows)
    used: jnp.ndarray,  # [b, w] uint32
    interpret: bool,
) -> jnp.ndarray:
    """Jit'd wrapper; pads the word dimension and invokes the kernel."""
    b, w = used.shape
    mp = row_idx.shape[1]
    wp = pad_words(w)
    if wp != w:
        padw = ((0, 0), (0, wp - w))
        rows = jnp.pad(rows, padw)
        dom_bits = jnp.pad(dom_bits, padw)
        used = jnp.pad(used, padw)

    grid = (b, mp + 1)

    # bitmaps viewed as [rows, 1, wp] and the row table flattened to 1-D,
    # as in `repro.kernels.extend_step`, so the chip's compiler accepts them
    def dom_map(l, j, pos_s, idx_s):
        return (pos_s[l], 0, 0)

    def row_map(l, j, pos_s, idx_s):
        # j == 0 is the init step; feed the neutral row (index n_rows).
        jj = jnp.maximum(j - 1, 0)
        return (jnp.where(j == 0, rows.shape[0] - 1, idx_s[l * mp + jj]), 0, 0)

    def lane_map(l, j, pos_s, idx_s):
        return (l, 0, 0)

    bitmap = functools.partial(pl.BlockSpec, (None, 1, wp))
    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[bitmap(dom_map), bitmap(row_map), bitmap(lane_map)],
            out_specs=bitmap(lane_map),
        ),
        out_shape=jax.ShapeDtypeStruct((b, 1, wp), jnp.uint32),
        interpret=interpret,
    )(
        pos.astype(jnp.int32),
        row_idx.astype(jnp.int32).reshape(b * mp),
        dom_bits[:, None, :],
        rows[:, None, :],
        used[:, None, :],
    )
    return out[:, 0, :w]


def flatten_adj_rows(adj_bits: jnp.ndarray) -> jnp.ndarray:
    """``[n_elab, 2, n_t, w] -> [n_elab * 2 * n_t + 1, w]`` with a trailing
    all-ones neutral row (AND-identity) for padded parent slots."""
    ne, two, n_t, w = adj_bits.shape
    flat = adj_bits.reshape(ne * two * n_t, w)
    ones = jnp.full((1, w), jnp.uint32(0xFFFFFFFF))
    return jnp.concatenate([flat, ones], axis=0)


def flat_row_index(
    parent_pos: jnp.ndarray,  # [mp] int32 (-1 padded)
    parent_dir: jnp.ndarray,
    parent_elab: jnp.ndarray,
    mapping: jnp.ndarray,  # [p_pad] int32
    n_t: int,
    n_rows: int,
) -> jnp.ndarray:
    """Per-lane flattened adjacency row indices for `candidate_mask`."""
    t = jnp.where(parent_pos >= 0, mapping[jnp.maximum(parent_pos, 0)], 0)
    idx = (parent_elab * 2 + parent_dir) * n_t + jnp.clip(t, 0, n_t - 1)
    return jnp.where(parent_pos >= 0, idx, n_rows).astype(jnp.int32)
