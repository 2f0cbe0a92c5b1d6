"""Fused Pallas TPU kernel for the engine's whole expansion step
(DESIGN.md §6.3).

For a batch of ``b`` popped search lanes, one ``pallas_call`` performs
everything ``w``-wide the step needs:

1. **lowest-untried-bit extraction** — find the lowest set bit ``v`` of
   the lane's candidate bitmap, clear it (the parent's residual
   ``cand2``), and form its one-hot mask;
2. **child candidate initialization** — ``dom[pos+1] ∧ ¬used ∧ ¬bit(v)``
   (``used ∨ bit(v)`` is the child's used-set, so its complement is one
   fused AND);
3. **parent-constraint AND-tree** — one grid step per parent slot ANDs the
   flattened adjacency row chosen by the scalar-prefetched ``row_idx``
   table (unused slots point at a neutral all-ones row);
4. **match / child flagging** — at the finalize step, compare depth
   against the pattern size, zero the child bitmap unless a child is
   wanted, and emit per-lane ``(valid, v, is_match, has_child)`` flags the
   driver accumulates into its per-worker counters.

The loose-ops jnp step (`repro.core.extend.JnpStepBackend`) round-trips
each of these phases through HBM; here the lane's bitmaps stay in VMEM
across all ``mp + 2`` grid steps.

TPU mapping
-----------
* Grid ``(b, mp + 2)`` — lane-major: step 0 extracts + initializes, steps
  ``1..mp`` AND one prefetch-indexed adjacency row each, step ``mp + 1``
  finalizes.  Same-lane output blocks keep the same index for every ``j``,
  so the running bitmaps accumulate in VMEM without HBM round-trips
  (the `repro.kernels.candidate_mask` trick, extended to the whole step).
* The adjacency operand's ``index_map`` reads the scalar-prefetched
  ``row_idx`` table — the DMA engine chases the paper's adjacency-list
  pointers while the VPU processes the previous row.  ``row_idx`` is
  computed by the backend *before* launch (scalar prefetch requires it);
  it encodes the freshly mapped ``v`` for parent constraints that
  reference the just-extended position.
* Blocks are ``(1, wp)`` with ``wp = pad_words(w)`` (128-lane multiples);
  per grid step the kernel touches ≤ 5 such rows (cand/used/dom/row +
  out) — ≤ ~23 KB at the largest paper target — far below VMEM, leaving
  the pipeline free to double-buffer row DMAs.

Oracle: `repro.kernels.ref.extend_step_ref` (bit-exact, swept in
``tests/test_extend_step.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.candidate_mask import pad_words

WORD_BITS = 32
META_WIDTH = 4  # (valid, v, is_match, has_child) per lane


def _lowest_bit(c: jnp.ndarray):
    """Lowest set bit of a ``[1, wp]`` uint32 block.

    Returns ``(valid, v, vmask)``: a scalar flag, the global bit index
    (garbage when ``!valid`` — callers gate on ``valid``), and the one-hot
    ``[1, wp]`` mask of the bit (all-zero when ``!valid``).  Every
    reduction runs over int32 words and the popcount stays a vector op:
    the TPU lowering has neither unsigned reductions nor scalar popcounts.
    """
    wp = c.shape[1]
    iota = lax.broadcasted_iota(jnp.int32, c.shape, 1)
    widx = jnp.min(jnp.where(c != jnp.uint32(0), iota, wp))  # first non-zero word
    valid = widx < wp
    sel = iota == widx
    tz_words = lax.population_count(~c & (c - jnp.uint32(1))).astype(jnp.int32)
    tz = jnp.sum(jnp.where(sel, tz_words, 0))
    v = widx * WORD_BITS + tz
    vmask = jnp.where(sel, c & (~c + jnp.uint32(1)), jnp.uint32(0))
    return valid, v, vmask


def _kernel(
    cpos_ref, ridx_ref, depth_ref, np_ref,  # scalar prefetch
    cand_ref, used_ref, dom_ref, row_ref,  # operands
    cand2_ref, child_ref, meta_ref,  # outputs
    *, mp: int,
):
    l = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _extract_and_init():
        c = cand_ref[...]
        _valid, _v, vmask = _lowest_bit(c)
        cand2_ref[...] = c ^ vmask
        # child used-set is used ∨ bit(v); its complement fuses into the init
        child_ref[...] = dom_ref[...] & ~used_ref[...] & ~vmask

    @pl.when((j >= 1) & (j <= mp))
    def _and_parent_row():
        child_ref[...] = child_ref[...] & row_ref[...]

    @pl.when(j == mp + 1)
    def _finalize():
        valid, v, _vmask = _lowest_bit(cand_ref[...])
        depth = depth_ref[l]
        n_p = np_ref[0]
        is_match = valid & (depth + 1 >= n_p)
        want_child = valid & jnp.logical_not(is_match)
        child = jnp.where(want_child, child_ref[...], jnp.uint32(0))
        child_ref[...] = child
        has_child = want_child & jnp.any(child != jnp.uint32(0))
        meta_ref[...] = jnp.stack(
            [
                valid.astype(jnp.int32),
                jnp.where(valid, v, -1),
                is_match.astype(jnp.int32),
                has_child.astype(jnp.int32),
            ]
        ).reshape(1, META_WIDTH)


@functools.partial(jax.jit, static_argnames=("interpret",))
def extend_step(
    rows: jnp.ndarray,  # [n_rows + 1, w] uint32, last row all-ones neutral
    dom_bits: jnp.ndarray,  # [p_pad, w] uint32
    child_pos: jnp.ndarray,  # [b] int32 order position of the child
    row_idx: jnp.ndarray,  # [b, mp] int32 (unused slots -> n_rows)
    depth: jnp.ndarray,  # [b] int32 depth of the popped entry
    n_p: jnp.ndarray,  # scalar int32 actual pattern size
    used: jnp.ndarray,  # [b, w] uint32
    cand: jnp.ndarray,  # [b, w] uint32
    interpret: bool,
):
    """One fused expansion over ``b`` lanes.

    Returns ``(cand2 [b, w], child_cand [b, w], meta [b, 4] int32)`` with
    ``meta`` columns ``(valid, v, is_match, has_child)``; ``v`` is -1 on
    invalid lanes.  ``interpret`` comes from
    `repro.kernels.ops.resolve_interpret`: compiled on a TPU, the Pallas
    interpreter anywhere else.
    """
    b, w = cand.shape
    mp = row_idx.shape[1]
    n_rows = rows.shape[0] - 1
    if mp == 0:  # degenerate plans: keep one neutral parent slot
        row_idx = jnp.full((b, 1), n_rows, jnp.int32)
        mp = 1
    wp = pad_words(w)
    if wp != w:
        padw = ((0, 0), (0, wp - w))
        rows = jnp.pad(rows, padw)
        dom_bits = jnp.pad(dom_bits, padw)
        used = jnp.pad(used, padw)
        cand = jnp.pad(cand, padw)

    grid = (b, mp + 2)

    # Every bitmap is viewed as [rows, 1, wp] and blocked (squeezed, 1, wp):
    # a (1, wp) block of a [b, wp] array breaks the TPU rule that a block's
    # last two dims tile by (8, 128) or span the array.  The row table is
    # flattened to 1-D, since SMEM pads a 2-D table's minor dim to 128.
    def lane_map(l, j, cpos_s, ridx_s, depth_s, np_s):
        return (l, 0, 0)

    def dom_map(l, j, cpos_s, ridx_s, depth_s, np_s):
        return (cpos_s[l], 0, 0)

    def row_map(l, j, cpos_s, ridx_s, depth_s, np_s):
        # j == 0 init and j == mp + 1 finalize get the neutral row
        jj = jnp.clip(j - 1, 0, mp - 1)
        take = (j >= 1) & (j <= mp)
        return (jnp.where(take, ridx_s[l * mp + jj], n_rows), 0, 0)

    bitmap = functools.partial(pl.BlockSpec, (None, 1, wp))
    cand2, child, meta = pl.pallas_call(
        functools.partial(_kernel, mp=mp),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=[
                bitmap(lane_map),  # cand
                bitmap(lane_map),  # used
                bitmap(dom_map),  # dom_bits
                bitmap(row_map),  # adjacency rows
            ],
            out_specs=[
                bitmap(lane_map),  # cand2
                bitmap(lane_map),  # child_cand
                pl.BlockSpec((None, 1, META_WIDTH), lane_map),  # meta
            ],
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b, 1, wp), jnp.uint32),
            jax.ShapeDtypeStruct((b, 1, wp), jnp.uint32),
            jax.ShapeDtypeStruct((b, 1, META_WIDTH), jnp.int32),
        ),
        interpret=interpret,
    )(
        child_pos.astype(jnp.int32),
        row_idx.astype(jnp.int32).reshape(b * mp),
        depth.astype(jnp.int32),
        jnp.asarray(n_p, jnp.int32).reshape((1,)),
        cand[:, None, :],
        used[:, None, :],
        dom_bits[:, None, :],
        rows[:, None, :],
    )
    return cand2[:, 0, :w], child[:, 0, :w], meta[:, 0, :]
