"""Pallas TPU kernel for the sparse CSR expansion step (DESIGN.md §6.4).

The dense fused step (`repro.kernels.extend_step`) ANDs whole ``[w]``-word
adjacency bitmap rows — ``O(n_planes · n_t · w)`` resident words, which
stops scaling past the paper's 33k-node targets.  This kernel walks the
**CSR adjacency planes** instead: for each popped lane it

1. extracts the lowest untried candidate bit ``v`` in-register (the same
   ``cand2`` / fused ``¬(used ∨ bit(v))`` child init as the dense kernel);
2. copies every real parent's neighbor segment from the flat ``indices``
   array in HBM into SMEM — the segment bounds arrive through **scalar
   prefetch** (the backend gathers ``indptr[plane, t]`` /
   ``indptr[plane, t + 1]`` per lane before launch, the same
   row-bounds-ahead-of-data pattern the dense kernel uses for row ids);
3. sorted-intersects on the scalar unit: each neighbor of the **driver**
   (first real) parent survives iff a binary search finds it in every
   other real parent's sorted segment;
4. ORs the survivors into a ``[1, wp]`` bitmap on the VPU, ANDs it with
   ``dom ∧ ¬used'`` and emits the ``(valid, v, is_match, has_child)`` meta
   row.

TPU mapping
-----------
* Grid ``(b,)`` — one step per lane.  Lanes that want no child (invalid,
  or a completed match) and parentless lanes skip steps 2–3.
* Segments are copied in DMAs of ``granule`` words from 1024-word-aligned
  HBM offsets into an SMEM buffer of ``mp`` slots.  :func:`csr_extend`
  copies each segment in one ``deg_cap``-wide DMA; the degree-bucketed
  :func:`csr_extend_bucketed` (DESIGN.md §10) issues only as many
  ``chunk``-word DMAs as the row's own length needs, so tail rows of a
  hub-heavy target cost ``O(chunk)`` instead of ``O(deg_cap)``.  The
  result is identical either way.
* The survivor scatter is a loop of full-width VPU ORs of one-hot words
  (TPU kernels have no vector gather or scatter); the SMEM survivor list
  holds at most ``deg_cap`` entries.

Oracles: `repro.kernels.ref.csr_extend_ref` and
`repro.kernels.ref.csr_extend_bucketed_ref` (bit-exact — the refs are
also the ``CsrStepBackend``'s jnp compute path, so kernel-vs-oracle
equality is exactly kernel-vs-engine equality).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.candidate_mask import pad_words
from repro.kernels.extend_step import META_WIDTH, WORD_BITS, _lowest_bit

# python int (not a jnp scalar: pallas kernels must not capture traced
# constants); fits int32 and exceeds every node id, so sentinel-masked
# segments stay sorted.
SENTINEL = 2**31 - 1
# HBM tiles a 1-D int32 array in 1024-word lines; a DMA source slice must
# start and end on a line boundary.
DMA_WORDS = 1024


def _contains(buf_ref, lo, n, u, iters: int):
    """Whether ``u`` is in the sorted SMEM run ``buf_ref[lo : lo + n]`` —
    a branchless lower-bound search of ``iters`` halvings."""

    last = jnp.maximum(n - 1, 0)  # reads stay inside the run even if empty

    def step(_, lh):
        a, b = lh
        mid = (a + b) >> 1
        go = (a < b) & (buf_ref[lo + jnp.clip(mid, 0, last)] < u)
        return jnp.where(go, mid + 1, a), jnp.where((a < b) & ~go, mid, b)

    a, _ = lax.fori_loop(0, iters, step, (jnp.int32(0), n))
    return (a < n) & (buf_ref[lo + jnp.clip(a, 0, last)] == u)


def _kernel(
    cpos_ref, sst_ref, sln_ref, depth_ref, np_ref,  # scalar prefetch
    cand_ref, used_ref, dom_ref, ind_hbm,  # operands
    cand2_ref, child_ref, meta_ref,  # outputs
    seg_buf, surv_buf, sem,  # scratch
    *, mp: int, deg_cap: int, granule: int, span: int,
):
    l = pl.program_id(0)
    wp = cand_ref.shape[1]

    c = cand_ref[...]
    valid, v, vmask = _lowest_bit(c)
    cand2_ref[...] = c ^ vmask
    base = dom_ref[...] & ~used_ref[...] & ~vmask  # [1, wp]

    lens = [jnp.minimum(sln_ref[l * mp + j], deg_cap) for j in range(mp)]
    real = [n >= 0 for n in lens]
    has_parent = functools.reduce(jnp.logical_or, real)
    depth = depth_ref[l]
    n_p = np_ref[0]
    is_match = valid & (depth + 1 >= n_p)
    want_child = valid & jnp.logical_not(is_match)
    child_ref[...] = jnp.where(want_child, base, jnp.uint32(0))

    @pl.when(want_child & has_parent)
    def _walk():
        # -- copy each real parent's segment into its SMEM slot ------------
        starts, offs, trips = [], [], []
        for j in range(mp):
            s = sst_ref[l * mp + j]
            a = (s // DMA_WORDS) * DMA_WORDS
            starts.append(a)
            offs.append(s - a)
            trips.append(jnp.where(
                real[j], (s - a + lens[j] + granule - 1) // granule, 0))

        def copy(j, i):
            return pltpu.make_async_copy(
                ind_hbm.at[pl.ds(starts[j] + i * granule, granule)],
                seg_buf.at[pl.ds(j * span + i * granule, granule)],
                sem.at[j],
            )

        def start(j, i, carry):
            copy(j, i).start()
            return carry

        def wait(j, i, carry):
            copy(j, i).wait()
            return carry

        for j in range(mp):
            lax.fori_loop(0, trips[j], functools.partial(start, j), 0)
        for j in range(mp):
            lax.fori_loop(0, trips[j], functools.partial(wait, j), 0)

        # -- driver = first real parent; intersect on the scalar unit ------
        d = jnp.int32(mp - 1)
        for j in reversed(range(mp - 1)):
            d = jnp.where(real[j], j, d)
        d_lo = d * span + sum(jnp.where(d == j, offs[j], 0) for j in range(mp))
        d_len = sum(jnp.where(d == j, lens[j], 0) for j in range(mp))
        iters = max(1, deg_cap).bit_length() + 1

        def visit(k, n_surv):
            u = seg_buf[d_lo + k]
            ok = (u >= 0) & (u < wp * WORD_BITS)
            for j in range(mp):
                skip = jnp.logical_not(real[j]) | (j == d)
                hit = _contains(seg_buf, j * span + offs[j], lens[j], u, iters)
                ok = ok & (skip | hit)
            surv_buf[n_surv] = u
            return n_surv + ok.astype(jnp.int32)

        n_surv = lax.fori_loop(0, d_len, visit, jnp.int32(0))

        # -- OR the survivors' one-hot words into the child bitmap ---------
        iota = lax.broadcasted_iota(jnp.int32, (1, wp), 1)

        def scatter(i, acc):
            u = surv_buf[i]
            return acc | jnp.where(iota == u // WORD_BITS,
                                   jnp.left_shift(1, u % WORD_BITS), 0)

        walked = lax.fori_loop(0, n_surv, scatter, jnp.zeros((1, wp), jnp.int32))
        child_ref[...] = base & lax.bitcast_convert_type(walked, jnp.uint32)

    has_child = want_child & jnp.any(child_ref[...] != jnp.uint32(0))
    meta_ref[...] = jnp.stack(
        [
            valid.astype(jnp.int32),
            jnp.where(valid, v, -1),
            is_match.astype(jnp.int32),
            has_child.astype(jnp.int32),
        ]
    ).reshape(1, META_WIDTH)


def _walk(indices, dom_bits, seg_start, seg_len, child_pos, depth, n_p, used,
          cand, *, deg_cap: int, granule: int, interpret: bool):
    b, w = cand.shape
    mp = seg_len.shape[1]
    if mp == 0:  # degenerate plans: keep one neutral (unused) parent slot
        seg_start = jnp.zeros((b, 1), jnp.int32)
        seg_len = jnp.full((b, 1), -1, jnp.int32)
        mp = 1
    wp = pad_words(w)
    if wp != w:
        padw = ((0, 0), (0, wp - w))
        dom_bits = jnp.pad(dom_bits, padw)
        used = jnp.pad(used, padw)
        cand = jnp.pad(cand, padw)
    # a slot holds a segment of up to deg_cap words plus its offset into
    # its first 1024-word line, rounded up to whole DMAs
    span = -(-(deg_cap + DMA_WORDS) // granule) * granule
    n_ind = -(-indices.shape[0] // DMA_WORDS) * DMA_WORDS + span
    indices = jnp.pad(indices, (0, n_ind - indices.shape[0]),
                      constant_values=SENTINEL)

    # bitmaps are viewed as [rows, 1, wp] and blocked (squeezed, 1, wp), and
    # the segment tables flattened to 1-D, as in extend_step
    def lane_map(l, cpos_s, sst_s, sln_s, depth_s, np_s):
        return (l, 0, 0)

    def dom_map(l, cpos_s, sst_s, sln_s, depth_s, np_s):
        return (cpos_s[l], 0, 0)

    bitmap = functools.partial(pl.BlockSpec, (None, 1, wp))
    cand2, child, meta = pl.pallas_call(
        functools.partial(_kernel, mp=mp, deg_cap=deg_cap, granule=granule,
                          span=span),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(b,),
            in_specs=[
                bitmap(lane_map),  # cand
                bitmap(lane_map),  # used
                bitmap(dom_map),  # dom_bits
                pl.BlockSpec(memory_space=pltpu.HBM),  # flat CSR indices
            ],
            out_specs=[
                bitmap(lane_map),  # cand2
                bitmap(lane_map),  # child_cand
                pl.BlockSpec((None, 1, META_WIDTH), lane_map),  # meta
            ],
            scratch_shapes=[
                pltpu.SMEM((mp * span,), jnp.int32),  # parent segments
                pltpu.SMEM((max(deg_cap, 1),), jnp.int32),  # survivors
                pltpu.SemaphoreType.DMA((mp,)),
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
        seg_start.astype(jnp.int32).reshape(b * mp),
        seg_len.astype(jnp.int32).reshape(b * mp),
        depth.astype(jnp.int32),
        jnp.asarray(n_p, jnp.int32).reshape((1,)),
        cand[:, None, :],
        used[:, None, :],
        dom_bits[:, None, :],
        indices,
    )
    return cand2[:, 0, :w], child[:, 0, :w], meta[:, 0, :]


@functools.partial(jax.jit, static_argnames=("deg_cap", "interpret"))
def csr_extend(
    indices: jnp.ndarray,  # [nnz_pad + deg_cap] int32 flat CSR columns
    dom_bits: jnp.ndarray,  # [p_pad, w] uint32
    seg_start: jnp.ndarray,  # [b, mp] int32 global segment offsets
    seg_len: jnp.ndarray,  # [b, mp] int32 (-1 on unused parent slots)
    child_pos: jnp.ndarray,  # [b] int32 order position of the child
    depth: jnp.ndarray,  # [b] int32 depth of the popped entry
    n_p: jnp.ndarray,  # scalar int32 actual pattern size
    used: jnp.ndarray,  # [b, w] uint32
    cand: jnp.ndarray,  # [b, w] uint32
    *,
    deg_cap: int,
    interpret: bool,
):
    """One sparse fused expansion over ``b`` lanes.

    Same contract as `repro.kernels.extend_step.extend_step` with the
    scalar-prefetched row-id table replaced by per-parent CSR segment
    bounds: returns ``(cand2 [b, w], child_cand [b, w], meta [b, 4])``,
    ``meta`` columns ``(valid, v, is_match, has_child)``.  Segments are
    sorted and at most ``deg_cap`` long; each is copied in one
    ``deg_cap``-wide DMA.
    """
    granule = -(-(deg_cap + DMA_WORDS) // DMA_WORDS) * DMA_WORDS
    return _walk(indices, dom_bits, seg_start, seg_len, child_pos, depth,
                 n_p, used, cand, deg_cap=deg_cap, granule=granule,
                 interpret=interpret)


@functools.partial(jax.jit, static_argnames=("deg_cap", "chunk", "interpret"))
def csr_extend_bucketed(
    indices: jnp.ndarray,  # [nnz_pad + deg_cap] int32 flat CSR columns
    dom_bits: jnp.ndarray,  # [p_pad, w] uint32
    seg_start: jnp.ndarray,  # [b, mp] int32 global segment offsets
    seg_len: jnp.ndarray,  # [b, mp] int32 (-1 on unused parent slots)
    child_pos: jnp.ndarray,  # [b] int32 order position of the child
    depth: jnp.ndarray,  # [b] int32 depth of the popped entry
    n_p: jnp.ndarray,  # scalar int32 actual pattern size
    used: jnp.ndarray,  # [b, w] uint32
    cand: jnp.ndarray,  # [b, w] uint32
    *,
    deg_cap: int,
    interpret: bool,
    chunk: int = DMA_WORDS,
):
    """Bucketed sparse fused expansion over ``b`` lanes (DESIGN.md §10).

    Identical contract and results to :func:`csr_extend`; only the copy
    schedule differs — each segment moves in ``chunk``-word DMAs (a
    multiple of 1024), as many as the row's own length needs, so tail rows
    cost ``O(chunk)`` instead of the global hub-sized ``deg_cap``.
    Oracle: `repro.kernels.ref.csr_extend_bucketed_ref`.
    """
    return _walk(indices, dom_bits, seg_start, seg_len, child_pos, depth,
                 n_p, used, cand, deg_cap=deg_cap,
                 granule=-(-chunk // DMA_WORDS) * DMA_WORDS, interpret=interpret)
