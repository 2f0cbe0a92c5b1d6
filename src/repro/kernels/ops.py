"""Jit'd public wrappers around the Pallas kernels.

Every wrapper resolves its execution mode through one helper,
:func:`resolve_interpret`: on a TPU backend the kernels lower compiled,
anywhere else they run in interpret mode (the kernel body executes as
Python/jnp — validation, not speed).  Nothing overrides that choice, so a
run on the chip can take no interpret path.
"""

from __future__ import annotations

import jax

from repro.kernels import candidate_mask as _cm
from repro.kernels import csr_extend as _ce
from repro.kernels import domain_ac as _ac
from repro.kernels import extend_step as _es
from repro.kernels import popcount_reduce as _pc
from repro.kernels import ref as kref


def resolve_interpret() -> bool:
    """The one interpret-mode decision point for every kernel wrapper:
    interpret iff the default backend is not a TPU."""
    return jax.default_backend() != "tpu"


def candidate_mask(rows, dom_bits, pos, row_idx, used):
    """See `repro.kernels.candidate_mask.candidate_mask`."""
    return _cm.candidate_mask(
        rows, dom_bits, pos, row_idx, used, interpret=resolve_interpret()
    )


def extend_step(rows, dom_bits, child_pos, row_idx, depth, n_p, used, cand):
    """See `repro.kernels.extend_step.extend_step` (the fused engine step)."""
    return _es.extend_step(
        rows, dom_bits, child_pos, row_idx, depth, n_p, used, cand,
        interpret=resolve_interpret(),
    )


def csr_extend(indices, dom_bits, seg_start, seg_len, child_pos, depth, n_p,
               used, cand, deg_cap=8):
    """See `repro.kernels.csr_extend.csr_extend` (the sparse engine step)."""
    return _ce.csr_extend(
        indices, dom_bits, seg_start, seg_len, child_pos, depth, n_p,
        used, cand, deg_cap=deg_cap, interpret=resolve_interpret(),
    )


def csr_extend_bucketed(indices, dom_bits, seg_start, seg_len, child_pos, depth,
                        n_p, used, cand, deg_cap=8):
    """See `repro.kernels.csr_extend.csr_extend_bucketed` (the degree-bucketed
    sparse engine step, DESIGN.md §10)."""
    return _ce.csr_extend_bucketed(
        indices, dom_bits, seg_start, seg_len, child_pos, depth, n_p,
        used, cand, deg_cap=deg_cap, interpret=resolve_interpret(),
    )


def adjacency_any(rows, mask):
    """See `repro.kernels.domain_ac.adjacency_any`."""
    return _ac.adjacency_any(rows, mask, interpret=resolve_interpret())


def arc_any_sweep(adj_flat, arc_row, masks):
    """See `repro.kernels.domain_ac.arc_any_sweep`."""
    return _ac.arc_any_sweep(
        adj_flat, arc_row, masks, interpret=resolve_interpret()
    )


def csr_arc_sweep(seg_start, seg_len, indices, arc_row, masks, deg_cap=8):
    """See `repro.kernels.domain_ac.csr_arc_sweep` (the sparse AC sweep)."""
    return _ac.csr_arc_sweep(
        seg_start, seg_len, indices, arc_row, masks, deg_cap=deg_cap,
        interpret=resolve_interpret(),
    )


def popcount_rows(bits):
    """See `repro.kernels.popcount_reduce.popcount_rows`."""
    return _pc.popcount_rows(bits, interpret=resolve_interpret())


flatten_adj_rows = _cm.flatten_adj_rows
flat_row_index = _cm.flat_row_index
pack_bits = kref.pack_bits_ref
