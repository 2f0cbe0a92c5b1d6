"""Peaks of each chip and the least work of each kernel call.

``PEAKS`` is keyed by ``jax.Device.device_kind``; a kind that is not in it
is an error, never a default.  Source of the v5e numbers: Google Cloud
documentation, "TPU v5e" (per chip: 197 TFLOP/s bf16, 393 TOP/s int8,
16 GB HBM at 819 GB/s).

The work functions count what the algorithm requires of one kernel call,
from the call's static shapes and the target's, so a later kernel that
computes the same outputs is read against the same yardstick.  The CSR
segment words are counted from the target's static sizes: a sweep reads
every segment of the arc's plane (a row's walk may stop at its first
supported neighbour, which only rows with support can do), and an extend
lane reads its driver parent's segment, counted at the plane's mean
segment length.  Both kernels move 32-bit words and do a few integer
operations per word, so the HBM bandwidth bound is the one that applies;
the operation bound is counted against the int8 peak, the highest
integer rate the chip publishes, and comes out far below the byte bound.
"""

from __future__ import annotations

from typing import Dict

WORD = 4  # bytes of an int32 / uint32 word

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
                    "int8_ops_per_s": 393e12, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def csr_extend_work(b: int, w: int, mp: int, nnz_plane: int,
                    n_t: int) -> Dict[str, float]:
    """One ``csr_extend`` / ``csr_extend_bucketed`` call over ``b`` lanes of
    ``w``-word bitmaps, with ``mp`` parent slots per lane, on a target of
    ``n_t`` nodes whose adjacency planes hold ``nnz_plane`` words each.

    Required per lane: look up its parents' segment bounds (start and
    length, ``2 mp`` words), read its driver parent's segment (the plane's
    mean, ``nnz_plane / n_t`` words), read its candidate row and its used
    row (``2 w`` words), write the remaining candidates and the child's
    candidates (``2 w`` words) and the 4-word meta row.  The pattern's
    domain rows are read once per call and left out (under a thousandth
    of the rest).  Operations: the child row is ``dom & ~used & ~bit`` and
    the rest ``cand & ~bit`` (3 word operations per word of the child, 1
    per word of the rest), and one per segment word.
    """
    seg = nnz_plane / n_t
    return {"bytes": WORD * b * (2 * mp + seg + 4 * w + 4),
            "ops": b * (4 * w + seg)}


def csr_arc_sweep_work(n_arcs: int, n_planes: int, n_t: int, w: int,
                       nnz_plane: int) -> Dict[str, int]:
    """One ``csr_arc_sweep`` call over ``n_arcs`` arcs of an ``n_t``-node
    target with ``n_planes`` adjacency planes of ``nnz_plane`` words each.

    Required: read each plane's row start and length tables once
    (``2 n_t`` words a plane); for each arc, read every segment of its
    plane (``nnz_plane`` words) and its mask (``w`` words), and write its
    support flags for the target's rows, packed (``w`` words).
    Operations: one test per segment word and arc.
    """
    return {"bytes": WORD * (2 * n_planes * n_t
                             + n_arcs * (nnz_plane + 2 * w)),
            "ops": n_arcs * nnz_plane}


def least_seconds(work: Dict[str, int], device_kind: str) -> float:
    p = peaks(device_kind)
    return max(work["bytes"] / p["hbm_bytes_per_s"],
               work["ops"] / p["int8_ops_per_s"])
