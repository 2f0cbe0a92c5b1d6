"""Pallas TPU kernel: per-row popcount of packed bitmaps.

Used for domain-size vectors (SI tie-breaking), candidate counting, and the
engine's match statistics.  Grid over row tiles; each step reduces a
``(tr, w)`` uint32 block to ``(tr, 1)`` int32 counts with the VPU popcount.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.kernels.candidate_mask import pad_words

ROW_TILE = 256


def _kernel(bits_ref, out_ref):
    out_ref[...] = jnp.sum(
        lax.population_count(bits_ref[...]).astype(jnp.int32),
        axis=-1,
        keepdims=True,
    )


@functools.partial(jax.jit, static_argnames=("interpret", "row_tile"))
def popcount_rows(
    bits: jnp.ndarray,  # [n, w] uint32
    *,
    interpret: bool,
    row_tile: int = ROW_TILE,
) -> jnp.ndarray:
    n, w = bits.shape
    wp = pad_words(w)
    tr = row_tile
    n_pad = ((n + tr - 1) // tr) * tr
    bits_p = jnp.pad(bits, ((0, n_pad - n), (0, wp - w)))
    out = pl.pallas_call(
        _kernel,
        grid=(n_pad // tr,),
        in_specs=[pl.BlockSpec((tr, wp), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tr, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, 1), jnp.int32),
        interpret=interpret,
    )(bits_p)
    return out[:n, 0]
