"""Pallas TPU kernel: dense sliding-window reduction.

The stream-analytics hot path reduces every length-W window of a
[T, D] sensor block (``repro.stream.windows``).  The jnp oracle frames
the block into a [NW, W, D] gather — W-fold memory amplification and a
strided gather the TPU hates.  The kernel form keeps the input rows
VMEM-resident (BlockSpec pins the whole row range per lane tile, the
same "hot set in the fast tier" rule as ``armatch``) and sweeps the
window as W static row-shifted accumulations: each step is one [BR, 128]
VPU add/max over a contiguous slice — no gather, no amplification.

Stride-1 windows only; arbitrary stride is a row slice of the stride-1
result (see ``ops.window_reduce``).  Masking is handled by the caller
filling invalid rows with the reduction identity, so the kernel stays a
pure dense reduction.

VMEM: the whole [R, 128] row range of one lane tile must fit on chip
(R * 512 bytes).  For v5e the compiler accepts 65,536 and 98,304 rows
and refuses 131,072 (out of VMEM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_ROWS = 8     # f32 sublane tile
LANES = 128

_OPS = ("sum", "max", "min")


def _kernel(x_ref, o_ref, *, window: int, block_rows: int, op: str):
    """x_ref: [R, 128] (full rows, one lane tile); o_ref: [BR, 128]."""
    base = pl.program_id(0) * block_rows
    acc = x_ref[pl.ds(base, block_rows), :]
    for w in range(1, window):
        nxt = x_ref[pl.ds(base + w, block_rows), :]
        if op == "sum":
            acc = acc + nxt
        elif op == "max":
            acc = jnp.maximum(acc, nxt)
        else:
            acc = jnp.minimum(acc, nxt)
    o_ref[...] = acc


def sliding_reduce_2d(x2d: jnp.ndarray, window: int, *, op: str = "sum",
                      block_rows: int = BLOCK_ROWS,
                      interpret: bool = False) -> jnp.ndarray:
    """Stride-1 windowed reduction: [R, L] f32 -> [R - window + 1, L].

    L % 128 == 0 and (R - window + 1) % block_rows == 0 (callers pad
    with the reduction identity, see ops.py).
    """
    r, l = x2d.shape
    n_out = r - window + 1
    assert op in _OPS, op
    assert window >= 1 and n_out > 0, (r, window)
    assert l % LANES == 0 and n_out % block_rows == 0, (r, l, block_rows)
    grid = (n_out // block_rows, l // LANES)
    return pl.pallas_call(
        functools.partial(_kernel, window=window, block_rows=block_rows,
                          op=op),
        grid=grid,
        in_specs=[pl.BlockSpec((r, LANES), lambda i, j: (0, j))],
        out_specs=pl.BlockSpec((block_rows, LANES), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n_out, l), x2d.dtype,
                                       vma=jax.typeof(x2d).vma),
        interpret=interpret,
    )(x2d)
