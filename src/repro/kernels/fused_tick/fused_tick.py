"""Pallas TPU kernel: the fused stream-tick hot path.

The staged executor runs the per-tick inner loop as separate XLA ops —
a mean ``window_reduce`` over the D feature columns, a second framing
for the 5 rule features of the signal column, a third ``min`` framing
for the lineage birth stamp, then the rule-predicate sweep — each one
a full HBM round trip over the same [T, 1+D] block.  This kernel does
all of it in ONE VMEM-resident pass: per lane tile the whole row range
stays on chip (R * 512 bytes, the ``window_reduce`` sizing rule — plus
one mask tile) and a single W-step row sweep accumulates sum, max, min
and count *simultaneously*, with the rule table applied elementwise to
the finished accumulators before anything leaves VMEM.

Masked-rows-as-identity contract, same as ``window_reduce``: invalid
rows contribute the reduction identity (0 / finfo.min / finfo.max / 0)
— but the select happens *in kernel* from a validity tile, so one
input buffer serves all four reductions instead of three
identity-filled copies.

Rule evaluation is a static comparison table
(``RuleEngine.table()``: ``(feature_idx, op, value, consequence)`` in
application order).  Each row's five features are pure elementwise
functions of the accumulators (mean = sum/max(count,1), max/min with
empty windows forced to 0, sum, count), so the conflict-set sweep —
lowest precedence first, condition overwrites — runs elementwise on
every lane; the wrapper slices the signal lane.  Windows below
``min_count`` are forced to consequence 0 (``C_NONE``) in kernel.

Stride-1 windows only; arbitrary stride is a row slice of the stride-1
result (see ``ops.fused_tick``).  Accumulation order is the same
sequential left-to-right sweep as ``window_reduce`` and
``windows._seq_combine``, so the jnp oracle matches bit-for-bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_ROWS = 8     # f32 sublane tile
LANES = 128

F32_MIN = float(jnp.finfo(jnp.float32).min)
F32_MAX = float(jnp.finfo(jnp.float32).max)

#: rule comparison ops the table may carry (jnp closures are
#: elementwise, so the same lambda serves kernel and oracle)
_CMP = {
    ">=": lambda f, v: f >= v,
    ">":  lambda f, v: f > v,
    "<=": lambda f, v: f <= v,
    "<":  lambda f, v: f < v,
    "==": lambda f, v: f == v,
}


def rule_sweep(s, mx, mn, c, table, min_count: int):
    """Conflict-set resolution on accumulator arrays, elementwise.

    ``s``/``mx``/``mn``/``c`` are same-shape f32 arrays (per-window
    sum, masked max/min already forced to 0 when empty, valid count);
    ``table`` is ``RuleEngine.table()`` output.  Returns the f32
    consequence codes — identical op sequence inside the kernel and in
    the jnp/numpy oracles, so all paths agree bit-for-bit."""
    cf = jnp.maximum(c, 1.0)
    feats = (s / cf, mx, mn, s, c)       # F_MEAN..F_COUNT column order
    cons = jnp.zeros_like(s)             # C_NONE
    for fi, op, value, code in table:    # lowest precedence first
        cond = _CMP[op](feats[fi], value)
        cons = jnp.where(cond, jnp.float32(code), cons)
    return jnp.where(c >= min_count, cons, 0.0)


def _kernel(x_ref, v_ref, s_ref, mx_ref, mn_ref, c_ref, r_ref, *,
            window: int, block_rows: int, table, min_count: int):
    """x_ref: [R, 128] rows of one lane tile; v_ref: [R, 128] validity
    (row mask broadcast across lanes); outputs: [BR, 128] each."""
    base = pl.program_id(0) * block_rows

    def load(w):
        xv = x_ref[pl.ds(base + w, block_rows), :]
        m = v_ref[pl.ds(base + w, block_rows), :] > 0
        return xv, m

    xv, m = load(0)
    acc_s = jnp.where(m, xv, 0.0)
    acc_mx = jnp.where(m, xv, F32_MIN)
    acc_mn = jnp.where(m, xv, F32_MAX)
    acc_c = m.astype(jnp.float32)
    for w in range(1, window):
        xv, m = load(w)
        acc_s = acc_s + jnp.where(m, xv, 0.0)
        acc_mx = jnp.maximum(acc_mx, jnp.where(m, xv, F32_MIN))
        acc_mn = jnp.minimum(acc_mn, jnp.where(m, xv, F32_MAX))
        acc_c = acc_c + m.astype(jnp.float32)
    nonempty = acc_c > 0
    mx0 = jnp.where(nonempty, acc_mx, 0.0)   # empty window -> 0, not +-inf
    mn0 = jnp.where(nonempty, acc_mn, 0.0)
    s_ref[...] = acc_s
    mx_ref[...] = mx0
    mn_ref[...] = mn0
    c_ref[...] = acc_c
    r_ref[...] = rule_sweep(acc_s, mx0, mn0, acc_c, table, min_count)


def fused_reduce_2d(x2d: jnp.ndarray, valid: jnp.ndarray, window: int,
                    table, min_count: int, *,
                    block_rows: int = BLOCK_ROWS, interpret: bool = False
                    ) -> tuple[jnp.ndarray, ...]:
    """Stride-1 fused reduction: [R, L] f32 + [R] mask ->
    (sum, max, min, count, consequence), each [R - window + 1, L].

    L % 128 == 0 and (R - window + 1) % block_rows == 0 (callers pad
    rows as *invalid*, see ops.py — padding never affects results).
    """
    r, l = x2d.shape
    n_out = r - window + 1
    assert window >= 1 and n_out > 0, (r, window)
    assert l % LANES == 0 and n_out % block_rows == 0, (r, l, block_rows)
    # one [R, 128] validity tile shared by every lane tile (index map
    # pins tile 0): rows are valid or not regardless of lane
    vtile = jnp.broadcast_to(
        valid.astype(jnp.float32)[:, None], (r, LANES))
    grid = (n_out // block_rows, l // LANES)
    # inside a fleet shard_map the outputs vary over the mesh axes the
    # rows do (jax.shard_map checks that on every out_shape)
    out = jax.ShapeDtypeStruct((n_out, l), jnp.float32,
                               vma=jax.typeof(x2d).vma)
    return pl.pallas_call(
        functools.partial(_kernel, window=window, block_rows=block_rows,
                          table=tuple(table), min_count=min_count),
        grid=grid,
        in_specs=[pl.BlockSpec((r, LANES), lambda i, j: (0, j)),
                  pl.BlockSpec((r, LANES), lambda i, j: (0, 0))],
        out_specs=[pl.BlockSpec((block_rows, LANES), lambda i, j: (i, j))
                   for _ in range(5)],
        out_shape=[out] * 5,
        interpret=interpret,
    )(x2d, vtile)
