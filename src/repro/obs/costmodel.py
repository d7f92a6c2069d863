"""Device cost accounting: XLA HLO cost analysis over the traced tick,
attributed to the ``jax.named_scope`` stages.

Two layers:

* :func:`analyze` lowers a jitted step with its real operands (lower +
  compile only — nothing executes, no donated buffer is consumed) and
  reads the compiled executable's ``cost_analysis()``: total FLOPs,
  bytes accessed, and transcendentals for ONE tick, as XLA's own cost
  model sees it post-fusion.  Per-stage attribution comes from the
  compiled HLO text: every op carries its ``op_name`` metadata with the
  full ``named_scope`` path (``.../obs:window/reduce``), so ops and
  their result bytes are summed per ``obs:*`` stage
  (the stages of the layer table in PERF.md, section 3; the innermost
  scope wins — scopes nest).  Result bytes undercount true traffic (operand reads
  are not re-counted) — treat stage bytes as a *relative* ranking; the
  executable-level total is the roofline-grade number.
* :func:`roofline` turns (flops, bytes, measured seconds) into achieved
  GFLOP/s, GB/s, arithmetic intensity, and — when peak numbers are
  known — utilization fractions against the machine's compute and
  bandwidth roofs.  Peaks come from ``REPRO_PEAK_FLOPS`` /
  ``REPRO_PEAK_BW`` (FLOP/s and bytes/s) or explicit arguments; with
  no peak declared the utilization columns report 0.0 (unknown), never
  a guess.

This is the sub-tick decomposition the latency lineage deliberately
does not attempt (lineage is tick-quantized): lineage says *where
records wait*, the cost model says *where the tick's device time must
go*.  Both land in ``bench_payload`` rows, which is what lets
``benchmarks/roofline_report.py`` cover the streaming path.
"""
from __future__ import annotations

import os
import re

import numpy as np

#: HLO result-literal dtype sizes in bytes (enough for this codebase).
_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

#: One HLO instruction line: ``%name = f32[32,3]{1,0} add(...)`` with
#: optional ``metadata={op_name="..." ...}`` trailing.
_OP_RE = re.compile(
    r"=\s*(?:\()?([a-z0-9]+)\[([0-9,]*)\]")
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')
_STAGE_RE = re.compile(r"obs:[a-z0-9_]+")


def _result_bytes(dtype: str, dims: str) -> int:
    size = _DTYPE_BYTES.get(dtype)
    if size is None:
        return 0
    n = 1
    for d in filter(None, dims.split(",")):
        n *= int(d)
    return n * size


def analyze(jitted, *args, **kwargs) -> dict:
    """Cost-analyze one traced call of ``jitted`` (a ``jax.jit``-wrapped
    function) on the given operands.  Lower + compile only; returns::

        {"flops": float, "bytes_accessed": float, "transcendentals":
         float, "stages": {"obs:window": {"ops": int, "bytes": int},
         ...}}

    Stage keys appear only for stages present in the compiled module;
    an op under nested scopes is attributed to the *innermost* one.
    Compiling here hits jax's compilation cache when the executor has
    already traced the same shapes, so the pass is cheap to run after
    warmup."""
    return cost_of(jitted.lower(*args, **kwargs).compile())


def cost_of(compiled) -> dict:
    """:func:`analyze` of an already compiled executable
    (``jax.stages.Compiled``)."""
    ca = compiled.cost_analysis()
    totals = {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "transcendentals": float(ca.get("transcendentals", 0.0)),
    }
    stages: dict = {}
    for line in compiled.as_text().splitlines():
        names = _OPNAME_RE.search(line)
        if names is None:
            continue
        hits = _STAGE_RE.findall(names.group(1))
        if not hits:
            continue
        stage = hits[-1]                     # innermost scope wins
        shape = _OP_RE.search(line)
        nbytes = _result_bytes(*shape.groups()) if shape else 0
        agg = stages.setdefault(stage, {"ops": 0, "bytes": 0})
        agg["ops"] += 1
        agg["bytes"] += nbytes
    totals["stages"] = stages
    return totals


def roofline(flops: float, bytes_accessed: float, seconds: float,
             peak_flops: float | None = None,
             peak_bw: float | None = None) -> dict:
    """Roofline coordinates for one tick: achieved rates, arithmetic
    intensity, and utilization against declared peaks.

    ``peak_flops``/``peak_bw`` default from ``$REPRO_PEAK_FLOPS`` /
    ``$REPRO_PEAK_BW`` (FLOP/s, bytes/s); unset or 0 reports 0.0
    utilization — "unknown", never a fabricated roof."""
    if peak_flops is None:
        peak_flops = float(os.environ.get("REPRO_PEAK_FLOPS", 0) or 0)
    if peak_bw is None:
        peak_bw = float(os.environ.get("REPRO_PEAK_BW", 0) or 0)
    seconds = max(float(seconds), 1e-12)
    fps = float(flops) / seconds
    bps = float(bytes_accessed) / seconds
    return {
        "gflops": fps / 1e9,
        "gbs": bps / 1e9,
        "ai": float(flops) / max(float(bytes_accessed), 1.0),
        "flops_util": fps / peak_flops if peak_flops > 0 else 0.0,
        "bw_util": bps / peak_bw if peak_bw > 0 else 0.0,
    }


def stage_table(analysis: dict) -> list[tuple[str, int, int]]:
    """``analysis["stages"]`` as rows sorted by descending bytes:
    ``[(stage, ops, bytes), ...]`` — the printable breakdown."""
    return sorted(((k, v["ops"], v["bytes"])
                   for k, v in analysis.get("stages", {}).items()),
                  key=lambda r: -r[2])
