"""Operations and bytes the windows-and-rules stage needs, from its
shapes alone, whichever implementation runs it (the fused kernel, or
the staged windows and rules).

The stage reads the dequeued block once: T = B + W - S rows of the
carried and dequeued ring rows, each ``2 + D`` float32 words (event
time, ingest stamp, channels) plus one validity byte.  It writes, per
window, the D channel means, 5 rule features, the count, the birth
stamp and the rule code, 4 bytes each.  Its operations, with
NW = (T - W) / S + 1 complete windows of W rows:

* sums over the D channels: NW * W * D additions, and one division a
  window per channel for the mean;
* over the signal channel: a max and a min per row, one division for
  the mean feature;
* the birth stamp: one min per row over the ingest-stamp column;
* the count: one addition per row;
* the rules: one comparison and one select per rule and window.

Sliding windows can share partial sums; the count here is the framing
the stage is specified with, so it does not depend on one
implementation's tricks.
"""
from __future__ import annotations


def window_rules(cfg: dict) -> dict[str, float]:
    """{"ops", "bytes", "rows", "windows"} of one shard's tick."""
    b, w, s, d = (cfg["micro_batch"], cfg["window"], cfg["stride"],
                  cfg["channels"])
    rules = len(cfg["rules"])
    t = b + w - s
    nw = (t - w) // s + 1
    per_row = d + 2 + 1 + 1            # channel sums, max+min, birth, count
    ops = nw * w * per_row + nw * (d + 1) + nw * rules * 2
    read = t * (2 + d) * 4 + t
    written = nw * (d + 5 + 3) * 4
    return {"ops": float(ops), "bytes": float(read + written),
            "rows": float(t), "windows": float(nw)}


def least_seconds(work: dict, peak: dict) -> tuple[float, str]:
    """The stage's least time on a chip: the larger of its operations
    over peak operations and its bytes over peak bandwidth, and which
    of the two bounds it."""
    t_ops = work["ops"] / peak["flops_per_s"]
    t_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    return (t_ops, "ops") if t_ops >= t_bytes else (t_bytes, "bytes")
