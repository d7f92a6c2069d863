"""Elastic scaling: re-mesh over a changed device set.

A job checkpointed on mesh A resumes on mesh B (more pods, fewer pods,
or a degraded pod with failed chips carved out).  The checkpoint layer
stores unsharded logical arrays; this module builds the new mesh, and
the owner of the state places it there.  The quadtree overlay is rebuilt from
the new mesh shape (the paper's join/rebootstrap phase, done at
re-launch time rather than via runtime discovery messages).

The same join/leave machinery has a stream-facing face:
``ElasticBudget`` resizes the fleet core budget between ticks from
observed escalation pressure — capacity joins (grows) under sustained
load and leaves (shrinks) when idle, exactly the remesh trade applied
to the core sub-mesh's per-tick work budget instead of its chip count.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

from repro.core.overlay import Overlay


def remesh(old_shape: dict, new_devices: list, axis_names: tuple,
           fixed_axis: str | None = None) -> "jax.sharding.Mesh":
    """Build the largest mesh of the same axis structure that fits the
    surviving device list.

    Which axis is *preserved* (keeps its old size) and which *absorbs*
    the device-count change:

    * ``fixed_axis=<name>`` (2-axis meshes) — the named axis keeps its
      ``old_shape`` size and the other axis absorbs.  This is the
      stream fleet's ``("region", "edge")`` contract: an edge resize
      fixes ``"region"`` (regions persist, each gains/loses edge
      devices), a region resize fixes ``"edge"`` (regions of unchanged
      width appear/disappear) — one call resizes exactly one axis, and
      the device count must be a multiple of the fixed axis's size.
    * default, single axis — e.g. a flat ``("edge",)`` fleet — there is
      nothing to preserve: the only axis *is* the elastic one, and
      every surviving device lands on it.
    * default, multi-axis — the training-mesh legacy: the trailing
      (model) axis is preserved and the leading data axis absorbs; a
      3-axis ``(pod, data, model)`` mesh additionally halves the pod
      axis until it divides the remainder.
    """
    n = len(new_devices)
    if n < 1:
        raise ValueError("no devices to re-mesh over")
    if fixed_axis is not None:
        if fixed_axis not in axis_names:
            raise ValueError(f"fixed_axis {fixed_axis!r} not in "
                             f"{axis_names}")
        if len(axis_names) == 1:
            raise ValueError(
                f"fixed_axis {fixed_axis!r} on a single-axis mesh: the "
                "only axis is the elastic one, nothing can be preserved")
        if len(axis_names) != 2:
            raise ValueError(
                "fixed_axis supports 2-axis meshes (for >2 axes use the "
                f"default trailing-axis contract), got {axis_names}")
        keep = old_shape[fixed_axis]
        other = n // keep
        if other == 0 or other * keep != n:
            raise ValueError(
                f"{n} devices cannot keep {fixed_axis}={keep} "
                f"(need a positive multiple of {keep})")
        shape = (keep, other) if fixed_axis == axis_names[0] \
            else (other, keep)
    elif len(axis_names) == 1:
        shape = (n,)
    else:
        model = old_shape[axis_names[-1]]
        lead = n // model
        if lead == 0 or lead * model != n:
            raise ValueError(f"{n} devices cannot keep model={model}")
        if len(axis_names) == 3:
            pod = old_shape[axis_names[0]]
            while pod > 1 and lead % pod:
                pod //= 2
            shape = (pod, lead // pod, model)
        else:
            shape = (lead, model)
    devs = np.asarray(new_devices[: int(np.prod(shape))]).reshape(shape)
    return jax.sharding.Mesh(devs, axis_names)


@dataclasses.dataclass
class ElasticBudget:
    """Hysteresis grow/shrink policy for an elastic per-tick work budget.

    Feed it the observed demand (fleet escalations this tick) and the
    current budget; it proposes a new budget.  Growth fires after
    ``patience`` consecutive ticks at utilization >= ``grow_at``;
    shrink after ``patience`` consecutive ticks at <= ``shrink_at`` —
    the two-sided deadband keeps a noisy workload from thrashing the
    budget (each fleet resize is a real event: a possible re-trace and
    a capacity re-negotiation, the stream analogue of a remesh).
    """
    min_budget: int
    max_budget: int
    grow_at: float = 0.9          # utilization that counts as pressure
    shrink_at: float = 0.25       # utilization that counts as idle
    grow_factor: float = 2.0      # multiplicative grow / shrink step
    patience: int = 2             # consecutive ticks before resizing
    _hot: int = 0
    _cold: int = 0

    def __post_init__(self):
        if not (0 < self.min_budget <= self.max_budget):
            raise ValueError(f"bad budget range: {self}")
        if not (0.0 <= self.shrink_at < self.grow_at):
            raise ValueError(f"need 0 <= shrink_at < grow_at, got {self}")
        if self.grow_factor <= 1.0 or self.patience < 1:
            raise ValueError(f"need grow_factor > 1, patience >= 1: {self}")

    def propose(self, demand: int, budget: int) -> int:
        """One control tick: observed demand -> proposed budget.

        Patience is only consumed by proposals that actually move the
        budget: at a saturated ceiling (``budget == max_budget`` under
        pressure) or floor (``budget == min_budget`` when idle) the
        proposal is a no-op and the counters keep accruing — sustained
        pressure at the ceiling must not re-pay full patience every
        tick, so the moment headroom appears the resize fires at once.
        """
        util = demand / max(budget, 1)
        if util >= self.grow_at:
            self._hot, self._cold = self._hot + 1, 0
        elif util <= self.shrink_at:
            self._hot, self._cold = 0, self._cold + 1
        else:
            self._hot = self._cold = 0
        if self._hot >= self.patience:
            proposed = min(self.max_budget,
                           max(budget + 1, int(budget * self.grow_factor)))
            if proposed != budget:
                self._hot = 0
                return proposed
        if self._cold >= self.patience:
            proposed = max(self.min_budget, int(budget / self.grow_factor))
            if proposed != budget:
                self._cold = 0
                return proposed
        return budget


def rebuild_overlay(mesh, **kw) -> Overlay:
    """Overlay over the dp x model chip grid of the (possibly new) mesh."""
    shape = dict(mesh.shape)
    names = list(shape)
    rows = int(np.prod([shape[n] for n in names[:-1]]))
    cols = shape[names[-1]]
    return Overlay.from_mesh_shape(rows, cols, **kw)
