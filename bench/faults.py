"""Faults planted under the timed path, for the tests that show the
comparison catches them (``bench/test_control.py``).  Each is a context
manager that breaks the program (or the system wrapper around it) for
the runs made inside it.

* ``state_unchanged``: every step hands back the state it was given;
* ``half_batch``: the program sees the first half of every tick twice,
  so the second half is left out (dropped as re-deliveries);
* ``exchange_left_out``: the fleet's escalation exchange returns no
  core results, as if nothing crossed the chips;
* ``answer_altered``: the fused tick alters one window's rule code where
  it is produced.
"""
from __future__ import annotations

import contextlib

FAULTS = ("state_unchanged", "half_batch", "exchange_left_out",
          "answer_altered")


@contextlib.contextmanager
def _patched(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


@contextlib.contextmanager
def planted(fault: str):
    import jax
    import jax.numpy as jnp

    from bench import system as S

    if fault == "state_unchanged":
        def wrap(step):
            def frozen(self, items, ts, span):
                old = jax.tree.map(jnp.copy, self.state)
                out = step(self, items, ts, span)
                self.state = old
                return out
            return frozen
    elif fault == "half_batch":
        def wrap(step):
            def half(self, items, ts, span):
                h = items.shape[1] // 2
                items, ts = items.copy(), ts.copy()
                items[:, h:2 * h], ts[:, h:2 * h] = items[:, :h], ts[:, :h]
                return step(self, items, ts, span)
            return half
    elif fault == "exchange_left_out":
        from repro.stream.fleet import federation as F
        orig = F.federate_escalations_tiered

        def broken(*a, **k):
            out = orig(*a, **k)
            return (out[0], out[1], out[2] & False) + tuple(out[3:])
        with _patched(F, "federate_escalations_tiered", broken):
            yield
        return
    elif fault == "answer_altered":
        import repro.kernels.fused_tick as FT
        orig = FT.fused_tick

        def altered(*a, **k):
            agg, count, feats, birth, cons = orig(*a, **k)
            return agg, count, feats, birth, cons.at[3].set(
                (cons[3] + 1) % 3)
        with _patched(FT, "fused_tick", altered):
            yield
        return
    else:
        raise ValueError(f"unknown fault {fault!r}")
    with _patched(S.EdgeSystem, "step", wrap(S.EdgeSystem.step)), \
            _patched(S.FleetSystem, "step", wrap(S.FleetSystem.step)):
        yield
