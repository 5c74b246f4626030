"""Bridge between GenericScheduler and the JAX placement engine.

``compute_placements_with_engine`` returns True when the engine handled the
eval's whole placement batch, or NotImplemented to fall back to the host
iterator stack (the host path is always semantically complete).

Both entry points run under the ``engine_gate`` stage: the gate checks,
encode attempts and fallback decisions are host work the worker pays on
EVERY eval (device-handled or not), and without a span of their own they
showed up as unexplained worker_busy time in phases.coverage. The
engine's finer phases (encode/pad_stack/device/apply) nest inside; the
coverage union dedups the overlap.
"""
from __future__ import annotations

from ..trace import lifecycle as _lifecycle


def compute_placements_with_engine(sched, destructive, place):
    with _lifecycle.stage("engine_gate", sched.eval.id):
        # the lazy engine import is part of the gate cost: the first
        # eval pays it (jax + kernel modules), and outside the span it
        # surfaced as a one-shot unexplained worker_busy chunk
        from .engine import TpuPlacementEngine

        engine = TpuPlacementEngine.shared()
        return engine.compute_placements(sched, destructive, place)


def compute_system_placements_with_engine(sched, place, sched_config=None):
    """SystemScheduler device path (forced-node dense pass); True when
    handled, a list of leftover placements when only preemption-needing
    nodes remain for the host loop, NotImplemented to fall back to the
    host per-node stack wholesale."""
    with _lifecycle.stage("engine_gate", sched.eval.id):
        from .engine import TpuPlacementEngine

        engine = TpuPlacementEngine.shared()
        return engine.compute_system_placements(sched, place, sched_config)
