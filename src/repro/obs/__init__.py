"""repro.obs — structured tracing + metrics for the repro stack.

Spans/counters/gauges with explicit device-sync boundaries, a Recorder
emitting Chrome-trace-event JSONL (Perfetto-loadable via ``python -m
repro.obs.report --to-chrome``), and convergence traces from the fluid
solver.  Dependency-free: jax is only touched lazily at sync points.
"""

from .record import (
    NullRecorder,
    Recorder,
    Span,
    get_recorder,
    recording,
    set_recorder,
)
from .trace import ConvergenceTrace

__all__ = [
    "ConvergenceTrace",
    "NullRecorder",
    "Recorder",
    "Span",
    "get_recorder",
    "recording",
    "set_recorder",
]
