"""Structured tracing and metrics: spans, counters, and a trace Recorder.

This module is dependency-free (stdlib only; jax is imported lazily
inside :meth:`Span.sync`, and a span uses it only when the process has
already imported it).  It gives the graph builder, routing, the path
builder, the fluid solver, the blockwise executor, and the packet engine
a shared vocabulary:

- **Spans** are nested wall-clock intervals.  A span's clock obeys the
  same discipline as ``benchmarks.common.timed``: asynchronous device
  work must be drained *before* the closing clock read, via an explicit
  :meth:`Span.sync` boundary (which calls ``jax.block_until_ready``).
  A span that never calls ``sync`` measures host wall time only.  Each
  span records the name of the span it opened inside (``parent``), and
  while a :class:`Recorder` is installed it is also a
  ``jax.profiler.TraceAnnotation`` of the same name (once jax is
  loaded), so a profiler capture shows the program's spans in its host
  plane, on the clock of the device ops.
- **Counters** accumulate (sum over the run); **gauges** keep the last
  value; **histograms** bin a batch of integer-valued samples;
  **series** store a (downsampled) time series such as a per-cycle
  occupancy trace.
- The :class:`Recorder` buffers everything as Chrome-trace events and
  dumps them as JSONL (one JSON event per line).  ``python -m
  repro.obs.report --to-chrome`` wraps that into the JSON-array form
  Perfetto / ``chrome://tracing`` load directly.

The process-global default recorder is a :class:`NullRecorder` whose
spans are a single reusable no-op context manager — instrumented hot
paths pay only a ``get_recorder()`` attribute chase plus one virtual
call when tracing is off.
"""

from __future__ import annotations

import json
import sys
import time
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence

__all__ = [
    "Recorder",
    "NullRecorder",
    "Span",
    "get_recorder",
    "set_recorder",
    "recording",
    "summarize_spans",
]


# The recorder owns the clock: span boundaries drain async device work
# first (Span.sync, same discipline common.timed encodes), so the read
# below is behind the sync boundary rather than racing it.
def _now() -> float:  # reprolint: allow[naked-clock] -- recorder-internal clock; spans sync devices before the closing read
    return time.perf_counter()


def _annotation(name: str) -> Any:
    """A ``jax.profiler.TraceAnnotation`` for a span, or None while the
    process has not imported jax (this module never imports it)."""
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    return None if profiler is None else profiler.TraceAnnotation(name)


class Span:
    """A live span handle.  Use via ``with recorder.span(name): ...``.

    ``sync(out)`` marks the explicit device-sync boundary: it blocks on
    ``out`` (any pytree of jax arrays) and returns it, so the span's
    duration includes the device work that produced it.
    """

    __slots__ = ("_rec", "name", "args", "_t0", "_parent", "_ann")

    def __init__(self, rec: "Recorder", name: str, args: Dict[str, Any]):
        self._rec = rec
        self.name = name
        self.args = args
        self._t0 = 0.0
        self._parent: Optional[str] = None
        self._ann: Any = None

    def set(self, **attrs: Any) -> None:
        """Attach attributes to this span (rendered as Chrome-trace args)."""
        self.args.update(attrs)

    def sync(self, out: Any = None) -> Any:
        """Block until ``out`` is ready on device; returns ``out``.

        This is the explicit device-sync boundary: call it on the jitted
        result before the span closes so the measured duration covers
        the asynchronously dispatched work.
        """
        if out is not None:
            import jax

            jax.block_until_ready(out)
        return out

    def __enter__(self) -> "Span":
        self._parent = self._rec._push(self)
        self._ann = _annotation(self.name)
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = self._rec._clock()
        return self

    def __exit__(self, *exc: Any) -> bool:
        t1 = self._rec._clock()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self._rec._pop(self)
        self._rec._complete(self.name, self._t0, t1, self.args, self._parent)
        return False


class _NullSpan:
    """Reusable no-op span; the default when tracing is off."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc: Any) -> bool:
        return False

    def set(self, **attrs: Any) -> None:
        pass

    def sync(self, out: Any = None) -> Any:
        return out


_NULL_SPAN = _NullSpan()


class NullRecorder:
    """No-op recorder: every operation is a constant-time no-op.

    This is the process default so instrumented code needs no ``if``
    guards; the only cost on hot paths is one virtual call returning the
    shared no-op span.
    """

    __slots__ = ()

    def span(self, name: str, **args: Any) -> _NullSpan:
        return _NULL_SPAN

    def request(self, rid: Any) -> None:
        pass

    def counter(self, name: str, value: float = 1.0, **args: Any) -> None:
        pass

    def gauge(self, name: str, value: float) -> None:
        pass

    def histogram(self, name: str, values: Sequence[int]) -> None:
        pass

    def series(self, name: str, values: Sequence[float], max_points: int = 512) -> None:
        pass

    def events(self) -> List[Dict[str, Any]]:
        return []

    def metrics(self) -> Dict[str, Any]:
        return {}

    def span_summary(self) -> Dict[str, Dict[str, float]]:
        return {}

    def summary(self) -> Dict[str, Any]:
        return {}

    def dump(self, path: str) -> None:
        pass


class Recorder:
    """Buffers trace events and aggregates metric tables.

    Events follow the Chrome trace event format (``ph`` codes): ``X``
    complete events for spans (``ts``/``dur`` in microseconds), ``C``
    counter events, and ``i`` instant events carrying histogram bins.
    ``dump`` writes one event per line (JSONL); see ``repro.obs.report``
    for rendering and Perfetto conversion.

    ``clock`` is injectable for deterministic tests; it must be a
    monotonic float-seconds callable like ``time.perf_counter``.

    A span event carries ``parent``, the name of the span open around it,
    where there is one; after ``request(rid)`` every event carries
    ``request``: rid, until the next call (``request(None)`` ends it).
    """

    def __init__(self, clock: Optional[Callable[[], float]] = None):
        self._clock = clock if clock is not None else _now
        self._t0 = self._clock()
        self._events: List[Dict[str, Any]] = []
        self._open: List[Span] = []
        self._request: Any = None

    # -- event ingestion ------------------------------------------------

    def _us(self, t: float) -> float:
        return (t - self._t0) * 1e6

    def _push(self, span: Span) -> Optional[str]:
        """Open `span`; returns the name of the span it opened inside."""
        parent = self._open[-1].name if self._open else None
        self._open.append(span)
        return parent

    def _pop(self, span: Span) -> None:
        # a span held open across a generator's yield may close out of
        # order, so remove this one rather than the innermost
        if self._open and self._open[-1] is span:
            self._open.pop()
        else:
            self._open.remove(span)

    def _event(self, name: str, ph: str, t: float, args: Dict[str, Any],
               **extra: Any) -> Dict[str, Any]:
        ev = {"name": name, "ph": ph, "ts": round(self._us(t), 3),
              "pid": 1, "tid": 1, "args": args, **extra}
        if self._request is not None:
            ev["request"] = self._request
        self._events.append(ev)
        return ev

    def _complete(self, name: str, t0: float, t1: float,
                  args: Dict[str, Any], parent: Optional[str] = None) -> None:
        ev = self._event(name, "X", t0, dict(args),
                         dur=round((t1 - t0) * 1e6, 3))
        if parent is not None:
            ev["parent"] = parent

    def span(self, name: str, **args: Any) -> Span:
        """Open a nested wall-clock span (context manager)."""
        return Span(self, name, dict(args))

    def request(self, rid: Any) -> None:
        """Tag the events that follow with ``request``: rid (one answer,
        one query), until the next call; None stops tagging."""
        self._request = rid

    def counter(self, name: str, value: float = 1.0, **args: Any) -> None:
        """Accumulate ``value`` onto counter ``name`` (summed in metrics)."""
        self._event(name, "C", self._clock(), {"value": value, **args})

    def gauge(self, name: str, value: float) -> None:
        """Record an instantaneous value; metrics keep last/min/max/mean."""
        self._event(name, "C", self._clock(), {"value": value, "gauge": True})

    def histogram(self, name: str, values: Sequence[int]) -> None:
        """Bin non-negative integer samples; stores ``bins[d] = count``."""
        bins: Dict[int, int] = {}
        count = 0
        for v in values:
            k = int(v)
            bins[k] = bins.get(k, 0) + 1
            count += 1
        self._event(name, "i", self._clock(),
                    {"histogram": {str(k): bins[k] for k in sorted(bins)},
                     "count": count}, s="g")

    def series(self, name: str, values: Sequence[float], max_points: int = 512) -> None:
        """Record a time series (e.g. per-cycle occupancy), downsampled.

        Long inputs are strided down to at most ``max_points`` samples;
        the stride is recorded so consumers can recover the time axis.
        """
        n = len(values)
        stride = max(1, -(-n // max_points))
        sampled = [float(values[i]) for i in range(0, n, stride)]
        self._event(name, "i", self._clock(),
                    {"series": sampled, "stride": stride, "n": n}, s="g")

    # -- aggregation ----------------------------------------------------

    def events(self) -> List[Dict[str, Any]]:
        return list(self._events)

    def span_summary(self) -> Dict[str, Dict[str, float]]:
        """Per-span-name aggregate: `summarize_spans` of the events."""
        return summarize_spans(self._events)

    def metrics(self) -> Dict[str, Any]:
        """Aggregated counter/gauge/histogram tables keyed by name."""
        counters: Dict[str, float] = {}
        gauges: Dict[str, Dict[str, float]] = {}
        histograms: Dict[str, Dict[str, int]] = {}
        for ev in self._events:
            name, args = ev["name"], ev.get("args", {})
            if ev["ph"] == "C":
                v = float(args.get("value", 0.0))
                if args.get("gauge"):
                    g = gauges.setdefault(
                        name, {"last": v, "min": v, "max": v, "sum": 0.0, "count": 0}
                    )
                    g["last"] = v
                    g["min"] = min(g["min"], v)
                    g["max"] = max(g["max"], v)
                    g["sum"] += v
                    g["count"] += 1
                else:
                    counters[name] = counters.get(name, 0.0) + v
            elif ev["ph"] == "i" and "histogram" in args:
                h = histograms.setdefault(name, {})
                for k, c in args["histogram"].items():
                    h[k] = h.get(k, 0) + int(c)
        for g in gauges.values():
            g["mean"] = g["sum"] / g["count"]
        return {"counters": counters, "gauges": gauges, "histograms": histograms}

    def summary(self) -> Dict[str, Any]:
        """Compact summary for embedding in BENCH_*.json ``obs`` tables."""
        spans = self.span_summary()
        met = self.metrics()
        top = sorted(spans.items(), key=lambda kv: -kv[1]["total_us"])[:8]
        return {
            "events": len(self._events),
            "spans": {
                name: {k: round(v, 3) for k, v in row.items()}
                for name, row in top
            },
            "counters": met["counters"],
            "gauges": {
                name: round(g["last"], 6) for name, g in met["gauges"].items()
            },
        }

    # -- output ---------------------------------------------------------

    def lines(self) -> Iterator[str]:
        for ev in self._events:
            yield json.dumps(ev, sort_keys=True)

    def dump(self, path: str) -> None:
        """Write buffered events as Chrome-trace-event JSONL."""
        with open(path, "w") as fh:
            for line in self.lines():
                fh.write(line + "\n")

    def clear(self) -> None:
        self._events.clear()


def summarize_spans(events: Sequence[Dict[str, Any]]
                    ) -> Dict[str, Dict[str, float]]:
    """Per-span-name aggregate of the span (``ph="X"``) events: count and
    total/mean/max duration, and ``self_us``: the total less the part that
    the spans directly inside each one cover (all in us).  Nesting is read
    from the intervals, so a trace loaded from a file gives the same
    table as the recorder that wrote it."""
    spans = sorted((ev for ev in events if ev.get("ph") == "X"),
                   key=lambda ev: (ev.get("ts", 0.0), -ev.get("dur", 0.0)))
    out: Dict[str, Dict[str, float]] = {}
    open_: List[list] = []  # [end, row] of the spans around the current one
    for ev in spans:
        ts, dur = float(ev.get("ts", 0.0)), float(ev.get("dur", 0.0))
        # 2 ns of slack: ts and dur are each rounded to the nanosecond
        while open_ and open_[-1][0] < ts + dur - 2e-3:
            open_.pop()
        row = out.setdefault(ev.get("name", "?"),
                             {"count": 0, "total_us": 0.0, "max_us": 0.0,
                              "self_us": 0.0})
        row["count"] += 1
        row["total_us"] += dur
        row["self_us"] += dur
        row["max_us"] = max(row["max_us"], dur)
        if open_:
            open_[-1][1]["self_us"] -= dur
        open_.append([ts + dur, row])
    for row in out.values():
        row["mean_us"] = row["total_us"] / row["count"]
    return out


_RECORDER: Any = NullRecorder()


def get_recorder() -> Any:
    """The process-global recorder (a NullRecorder unless installed)."""
    return _RECORDER


def set_recorder(rec: Any) -> Any:
    """Install ``rec`` as the global recorder; returns the previous one."""
    global _RECORDER
    prev = _RECORDER
    _RECORDER = rec
    return prev


class recording:
    """Context manager installing ``rec`` for the enclosed block.

    >>> from repro.obs import Recorder, recording, get_recorder
    >>> rec = Recorder()
    >>> with recording(rec):
    ...     with get_recorder().span("step"):
    ...         pass
    >>> rec.span_summary()["step"]["count"]
    1
    """

    def __init__(self, rec: Any):
        self._rec = rec
        self._prev: Any = None

    def __enter__(self) -> Any:
        self._prev = set_recorder(self._rec)
        return self._rec

    def __exit__(self, *exc: Any) -> bool:
        set_recorder(self._prev)
        return False
