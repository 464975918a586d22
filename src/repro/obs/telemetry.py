"""Unified telemetry: spans, counters/gauges/histograms, one sink per run.

Every layer of the DSE->serving stack reports here: ``dse.run_dse`` wraps its
stages (characterize / MaP / GA / validate) in **spans**, the kernel
registry/autotuner counts dispatches and cache traffic with **counters**, the
Pallas wrappers record pad-to-block waste **gauges**, and the serving driver
fills per-request latency **histograms**.  ``repro.obs.device`` adds on-device
metric taps (``io_callback`` sinks that fire once per *dispatch*, not once per
trace) used by ``fastmoo.CompiledNSGA2`` for per-generation hypervolume
curves.

Design rules:

  * **One sink.**  A :class:`Telemetry` object is carried by
    ``ExecutionContext(telemetry=...)`` and threaded to every engine.  Code
    without a context reports to the process-wide :data:`GLOBAL` aggregate
    (or whatever :func:`use` has made current); counters on a child telemetry
    propagate to its ``parent`` so process totals stay queryable (the
    ``kernels.tuning.STATS`` back-compat alias reads them there).
  * **Disabled means no-op.**  :data:`NULL` (``telemetry="off"``) swallows
    everything: ``span`` returns a shared reusable context manager, counters
    are ``pass``, and device taps insert *nothing* into traced programs, so
    the off path is the pre-telemetry program bit for bit.
  * **No JAX here.**  This module is stdlib-only (numpy accepted at call
    sites); the device taps import JAX lazily, and a span enters a
    ``jax.profiler.TraceAnnotation`` only when JAX is already imported, so
    numpy-only processes stay JAX-free.  In a JAX process every span is on
    the profiler's clock: it appears on the host plane of a
    ``jax.profiler`` trace, beside the device's operations (a TraceMe that
    costs next to nothing while no profiler runs).

Spans are thread- and contextvar-safe: the open-span stack lives in a
``contextvars.ContextVar``, so concurrent threads (or async tasks) nest
correctly without sharing parents.  Export formats: JSONL (one record per
line; see :mod:`repro.obs.export`) and Chrome-trace JSON loadable in Perfetto
(``chrome://tracing``), with counters attached as metadata.
"""

from __future__ import annotations

import contextvars
import functools
import itertools
import sys
import threading
import time
from collections import deque
from dataclasses import dataclass, field

__all__ = [
    "Span",
    "Telemetry",
    "NullTelemetry",
    "GLOBAL",
    "NULL",
    "as_telemetry",
    "current",
    "use",
    "note_trace",
    "record_pad_waste",
]

# open-span stack (tuple of Span) per thread/task; shared mutable state stays
# on the Telemetry object itself, guarded by its lock
_SPAN_STACK: contextvars.ContextVar[tuple] = contextvars.ContextVar(
    "repro_obs_span_stack", default=()
)

_MAX_SPANS = 100_000          # ring buffer: long processes never grow unbounded
_MAX_HIST = 100_000
_MAX_SERIES = 1_000_000


@dataclass
class Span:
    """One finished (or open) wall-clock interval."""

    name: str
    t0: float                          # perf_counter seconds (monotonic)
    t1: float | None = None
    span_id: int = 0
    parent_id: int | None = None
    tid: int = 0
    attrs: dict = field(default_factory=dict)

    @property
    def duration_s(self) -> float:
        return 0.0 if self.t1 is None else self.t1 - self.t0

    def to_record(self) -> dict:
        return {
            "type": "span",
            "name": self.name,
            "t0": self.t0,
            "t1": self.t1,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "tid": self.tid,
            "attrs": dict(self.attrs),
        }


class _SpanCM:
    """Context manager entering/exiting one span on one telemetry object."""

    __slots__ = ("_tel", "_span", "_token", "_annot")

    def __init__(self, tel: "Telemetry", span: Span):
        self._tel = tel
        self._span = span
        self._token = None
        self._annot = None

    def __enter__(self) -> Span:
        stack = _SPAN_STACK.get()
        if stack:
            self._span.parent_id = stack[-1].span_id
        self._token = _SPAN_STACK.set(stack + (self._span,))
        self._span.t0 = time.perf_counter()
        self._annot = _trace_annotation(self._span.name)
        if self._annot is not None:
            self._annot.__enter__()
        return self._span

    def __exit__(self, *exc) -> None:
        self._span.t1 = time.perf_counter()
        if self._annot is not None:
            self._annot.__exit__(*exc)
        _SPAN_STACK.reset(self._token)
        self._tel._finish_span(self._span)


def _trace_annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` when JAX is already imported, else
    None: spans then line up with XLA activity in a jax.profiler trace."""
    if "jax" not in sys.modules:
        return None
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name)


class Telemetry:
    """Span + metric sink.  Thread-safe; cheap enough to leave on.

    ``parent`` chains counter/gauge/histogram updates upward (child sinks
    created per run still feed process-wide totals); spans and device-tap
    series stay local to the object that recorded them.  ``device_taps``
    opts compiled programs into on-device metric emission (extra per-step
    work inside e.g. the NSGA-II ``fori_loop``), so it is False unless the
    telemetry was explicitly requested with ``"on"``.
    """

    enabled = True

    def __init__(
        self,
        name: str = "telemetry",
        parent: "Telemetry | None" = None,
        device_taps: bool = False,
    ) -> None:
        self.name = name
        self.parent = parent
        self.device_taps = bool(device_taps)
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        self.spans: deque = deque(maxlen=_MAX_SPANS)
        self.counters: dict[str, int] = {}
        self.gauges: dict[str, float] = {}
        self.histograms: dict[str, deque] = {}
        self.series: dict[str, list] = {}

    # -- spans ----------------------------------------------------------------

    def span(self, name: str, **attrs) -> _SpanCM:
        """Context manager: ``with tel.span("dse.ga", pop=64) as s: ...``"""
        sp = Span(
            name=name, t0=0.0, span_id=next(self._ids),
            tid=threading.get_ident(), attrs=attrs,
        )
        return _SpanCM(self, sp)

    def wrap(self, name: str | None = None, **attrs):
        """Decorator twin of :meth:`span`."""

        def deco(fn):
            label = name or fn.__qualname__

            @functools.wraps(fn)
            def inner(*args, **kwargs):
                with self.span(label, **attrs):
                    return fn(*args, **kwargs)

            return inner

        return deco

    def _finish_span(self, span: Span) -> None:
        with self._lock:
            self.spans.append(span)

    # -- metrics --------------------------------------------------------------

    def count(self, name: str, n: int = 1) -> None:
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + n
        if self.parent is not None:
            self.parent.count(name, n)

    def gauge(self, name: str, value: float) -> None:
        with self._lock:
            self.gauges[name] = float(value)
        if self.parent is not None:
            self.parent.gauge(name, value)

    def observe(self, name: str, value: float) -> None:
        """Histogram sample (stored raw; percentiles computed on demand)."""
        with self._lock:
            self.histograms.setdefault(name, deque(maxlen=_MAX_HIST)).append(
                float(value)
            )
        if self.parent is not None:
            self.parent.observe(name, value)

    def set_counter(self, name: str, value: int) -> None:
        """Force a counter value (back-compat STATS writes; not propagated)."""
        with self._lock:
            self.counters[name] = int(value)

    def emit(self, name: str, record: dict) -> None:
        """Append one record to a named series (device taps land here)."""
        with self._lock:
            s = self.series.setdefault(name, [])
            if len(s) < _MAX_SERIES:
                s.append(record)

    # -- device taps (JAX imported lazily) ------------------------------------

    def device_tap(self, name: str, fields: tuple):
        """An emit function usable inside jitted code; see ``obs.device``."""
        from .device import make_tap

        return make_tap(self, name, fields)

    def device_batched_tap(self, name: str, fields: tuple):
        """Chunk-flushing tap ``tap(rows, valid)``; see ``obs.device``."""
        from .device import make_batched_tap

        return make_batched_tap(self, name, fields)

    # -- queries / export -----------------------------------------------------

    def counter(self, name: str) -> int:
        return self.counters.get(name, 0)

    def histogram_summary(self, name: str) -> dict:
        vals = sorted(self.histograms.get(name, ()))
        if not vals:
            return {"count": 0}
        n = len(vals)
        pick = lambda q: vals[min(n - 1, int(q * n))]
        return {
            "count": n,
            "mean": sum(vals) / n,
            "min": vals[0],
            "p50": pick(0.50),
            "p90": pick(0.90),
            "p99": pick(0.99),
            "max": vals[-1],
        }

    def summary(self) -> dict:
        with self._lock:
            return {
                "name": self.name,
                "spans": len(self.spans),
                "counters": dict(self.counters),
                "gauges": dict(self.gauges),
                "histograms": {
                    k: self.histogram_summary(k) for k in self.histograms
                },
                "series": {k: len(v) for k, v in self.series.items()},
            }

    def clear(self) -> None:
        with self._lock:
            self.spans.clear()
            self.counters.clear()
            self.gauges.clear()
            self.histograms.clear()
            self.series.clear()

    def to_jsonl(self, path: str) -> None:
        from .export import write_jsonl

        write_jsonl(self, path)

    def to_chrome_trace(self, path: str) -> None:
        from .export import write_chrome_trace

        write_chrome_trace(self, path)

    def __repr__(self) -> str:  # pragma: no cover - debugging nicety
        s = self.summary()
        return (f"Telemetry({self.name!r}, spans={s['spans']}, "
                f"counters={len(s['counters'])}, series={s['series']})")


class _NullSpanCM:
    """Shared, reusable no-op span context manager (zero allocation per use)."""

    __slots__ = ()

    def __enter__(self):
        return _NULL_SPAN

    def __exit__(self, *exc):
        return None


_NULL_SPAN = Span(name="<null>", t0=0.0, t1=0.0)
_NULL_CM = _NullSpanCM()


class NullTelemetry(Telemetry):
    """A true no-op sink: ``telemetry="off"``.

    Every method is constant-time and allocation-free; compiled programs
    built against it contain no tap callbacks at all, so the disabled path
    is within noise of a build with no telemetry calls anywhere (<1%
    overhead -- guarded by ``tests/test_obs.py``).
    """

    enabled = False

    def __init__(self) -> None:
        super().__init__(name="null", parent=None, device_taps=False)

    def span(self, name: str, **attrs):
        return _NULL_CM

    def wrap(self, name: str | None = None, **attrs):
        return lambda fn: fn

    def count(self, name: str, n: int = 1) -> None:
        pass

    def gauge(self, name: str, value: float) -> None:
        pass

    def observe(self, name: str, value: float) -> None:
        pass

    def set_counter(self, name: str, value: int) -> None:
        pass

    def emit(self, name: str, record: dict) -> None:
        pass

    def device_tap(self, name: str, fields: tuple):
        from .device import null_tap

        return null_tap

    def device_batched_tap(self, name: str, fields: tuple):
        from .device import null_tap

        return null_tap


#: process-wide aggregate: code without an ExecutionContext reports here, and
#: child telemetries propagate counters here (``tuning.STATS`` reads these)
GLOBAL = Telemetry(name="global")

#: the disabled sink (``telemetry="off"``); a singleton so identity checks work
NULL = NullTelemetry()

_CURRENT: contextvars.ContextVar[Telemetry | None] = contextvars.ContextVar(
    "repro_obs_current", default=None
)


def current() -> Telemetry:
    """The active telemetry: the innermost :func:`use`, else :data:`GLOBAL`."""
    tel = _CURRENT.get()
    return GLOBAL if tel is None else tel


class use:
    """``with use(tel): ...`` makes ``tel`` the current telemetry for code
    that has no ExecutionContext to read it from (jit trace bodies, library
    internals).  Re-entrant and contextvar-scoped."""

    def __init__(self, tel: Telemetry):
        self._tel = tel
        self._token = None

    def __enter__(self) -> Telemetry:
        self._token = _CURRENT.set(self._tel)
        return self._tel

    def __exit__(self, *exc) -> None:
        _CURRENT.reset(self._token)


def as_telemetry(value, default: Telemetry | None = None) -> Telemetry:
    """Normalize the ``ExecutionContext(telemetry=...)`` knob.

    ``None`` -> ``default`` (or :data:`GLOBAL`); ``"on"`` -> a fresh sink with
    device taps enabled, counters chained to :data:`GLOBAL`; ``"off"`` ->
    :data:`NULL`; a :class:`Telemetry` instance passes through unchanged.
    """
    if value is None:
        return GLOBAL if default is None else default
    if isinstance(value, Telemetry):
        return value
    if value == "on":
        return Telemetry(name="run", parent=GLOBAL, device_taps=True)
    if value == "off":
        return NULL
    raise ValueError(
        f"telemetry must be None, 'on', 'off' or a Telemetry, got {value!r}"
    )


def of(ctx) -> Telemetry:
    """The telemetry carried by an ExecutionContext (or the current sink).

    Accepts None and legacy-string backends so shim call sites can forward
    whatever they were given.
    """
    tel = getattr(ctx, "telemetry", None)
    return current() if tel is None or isinstance(tel, str) else tel


def note_trace(name: str) -> None:
    """Count one (re)trace of a jitted function.

    Call this inside the *python body* of a function handed to ``jax.jit``:
    the body only executes when XLA (re)traces, so the counter
    ``jit.retrace.<name>`` is exactly the retrace count -- a cheap cached-
    callable health check (a hot counter here means some argument keeps
    changing shape/dtype and the jit cache never warms).
    """
    current().count(f"jit.retrace.{name}")


def record_pad_waste(kernel: str, logical: tuple, padded: tuple) -> None:
    """Pad-to-block waste fraction of one kernel launch (trace-time).

    ``1 - prod(logical)/prod(padded)``: the fraction of the padded iteration
    space that computes zeros.  Recorded as a gauge (last launch) and a
    histogram (distribution over launches) on the current telemetry.
    """
    num = 1
    den = 1
    for lo, pa in zip(logical, padded):
        num *= int(lo)
        den *= int(pa)
    waste = 0.0 if den == 0 else 1.0 - num / den
    tel = current()
    tel.gauge(f"{kernel}.pad_waste", waste)
    tel.observe(f"{kernel}.pad_waste", waste)
