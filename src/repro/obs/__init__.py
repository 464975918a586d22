"""repro.obs -- unified telemetry for the DSE->serving stack.

Collection: spans + counters/gauges/histograms (:mod:`.telemetry`),
JSONL/Chrome-trace export (:mod:`.export`), on-device io_callback metric
taps (:mod:`.device`).  Analysis + exposure: bench-history regression
sentinel (:mod:`.regress`) and Prometheus ``/metrics`` + ``/healthz``
endpoints (:mod:`.prom`).  Stdlib-only at import time; JAX is
touched lazily.
"""

from .telemetry import (
    GLOBAL,
    NULL,
    NullTelemetry,
    Span,
    Telemetry,
    as_telemetry,
    current,
    note_trace,
    of,
    record_pad_waste,
    use,
)
from .export import chrome_trace_dict, read_jsonl, write_chrome_trace, write_jsonl
from .device import flush, make_tap, null_tap

# The analysis/exposure layer resolves lazily (PEP 562): `python -m
# repro.obs.regress` would otherwise import .regress twice (package init +
# runpy __main__), and collection-side users shouldn't pay for it.
_LAZY = {
    "MetricsServer": "prom", "health_payload": "prom",
    "render_prometheus": "prom",
    "append_history": "regress", "compare": "regress",
    "latest_report": "regress", "load_report": "regress",
}


def __getattr__(name):
    mod = _LAZY.get(name)
    if mod is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib

    return getattr(importlib.import_module(f".{mod}", __name__), name)

__all__ = [
    "GLOBAL",
    "NULL",
    "NullTelemetry",
    "Span",
    "Telemetry",
    "as_telemetry",
    "current",
    "note_trace",
    "of",
    "record_pad_waste",
    "use",
    "chrome_trace_dict",
    "read_jsonl",
    "write_chrome_trace",
    "write_jsonl",
    "flush",
    "make_tap",
    "null_tap",
    "MetricsServer",
    "health_payload",
    "render_prometheus",
    "append_history",
    "compare",
    "latest_report",
    "load_report",
]
