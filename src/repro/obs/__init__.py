"""repro.obs — unified tracing + metrics for the FLYCOO engine.

One observability surface across every layer: hierarchical wall-clock
spans (:mod:`~repro.obs.trace`) over plan → autotune → stream → dist →
ALS sweep → backend dispatch, a labeled counter/gauge/histogram registry
(:mod:`~repro.obs.metrics`), a Chrome-trace exporter
(:mod:`~repro.obs.export`), run summaries plus the span-derived overlap
cross-check (:mod:`~repro.obs.report`), and peak-memory probes
(:mod:`~repro.obs.probe`).

Quick start::

    from repro import obs

    obs.enable()                      # or: REPRO_TRACE=1 / =trace.json
    result = cp_als(tensor, rank=8)
    obs.write_chrome_trace("trace.json")   # load in ui.perfetto.dev
    print(obs.render_report())

Everything is zero-dependency and free when disabled: the module-level
:func:`span` is a single ``is None`` test returning a shared no-op when
no tracer is installed (CI gates traced entry points at < 5% overhead
with tracing off).

What the chip benchmark (``bench/``) reads. While it traces, every span
is mirrored into the JAX profiler as a ``TraceAnnotation``, on the
device trace's clock, and idle gaps of the chip are named by them:

* ``cpd.start`` (one ``cp_als`` start up to its first sweep),
  ``engine.init`` (``start_init_s``) and, after it, ``engine.upload``
  (``start_upload_s``; attribute ``bytes``, ended once the state is on
  the device -- it waits only while tracing);
* ``cpd.sweep`` (attribute ``fit``), ``engine.dispatch`` and ``cpd.fit``,
  the host's blocking read of the sweep's fit;
* the gauge ``engine_row_copies`` per mode, the factor-row DMAs of one EC
  kernel pass (``ec_ns_per_row_copy``).

The sweep program's ``mode<d>/{ec,remap,fold}`` named scopes are read
through ``repro.engine.api.op_scopes`` (``remap_scope_ms``, ``fold_ms``,
``unscoped_ms``).
"""
from .export import chrome_trace, validate_chrome_trace, write_chrome_trace
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry, REGISTRY,
                      counter, gauge, histogram)
from .probe import device_peak_bytes, memory_probe
from .report import (render_report, resilience_report,
                     stream_overlap_from_chrome, stream_overlap_from_spans,
                     time_tree)
from .trace import (ENV_VAR, NULL_SPAN, SpanRecord, Tracer, disable, enable,
                    get_tracer, is_enabled, span, traced)

__all__ = [
    # trace
    "span", "traced", "Tracer", "SpanRecord", "NULL_SPAN", "enable",
    "disable", "is_enabled", "get_tracer", "ENV_VAR",
    # metrics
    "REGISTRY", "MetricsRegistry", "Counter", "Gauge", "Histogram",
    "counter", "gauge", "histogram",
    # export
    "chrome_trace", "write_chrome_trace", "validate_chrome_trace",
    # report
    "render_report", "resilience_report", "time_tree",
    "stream_overlap_from_spans", "stream_overlap_from_chrome",
    # probe
    "memory_probe", "device_peak_bytes",
]
