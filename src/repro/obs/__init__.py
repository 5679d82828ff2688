"""repro.obs — structured observability for the fleet DR engine.

Four pieces, one schema:

  * `TelemetryConfig` / `ConvergenceTrace` — in-solve convergence
    telemetry, captured INSIDE the jitted AL loop as stacked aux
    outputs and surfaced as `result.extras["telemetry"]`
    (`SolveContext(telemetry=TelemetryConfig(every=10))`).
  * `EventWriter` / `read_events` — atomic, schema-versioned JSONL
    ledger of typed events (streaming ticks, spans, telemetry
    samples, benchmark runs).
  * `span` / `spans.recorded()` — the program's span recorder: each
    span carries `start_ns`/`end_ns` (perf_counter), its parent span and
    tick ids and any counts (`sp.count("h2d_bytes", n)`), opens a
    `jax.profiler.TraceAnnotation` of its name, and lands in a bounded,
    always-on ring (`spans.recorded()`, `spans.clear()`). The tick path
    opens `dr.tick` with its phases and `dr.solve` with its dispatch,
    wait, pull and report.
  * `python -m repro.obs.report run.jsonl` — terminal report
    (convergence curves, tick ledger, recompile audit).

Import discipline: importing `repro.obs` loads neither jax nor
`repro.core`, so the core engine can depend on it without cycles and
the report CLI runs anywhere the ledger lands.
"""
from repro.obs.events import (SCHEMA_VERSION, EventWriter, SpanEvent,
                              TelemetryEvent, TickEvent, host_meta,
                              read_events)
from repro.obs import spans
from repro.obs.spans import SpanRecord, SpanScope, span
from repro.obs.telemetry import ConvergenceTrace, TelemetryConfig

__all__ = [
    "SCHEMA_VERSION", "EventWriter", "SpanEvent", "TelemetryEvent",
    "TickEvent", "host_meta", "read_events",
    "SpanRecord", "SpanScope", "span", "spans",
    "ConvergenceTrace", "TelemetryConfig",
]
