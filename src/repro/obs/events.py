"""Structured run events: typed records + an atomic JSONL ledger.

One schema for everything the repo measures — streaming tick ledgers
(`core.streaming`), span timings (`obs.spans`), per-solve convergence
samples, and benchmark runs (`benchmarks/run.py`) all write through
`EventWriter`, so a single `python -m repro.obs.report run.jsonl` can
render any of them.

File format: one JSON object per line. The first record is always a
header (`{"kind": "header", "schema": N, "host": {...}}`) written when
the writer opens an empty file; `read_events` refuses files whose
header is missing or whose schema doesn't match `SCHEMA_VERSION` —
the pin that keeps old ledgers from being silently misread.

Appends are atomic: the fd is opened `O_APPEND` and each record goes
down in a single `os.write`, so concurrent writers (benchmark
subprocesses, a solver thread) interleave whole lines, never bytes.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, ClassVar, Optional

__all__ = ["SCHEMA_VERSION", "host_meta", "TickEvent", "SpanEvent",
           "TelemetryEvent", "EventWriter", "read_events"]

SCHEMA_VERSION = 2


def host_meta() -> dict:
    """Host/device fingerprint stamped into ledger headers + BENCH json.

    Imports jax lazily so report-side consumers (and tests) can call
    into `obs.events` without initializing a backend.
    """
    import jax

    devices = jax.devices()
    try:
        import jaxlib
        jaxlib_version = jaxlib.__version__
    except Exception:  # pragma: no cover - jaxlib always ships with jax
        jaxlib_version = None
    return {
        "platform": jax.default_backend(),
        "n_devices": len(devices),
        "device_kind": devices[0].device_kind if devices else None,
        "jax": jax.__version__,
        "jaxlib": jaxlib_version,
        "pallas_interpret": os.environ.get("REPRO_PALLAS_INTERPRET", ""),
    }


@dataclasses.dataclass(frozen=True)
class TickEvent:
    """One streaming tick of the rolling-horizon ledger."""
    kind: ClassVar[str] = "tick"
    tick: int
    revision: float              # ‖forecast − previous shifted‖ / ‖prev‖
    warm_steps: int              # inner-step budget actually spent
    cold: bool                   # True on the cold (tick-0 / reset) solve
    objective_proxy: Optional[float]  # carbon_reduction_pct of the plan
    latency_s: float             # wall clock of the `dr.tick` span up to
                                 # this record (0.0 on the later ticks
                                 # of a day scan's single dispatch)
    committed_carbon: list       # per-region kgCO2 committed this tick
    realized_carbon: list        # per-region kgCO2 at realized MCI
    migration_credit: float      # net kgCO2 saved by cross-region moves
    recompiles: int              # jit traces attributed to this tick
    dispatches: int              # device dispatches counted in the tick
    phases_ms: Optional[dict] = None  # self time of each span under
                                      # `dr.tick`, by name
    h2d_bytes: int = 0           # host bytes passed into the dispatches
    d2h_bytes: int = 0           # bytes pulled back to the host


@dataclasses.dataclass(frozen=True)
class SpanEvent:
    """One timed span (see `obs.spans.span`)."""
    kind: ClassVar[str] = "span"
    name: str
    elapsed_s: float
    meta: Optional[dict] = None
    start_ns: Optional[int] = None    # time.perf_counter_ns() at entry
    parent: Optional[int] = None      # id of the enclosing span
    tick: Optional[int] = None        # tick index the span belongs to
    counts: Optional[dict] = None     # e.g. {"h2d_bytes": n}


@dataclasses.dataclass(frozen=True)
class TelemetryEvent:
    """One in-solve convergence sample, tagged with its tick."""
    kind: ClassVar[str] = "telemetry"
    tick: int
    step: int
    objective: float
    grad_norm: float
    violation: float
    dx: float
    mu: float


class EventWriter:
    """Append-only JSONL ledger with a schema-versioned header.

    Usage::

        with EventWriter("run.jsonl", tags={"policy": "cr1"}) as w:
            w.write(TickEvent(...))

    The header (schema version + `host_meta()` + tags) is written only
    when the file is empty, so re-opening an existing ledger appends
    events under the original header.
    """

    def __init__(self, path, *, tags: dict | None = None):
        self.path = os.fspath(path)
        parent = os.path.dirname(self.path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        self._fd = os.open(self.path,
                           os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
        if os.fstat(self._fd).st_size == 0:
            self._write_record({"kind": "header", "schema": SCHEMA_VERSION,
                                "host": host_meta(), "tags": tags or {}})

    def _write_record(self, rec: dict) -> None:
        os.write(self._fd, (json.dumps(rec) + "\n").encode())

    def write(self, event: Any) -> None:
        """Append one event (a typed record dataclass, or a plain dict)."""
        if dataclasses.is_dataclass(event) and not isinstance(event, type):
            rec = {"kind": type(event).kind, **dataclasses.asdict(event)}
        elif isinstance(event, dict):
            if "kind" not in event:
                raise ValueError("dict events need an explicit 'kind'")
            rec = event
        else:
            raise TypeError(
                f"EventWriter.write wants an event dataclass or dict, "
                f"got {type(event).__name__}")
        self._write_record(rec)

    def close(self) -> None:
        if self._fd is not None:
            os.close(self._fd)
            self._fd = None

    def __enter__(self) -> "EventWriter":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def read_events(path) -> list[dict]:
    """Read a JSONL ledger, validating the schema pin.

    Returns every record (header first). Raises `ValueError` when the
    file has no header record or the header's schema version is not
    `SCHEMA_VERSION`.
    """
    records = []
    with open(os.fspath(path), "r", encoding="utf-8") as fh:
        for line in fh:
            line = line.strip()
            if line:
                records.append(json.loads(line))
    if not records or records[0].get("kind") != "header":
        raise ValueError(
            f"{path}: not an event ledger (first record must be a "
            f"'header'; found "
            f"{records[0].get('kind') if records else 'empty file'!r})")
    schema = records[0].get("schema")
    if schema != SCHEMA_VERSION:
        raise ValueError(
            f"{path}: ledger schema {schema!r} != supported "
            f"{SCHEMA_VERSION} — re-record or use a matching reader")
    return records
