"""Unified telemetry: typed per-round event streams, profiler spans and
state digests across all three round engines (host loop, ``lax.scan``
engine, mesh-sharded engine).

Quick start::

    from repro.telemetry import Telemetry
    from repro.federated import run_simulation

    with Telemetry.to_jsonl("events.jsonl") as tel:
        run_simulation(flcfg, rounds=20, telemetry=tel)

then ``python -m repro.telemetry.report events.jsonl``.

Layout: ``schema`` (event types + the ``RunContext`` factory +
validation), ``sinks`` (JSONL / ring buffer / recorder), ``taps``
(ordered ``jax.debug.callback`` streaming out of jitted scans — zero
ops when disabled), ``spans`` (TraceAnnotation host spans with timed
``span`` events), ``provenance`` (git/host stamps), ``report``
(validation CLI + wire-breakdown tables from events alone).
"""
from repro.telemetry.provenance import stamp
from repro.telemetry.schema import (ENGINES, EVENT_TYPES, SCHEMA,
                                    RunContext, delivered_sha, encode,
                                    validate_event, validate_events)
from repro.telemetry.sinks import (JsonlSink, ListSink, RingBufferSink,
                                   Telemetry)
from repro.telemetry.spans import span
from repro.telemetry.taps import TapSpec, collecting, instrument

__all__ = [
    "SCHEMA", "EVENT_TYPES", "ENGINES", "RunContext", "delivered_sha",
    "encode", "validate_event", "validate_events",
    "Telemetry", "JsonlSink", "RingBufferSink", "ListSink",
    "TapSpec", "collecting", "instrument",
    "span", "stamp",
]
