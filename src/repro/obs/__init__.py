"""Structured telemetry for the federated runtime (docs/observability.md).

The subsystem has four layers, each usable on its own:

* `repro.obs.schema`  — the versioned record schema: a registry of
  metric names/dtypes/units, the per-record-type field sets, and
  `validate_record` (exact int64 byte counters, no silent coercion).
* `repro.obs.sinks`   — JSONL file sink, bounded in-memory ring, and
  `RunRecorder`, which validates every record, fans it out to both
  sinks and writes a CI-consumable run manifest on close.
* `repro.obs.probes`  — device-side Sophia health metrics (clip
  fraction, m/h norms, curvature freshness), computed INSIDE the
  jitted round with no extra host syncs, plus `MetricsAccumulator`
  (`repro.obs.buffer`), the packed device-side metrics buffer that
  defers the host sync to the eval/checkpoint flush boundary.
* `repro.obs.spans`   — host-side span timers correlated with the
  scheduler's virtual clock, the opt-in `jax.profiler` trace hooks
  (`--profile-dir` in `repro.launch.train` / `serve`), and the round's
  named device phases (`phase`, `PHASES`).
* `repro.obs.trace`   — Chrome Trace Event / Perfetto export of a
  run's trace contexts (`ObsConfig.trace`), plus the structural
  validator `make obs-trace-smoke` gates on.
* `repro.obs.logio`   — tolerant record readers for finished or
  still-growing logs (JSONL, record arrays, legacy bench dicts),
  shared by every tool under tools/.
"""
from repro.obs.buffer import MetricsAccumulator
from repro.obs.logio import ObsLogError, read_records
from repro.obs.probes import PROBE_METRICS, sophia_health
from repro.obs.schema import (SCHEMA_VERSION, SUPPORTED_SCHEMA_VERSIONS,
                              ObsSchemaError, describe, fingerprint,
                              validate_record)
from repro.obs.sinks import JsonlSink, RingSink, RunRecorder
from repro.obs.spans import PHASES, SpanLog, annotate, phase, profile_trace
from repro.obs.trace import chrome_trace, validate_chrome_trace

__all__ = [
    "SCHEMA_VERSION", "SUPPORTED_SCHEMA_VERSIONS", "ObsSchemaError",
    "describe", "fingerprint", "validate_record",
    "JsonlSink", "RingSink", "RunRecorder",
    "MetricsAccumulator", "PROBE_METRICS", "sophia_health",
    "SpanLog", "annotate", "profile_trace", "PHASES", "phase",
    "ObsLogError", "read_records",
    "chrome_trace", "validate_chrome_trace",
]
