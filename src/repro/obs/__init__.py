"""Flight recorder (DESIGN.md §12) — three layers over one run:

  * :mod:`repro.obs.telemetry` — in-graph ``RoundStats``: fixed-shape f32
    per-round telemetry (per-stage wire byte attribution, staleness
    histogram, buffer occupancy, residual-store counters, selection /
    availability counts) carried next to the ``CommLedger`` through every
    topology's metrics, gated by ``FLConfig.telemetry``;
  * :mod:`repro.obs.trace` — host-side tracer: versioned JSONL span/event
    sink (compile, chunk execute, eval, async flush, checkpoint) plus the
    opt-in ``jax.profiler`` hook around ``run_rounds`` chunks;
  * :mod:`repro.obs.report` — ``python -m repro.obs.report run.jsonl``:
    terminal / markdown run summary (byte waterfall, staleness histogram,
    time breakdown, claims-ready rows);
  * :mod:`repro.obs.scopes` — the naming contract between the round
    program and a device trace (``hop.*`` / ``stage.*`` named scopes,
    ``repro.*`` profiler annotations) and ``scope_table``, which reads it
    back out of a compiled HLO text.

The package import is lazy on purpose: ``trace``, ``report`` and
``scopes`` are stdlib-only (jax loads only inside the helpers that need
it), so the report CLI runs anywhere the JSONL file does — importing
:mod:`repro.obs` must not drag jax in.
"""
_LAZY = {
    "RoundStats": "telemetry", "TelemetrySpec": "telemetry",
    "round_stats": "telemetry", "telemetry_spec": "telemetry",
    "stage_byte_table": "telemetry", "staleness_hist": "telemetry",
    "zero_stats": "telemetry", "STALENESS_EDGES": "telemetry",
    "N_STALENESS_BUCKETS": "telemetry",
    "Tracer": "trace", "SCHEMA_VERSION": "trace",
    "validate_file": "trace", "validate_record": "trace",
    "scope_table": "scopes",
}

__all__ = sorted(_LAZY)


def __getattr__(name):
    if name in _LAZY:
        import importlib
        mod = importlib.import_module(f"repro.obs.{_LAZY[name]}")
        return getattr(mod, name)
    raise AttributeError(f"module 'repro.obs' has no attribute {name!r}")
