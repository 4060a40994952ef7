"""Host-side tracer: a versioned JSONL span/event sink (DESIGN.md §12).

One record per line, every record carrying ``{"v": SCHEMA_VERSION}``.  Four
record kinds make up schema v1:

  * ``meta``   — first line of every file: ``schema``, wall-clock ``ts``,
    plus whatever run metadata the writer attached (arch, topology, ...);
  * span records (``"type": "span"``) — a timed section: ``kind`` names it
    (``compile`` — a chunk whose shape compiled here, including its first
    execution, ``chunk`` — a warm chunk execution, ``eval``,
    ``checkpoint``), with ``ts`` (wall clock at entry) and ``dur_s``;
  * ``event`` records — instantaneous marks (``flush`` — an async buffer
    flush derived from the round metrics, custom marks);
  * ``stages`` / ``round`` — machine-readable telemetry: ``stages`` names
    the RoundStats byte slots once, then one ``round`` record per round
    with every metric leaf flattened to ``m`` (scalars; NaN -> null, which
    is how eval-cadence gaps serialize).

Stdlib-only at import (jax loads lazily inside the helpers that need it),
so ``repro.obs.report`` can validate and render anywhere.
"""
from __future__ import annotations

import contextlib
import json
import sys
import time

from repro.obs.scopes import ANNOTATION

SCHEMA_VERSION = 1


def _json_scalar(x: float):
    x = float(x)
    return None if x != x else x      # NaN (cadence-skipped eval) -> null


def _path_name(entry) -> str:
    for attr in ("key", "name", "idx"):
        v = getattr(entry, attr, None)
        if v is not None:
            return str(v)
    return str(entry)


class Tracer:
    """Append-only JSONL sink.  Construct with the ``--trace`` path; every
    write flushes, so a killed run keeps its prefix."""

    def __init__(self, path: str, profile_dir: str = "", meta: dict = None):
        self.path = str(path)
        self.profile_dir = profile_dir or ""
        self._f = open(self.path, "w")
        self._write(dict(kind="meta", schema=SCHEMA_VERSION,
                         ts=time.time(), **(meta or {})))

    # ------------------------------------------------------------------ sink
    def _write(self, rec: dict) -> None:
        rec = {"v": SCHEMA_VERSION, **rec}
        self._f.write(json.dumps(rec) + "\n")
        self._f.flush()

    def event(self, kind: str, **attrs) -> None:
        self._write(dict(kind=kind, type="event", ts=time.time(), **attrs))

    @contextlib.contextmanager
    def span(self, kind: str, **attrs):
        """Timed section; yields a mutable attrs dict so the body can
        retag itself (e.g. a chunk span upgrading to ``compile`` once the
        jit cache says this shape compiled here).  Where jax is already
        loaded, the span also opens a ``repro.<kind>`` profiler annotation,
        so a ``--profile-dir`` trace shows it on the device's timeline."""
        rec = dict(kind=kind, **attrs)
        jax = sys.modules.get("jax")
        note = (jax.profiler.TraceAnnotation(ANNOTATION + kind)
                if jax is not None else contextlib.nullcontext())
        ts, t0 = time.time(), time.perf_counter()
        try:
            with note:
                yield rec
        finally:
            self._write(dict(type="span", ts=ts,
                             dur_s=time.perf_counter() - t0, **rec))

    def close(self) -> None:
        self._f.close()

    # ----------------------------------------------------------- jax helpers
    def profile(self):
        """Context manager: ``jax.profiler`` trace around the run when
        ``--profile-dir`` was given, else a no-op."""
        if not self.profile_dir:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.trace(self.profile_dir)

    def emit_rounds(self, metrics, spec=None, start_round: int = 0) -> None:
        """Write the stacked ``run_rounds`` metrics as one ``round`` record
        per row.  ``spec`` (a TelemetrySpec) writes the ``stages`` naming
        record first.  Metric leaves flatten to dotted names
        (``ledger.uplink_wire``, ``round_stats.up_stage_bytes``); vector
        leaves serialize as lists, NaN as null."""
        import jax
        import numpy as np
        if metrics is None:
            return
        if spec is not None:
            self._write(dict(kind="stages", up=list(spec.up_names),
                             down=list(spec.down_names)))
        flat = {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(metrics)[0]:
            flat[".".join(_path_name(p) for p in path)] = np.asarray(leaf)
        if not flat:
            return
        n = len(next(iter(flat.values())))
        for i in range(n):
            row = {}
            for k, v in flat.items():
                x = v[i]
                row[k] = (_json_scalar(x) if x.ndim == 0 else
                          [_json_scalar(y) for y in np.ravel(x)])
            self._write(dict(kind="round", round=start_round + i, m=row))


# ---------------------------------------------------------------------------
# schema validation (stdlib; used by tests and the obs-smoke CI leg)
# ---------------------------------------------------------------------------

def validate_record(rec: dict) -> None:
    """Raise ValueError when ``rec`` is not a well-formed v1 record."""
    if rec.get("v") != SCHEMA_VERSION:
        raise ValueError(f"schema version {rec.get('v')!r} != "
                         f"{SCHEMA_VERSION}")
    kind = rec.get("kind")
    if not isinstance(kind, str) or not kind:
        raise ValueError(f"record missing 'kind': {rec}")
    if kind == "meta" and rec.get("schema") != SCHEMA_VERSION:
        raise ValueError(f"meta record schema mismatch: {rec}")
    if rec.get("type") == "span" and not isinstance(
            rec.get("dur_s"), (int, float)):
        raise ValueError(f"span record missing dur_s: {rec}")
    if kind == "round":
        if not isinstance(rec.get("m"), dict):
            raise ValueError(f"round record missing metrics dict: {rec}")
        if not isinstance(rec.get("round"), int):
            raise ValueError(f"round record missing round index: {rec}")
    if kind == "stages" and not isinstance(rec.get("up"), list):
        raise ValueError(f"stages record missing slot names: {rec}")


def validate_file(path: str) -> list:
    """Validate every line of a trace file; the first record must be the
    ``meta`` header.  Returns the parsed records."""
    records = []
    with open(path) as fh:
        for ln, line in enumerate(fh):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError as e:
                raise ValueError(f"{path}:{ln + 1}: not JSON: {e}") from e
            validate_record(rec)
            records.append(rec)
    if not records:
        raise ValueError(f"{path}: empty trace")
    if records[0].get("kind") != "meta":
        raise ValueError(f"{path}: first record must be the meta header, "
                         f"got {records[0].get('kind')!r}")
    return records
