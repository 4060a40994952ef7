"""Faults planted in the program underneath a test-size run of the harness
(``test_bench_faults.py``).

The cases share what a fault cannot change: the reference's readings (the
reference takes nothing from the program) and, for the cases that leave
the program's code alone, the compiled round.  So the test compiles the
round twice, once sound and once with half of the cohort left out.
"""
import os
import sys
import time

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

from benchlib import build as build_mod  # noqa: E402
from benchlib import fl_ref, harness, spec  # noqa: E402

SEED = 12345678901
_shared = {}


@pytest.fixture
def quick_compiles():
    """Most XLA optimizations off while these tests compile: the numbers
    compared are the same to rounding, and compiles take half the time."""
    import jax
    before = jax.config.read("jax_disable_most_optimizations")
    jax.config.update("jax_disable_most_optimizations", True)
    yield
    jax.config.update("jax_disable_most_optimizations", before)


def _resolved():
    data = os.path.join(BENCH, "testdata")
    return {"name": "tiny", "chips": 1,
            "config": spec.load_json(os.path.join(data, "tiny-ssm.json")),
            "traffic": spec.load_json(os.path.join(data, "tiny-qsgd.json")),
            "end_to_end": spec.benchmark(ROOT)["end_to_end"],
            "per_layer": [], "seconds": 0}


def _share_reference(monkeypatch):
    rounds = fl_ref.Reference.rounds

    def shared(self, *args, **kw):
        if "reference" not in _shared:
            _shared["reference"] = rounds(self, *args, **kw)
        return _shared["reference"]
    monkeypatch.setattr(fl_ref.Reference, "rounds", shared)


def _share_sound_cell(monkeypatch):
    build = build_mod.build

    def shared(*args, **kw):
        if "cell" not in _shared:
            _shared["cell"] = build(*args, **kw)
        return _shared["cell"]
    monkeypatch.setattr(build_mod, "build", shared)


def unchanged(monkeypatch):
    """Each round runs, and the step returns the state it was given."""
    import jax
    import jax.numpy as jnp
    _share_sound_cell(monkeypatch)
    real = build_mod.build

    def build(*args, **kw):
        cell = real(*args, **kw)
        run = cell.runner.run

        def stuck(state, n):
            kept = jax.tree.map(jnp.copy, state)
            _, metrics = run(state, n)
            return kept, metrics
        monkeypatch.setattr(cell.runner, "run", stuck)
        return cell
    monkeypatch.setattr(build_mod, "build", build)


def half(monkeypatch):
    from repro.core import selection
    select = selection.select

    def half(*a, **kw):
        w = select(*a, **kw)
        return w.at[w.shape[0] - w.shape[0] // 2:].set(0.0)
    monkeypatch.setattr(selection, "select", half)


def check(fault, monkeypatch):
    """Drive a test-size cell through the harness (without its look for a
    chip) with ``fault`` planted; ``correct`` must be false, and true with
    no fault."""
    import jax
    _share_reference(monkeypatch)
    if fault is None:
        _share_sound_cell(monkeypatch)
    else:
        fault(monkeypatch)
    out = harness.run_cell(_resolved(), SEED, 0.0, False,
                           time.perf_counter(), jax.devices(),
                           log=lambda m: None)
    assert out["correct"] is (fault is None), out["check"]
