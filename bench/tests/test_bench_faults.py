"""The check that decides ``correct`` fails a broken round and passes a
sound one. Cases: no fault, a round that returns its state unchanged, and
half of the cohort left out with the mean taken over the rest (the faults
a one-chip training round can have)."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import fault_cases  # noqa: E402
from fault_cases import quick_compiles  # noqa: E402,F401


@pytest.mark.parametrize("fault", [None, fault_cases.unchanged,
                                   fault_cases.half])
def test_correct_comes_out_false_under_a_fault(fault, monkeypatch,
                                              quick_compiles):  # noqa: F811
    fault_cases.check(fault, monkeypatch)
