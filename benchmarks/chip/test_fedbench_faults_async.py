"""The faults of test_fedbench_faults, in the charlm-async cell."""
from __future__ import annotations

import pytest

from test_fedbench_faults import FAULTS, broken_run_is_not_correct


@pytest.mark.parametrize("fault", FAULTS)
def test_a_broken_timed_path_is_not_correct(fault, monkeypatch):
    broken_run_is_not_correct("charlm-async", fault, monkeypatch)
