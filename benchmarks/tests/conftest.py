import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


@pytest.fixture
def second_family(monkeypatch):
    """The family `two` (`data/two/family_two.py`, `program_two.py`) found as
    files of `benchmarks/harness/` would be, with no file there edited."""
    from benchmarks import harness

    two = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "two")
    monkeypatch.setattr(harness, "__path__", [*harness.__path__, two])
    yield
    for name in ("family_two", "program_two"):
        sys.modules.pop(f"benchmarks.harness.{name}", None)
