"""Bare-checkout collection shim.

The package lives under ``src/`` (setuptools src-layout); a fresh clone
without ``pip install -e .`` or a manual ``PYTHONPATH=src`` would fail
collection with ``ModuleNotFoundError: repro``.  Prepending ``src/`` here
makes ``python -m pytest`` work from any checkout -- and is a no-op when
the package is installed (the repo copy simply wins, which is what the
tier-1 run wants anyway).  It also holds the fixtures shared by several
test files.
"""

import sys
from pathlib import Path

import pytest

_SRC = str(Path(__file__).resolve().parent.parent / "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

from repro import obs  # noqa: E402  (needs src/ on the path)


@pytest.fixture
def tel():
    """Enabled, empty global telemetry; restores prior state afterwards."""
    prev = obs.enabled()
    obs.reset()
    obs.enable()
    yield obs.TELEMETRY
    obs.reset()
    if not prev:
        obs.disable()
