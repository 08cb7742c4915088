"""bench/tests run on the CPU (`JAX_PLATFORMS=cpu python -m pytest
bench/tests -q`): they import the benchmark's own modules from bench/."""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def plug(kind, name):
    """A module of one of the benchmark's plug directories, by name."""
    from lib import plugins

    return plugins.load(BENCH, kind, name)
