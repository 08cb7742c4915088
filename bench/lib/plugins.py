"""Everything that belongs to one dataset, one kind of traffic, one kind
of operation, one plain reference or one metric is a file of its own,
found by the name a data file gives:

    datasets/<name>.py    configs/<config>.json  "dataset"
    drivers/<name>.py     traffic/<mix>.json     "driver"
    ops/<name>.py         traffic/<mix>.json     "op"
    reference/<name>.py   the op's own name, or the mix's "reference"
    metrics/<name>.py     BENCHMARK.json         a metric's "name"

A later PR adds a file and names it; it edits none that is there."""

from __future__ import annotations

import importlib.util
import os
import re
import sys

KINDS = ("datasets", "drivers", "ops", "reference", "metrics")


def load(bench_dir: str, kind: str, name: str):
    """The module `<bench_dir>/<kind>/<name>.py`, loaded once per path."""
    if kind not in KINDS:
        raise ValueError(f"no such kind of file: {kind!r}")
    path = os.path.join(os.path.abspath(bench_dir), kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(
            f"{kind[:-1] if kind.endswith('s') else kind} {name!r} has no "
            f"file at {path}")
    key = "bench_" + kind + "_" + re.sub(r"\W", "_", name) + "_" + \
        format(hash(path) & 0xFFFFFFFF, "x")
    if key in sys.modules:
        return sys.modules[key]
    spec = importlib.util.spec_from_file_location(key, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[key] = module
    try:
        spec.loader.exec_module(module)
    except BaseException:
        del sys.modules[key]
        raise
    return module
