"""A model family, declared in data: `families/<name>.json`.

A configuration's file names its family (`"family"`); the family's file says
which keys describe the model, which of them are widths (never cut), which
may be cut to a chip's share (`shares`: depth, and what one chip holds of a
layer), and which modules of `benchmark/` hold the family's counts, its plain
reference and its weight draw. Nothing outside a family's own files knows its
keys, or where in a configuration's file they sit: the family's modules and
drivers read the file they are handed (tests/benchmark/bench_checks.py holds
the file to the family's declaration).

Every count function is called one way, whatever the family:
`fn(config, spec)` -> operations or bytes of one unit of work, where `config`
is the configuration's file, whole, and `spec` the workload's file.
"""

from __future__ import annotations

import importlib
import json
import os
from typing import Dict


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load(data_dir: str, name: str) -> Dict:
    return load_json(os.path.join(data_dir, "families", name + ".json"))


def module(family: Dict, kind: str):
    """The family's `counts`, `reference` or `weights` module."""
    return importlib.import_module("benchmark." + family["modules"][kind])


def count(context: Dict, name: str) -> float:
    """`name` of the counts module of the family a run's context holds,
    called on that run's configuration and workload."""
    return getattr(module(context["family"], "counts"), name)(context["config"], context["spec"])
