"""Traffic generators, one general generator per family of mixes.  A mix is
a data file under ``benchmarks/traffic/`` that names its generator; a new
mix needs no code.  Everything is drawn from ``--seed``."""

import importlib


def load(name):
    return importlib.import_module(f"benchmarks.generators.{name}")
