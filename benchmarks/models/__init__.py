"""One file per architecture: the parameter tree the system under test
takes (shapes by the benchmark's own table, weights from the seed in one
jitted call) and the builder of the program's model object.  ``run.py``
finds a file by the ``model`` named in the configuration file."""

import importlib


def load(name):
    return importlib.import_module(f"benchmarks.models.{name}")


def load_with_reference(name):
    """The architecture's file and the plain reference it names."""
    model = load(name)
    return model, importlib.import_module(model.REFERENCE)
