"""The chip benchmark: ``bench/run.py`` runs one cell of ``BENCHMARK.json``."""

import importlib.util
import pathlib


def load_module(path: pathlib.Path, name: str):
    """Import the file at ``path`` as module ``name``: configurations'
    references, drivers, generators and metric readers are found by the
    names ``BENCHMARK.json`` and the configuration files give."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
