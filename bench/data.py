"""The data of a configuration, made by the generator it names.

A configuration (``bench/configs/<name>.json``) names its generator, a file
``bench/generators/<generator>.py`` with ``generate(cfg, seed)``, which
returns a :class:`Dataset`: the triple array, the node and label names, and
the entity kinds that traffic files draw constants from.  A new generator
is a new file; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent


@dataclasses.dataclass
class Dataset:
    """One generated graph: ids, names and the entity kinds in it."""

    triples: np.ndarray  # int32 [E, 3]: (subject, label, object)
    node_names: list
    label_names: list
    # kind -> node ids of that kind, in the kind's own order
    kinds: dict

    @property
    def n_nodes(self) -> int:
        return len(self.node_names)

    def node_index(self) -> dict:
        return {n: i for i, n in enumerate(self.node_names)}


def rng(seed: int, salt: int) -> np.random.Generator:
    """A generator of its own for each use (``salt``) of one run seed."""
    s = seed % 2**64
    return np.random.default_rng([s & 0xFFFFFFFF, s >> 32, salt])


def load_by_name(kind: str, name: str, bench: Path | None = None):
    """The module ``bench/<kind>/<name>.py``, loaded from its file."""
    path = (bench or BENCH) / kind / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no {kind} file {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.cache
def generator(name: str, bench: Path | None = None):
    """``bench/generators/<name>.py``'s ``generate(cfg, seed)``."""
    return load_by_name("generators", name, bench).generate


def generate(cfg: dict, seed: int) -> Dataset:
    return generator(cfg["generator"])(cfg, seed)
