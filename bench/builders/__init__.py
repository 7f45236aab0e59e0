"""Configuration builders, found by the ``builder`` key of a config file.

A builder turns a configuration (``bench/configs/<name>.json``), the
run's seed and its schedule into a ``Built``: the DSL the service
compiles, the embedder, one text per request, and the plain reference
of the routing decision.  A new kind of configuration adds a module
here; a new configuration of a known kind adds only its file.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np


@dataclasses.dataclass
class Built:
    dsl: str
    embedder_dim: int
    embedder: Optional[Any]        # None: the router's HashEmbedder
    texts: List[str]
    make_texts: Callable[..., List[str]]   # (items, rng, tag) -> texts
    # the plain reference, made only after the window: () -> (RefPolicy,
    # texts -> float64 embeddings)
    reference: Callable[[], Tuple[Any, Callable[[List[str]], np.ndarray]]]
    backends: List[Dict[str, Any]] # name, arch, size, seed
    service: Dict[str, Any]        # RouterService keyword arguments
    tie_margin: float


def load(name: str):
    return importlib.import_module(f"bench.builders.{name}")
