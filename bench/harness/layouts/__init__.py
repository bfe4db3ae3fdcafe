"""Corpus layouts, found by the name a training traffic file gives as
``layout``: ``harness/layouts/<name>.py`` defines

    corpus(config, traffic, seed, cfg) -> Corpus

from the cell's configuration and traffic (dicts), the run's seed and the
program's ``W2VConfig``. A new layout is a new module; the training
harness knows none by name.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import List

import numpy as np


@dataclasses.dataclass
class Corpus:
    """What a layout generates for one seed."""
    sentences: List[np.ndarray]   # int32 ids in [0, vocab)
    vocab: int
    # the vocabulary's counts: they build the program's ``Vocab`` (so its
    # subsampling and negative laws) and the checks' laws
    counts: np.ndarray
    stream: bool                  # rows are stream-packed, not sentences


def load(name: str):
    """The layout module of that name."""
    return importlib.import_module(f"{__name__}.{name}")
