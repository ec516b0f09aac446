"""The one general generator of training batches: a mix's parameters +
a seed -> the token rows of each step.

A mix gives ``seq`` (tokens per row) and ``rows`` ("uniform_ids": every
id uniform over the vocabulary, every row different). The batch size
belongs to the configuration (it is what fits its memory). numpy only.
"""

from __future__ import annotations

from typing import Any, Dict, Iterator

import numpy as np


def generate(mix: Dict[str, Any], seed: int, batch: int,
             vocab_size: int) -> Iterator[np.ndarray]:
    """Yield int32 ``[batch, seq]`` arrays for ever; the same seed gives
    the same rows in the same order."""
    if mix.get("rows", "uniform_ids") != "uniform_ids":
        raise ValueError(f"unknown row kind {mix['rows']!r}")
    rng = np.random.default_rng([int(seed), 0xBA7C])
    seq = int(mix["seq"])
    while True:
        yield rng.integers(0, vocab_size, (batch, seq), dtype=np.int32)
