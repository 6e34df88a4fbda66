"""A training job on random tokens: batch ``i`` of a run is a pure function of
the seed and ``i``, made on the host, one more token than the sequence length."""
from __future__ import annotations

import numpy as np

KIND = "train"


def batch(seed: int, index: int, sequences: int, seq_len: int, vocab: int) -> np.ndarray:
    rng = np.random.default_rng([seed, index])
    return rng.integers(0, vocab, (sequences, seq_len + 1), dtype=np.int32)
