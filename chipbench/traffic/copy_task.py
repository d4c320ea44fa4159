"""Full-length sequences whose second half repeats the first (a copy of
`horovod_tpu.data.datasets.copy_task`, kept here so that the yardstick does
not move with the program)."""

import numpy as np


def make(seed: int, params: dict, vocab_size: int):
    """``params``: seq_len (even), n_sequences. Tokens are drawn over the
    whole vocabulary but 0, which is the BOS. Returns next-token pairs
    ``(inputs, labels)``, int32 ``[n_sequences, seq_len]``."""
    seq_len, n = params["seq_len"], params["n_sequences"]
    if seq_len % 2:
        raise ValueError("seq_len must be even")
    rng = np.random.default_rng(seed)
    first = rng.integers(1, vocab_size, size=(n, seq_len // 2), dtype=np.int32)
    bos = np.zeros((n, 1), np.int32)
    tokens = np.concatenate([bos, first, first], axis=1)
    return tokens[:, :-1], tokens[:, 1:]
