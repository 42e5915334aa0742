"""Rows for a causal language model: token ids and, as labels, the next token
of each position. A new row generator beside `data.py`, named by a
configuration's `rows`."""

from __future__ import annotations

import numpy as np


def next_token_rows(cfg: dict, n: int, rng) -> tuple:
    """`n` documents of `seq_len` + 1 ids, uniform over the `vocab_size` ids
    of the slice held here: inputs are a document's first `seq_len` tokens,
    labels its last `seq_len` (one document a row, no packing)."""
    t = rng.integers(0, cfg["vocab_size"], (n, cfg["seq_len"] + 1)).astype(np.int32)
    return np.ascontiguousarray(t[:, :-1]), np.ascontiguousarray(t[:, 1:])
