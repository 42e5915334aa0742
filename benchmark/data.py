"""The one traffic generator: rows and request decks from a traffic file's
parameters and the seed. The same seed gives the same inputs; every seed gives
the same sizes, in another order."""

from __future__ import annotations

import numpy as np

from benchmark import cells


def rows(cfg: dict, n: int, rng) -> tuple:
    """`n` labelled rows that all differ, (inputs, labels), from the
    generator the configuration names under `rows`."""
    return cells.load(cfg["rows"])(cfg, n, rng)


def _labels(cfg, n, rng):
    return rng.integers(0, cfg["num_labels"], n).astype(np.int32)


def token_rows(cfg: dict, n: int, rng) -> tuple:
    """[ids, token types, mask] of `seq_len` tokens with a ragged padded tail."""
    y, seq = _labels(cfg, n, rng), cfg["seq_len"]
    ids = rng.integers(0, cfg["vocab_size"], (n, seq)).astype(np.int32)
    types = np.zeros((n, seq), np.int32)
    mask = np.ones((n, seq), np.float32)
    tail = seq - 3 * seq // 4
    mask[:, seq - tail:] = rng.random((n, tail)) < 0.5
    return [ids, types, mask], y


def image_rows(cfg: dict, n: int, rng) -> tuple:
    """uint8 pixels, `image_size` squared by `num_channels`."""
    y, size = _labels(cfg, n, rng), cfg["image_size"]
    x = rng.integers(0, 256, (n, size, size, cfg["num_channels"]),
                     dtype=np.uint8)
    return x, y


def take(x, idx):
    return [a[idx] for a in x] if isinstance(x, list) else x[idx]


def request_deck(traffic: dict, rng) -> list:
    """Request sizes in rows: `deck_repeats` blocks, each the multiset that
    `rows` and `weights` state, shuffled within itself. Every seed gets the
    same sizes in another order, and no stretch of a block's length is
    heavier than another: the order moves the tail, so it is kept even."""
    block = np.repeat(np.asarray(traffic["rows"]), traffic["weights"])
    deck = np.concatenate([rng.permutation(block)
                           for _ in range(traffic["deck_repeats"])])
    return [int(r) for r in deck]
