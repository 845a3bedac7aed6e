"""The traffic generator of a language-model training cell: packed rows of
token ids, made with numpy from the seed and a traffic file's parameters.
Every row is `seq_len` long (packed documents: no padding, causal over the
whole row), ids uniform over the held vocabulary slice; `labels[:, t]` is the
next id, the last position -100 (left out of the loss). Every seed gives the
same sizes with other contents; `lib/datagen.py`'s `n_rows` and `RowIndex`
serve here too."""

from __future__ import annotations

import numpy as np

from .datagen import n_rows


def make_rows(config: dict, traffic: dict, seed: int) -> dict:
    """Column dict for `MemorySource`: `input_ids`, `labels`, int32 [rows, seq_len]."""
    rng = np.random.default_rng(int(seed))
    t = int(traffic["text"]["seq_len"])
    rows = n_rows(traffic, row_bytes=2 * 4 * t)
    ids = rng.integers(0, int(config["vocab_size"]), (rows, t), dtype=np.int32)
    labels = np.concatenate([ids[:, 1:], np.full((rows, 1), -100, np.int32)], axis=1)
    return {"input_ids": ids, "labels": labels}
