"""The one traffic generator: a fine-tune job's rows, made with numpy from the
seed and a traffic file's parameters. Every seed gives the same set of sizes
(row count, lengths' distribution) with other contents and another order."""

from __future__ import annotations

import numpy as np


def n_rows(traffic: dict, row_bytes: int) -> int:
    """Rows that fit `host_bytes_max`, at most `rows_max`, in whole dispatches
    (`batch * scan_chunk` rows) so that every epoch ends on a chunk boundary."""
    unit = int(traffic["batch"]) * int(traffic["scan_chunk"])
    rows = min(int(traffic["rows_max"]), int(traffic["host_bytes_max"]) // row_bytes)
    rows = (rows // unit) * unit
    if rows < unit:
        raise ValueError(f"traffic holds fewer than one dispatch of rows ({rows})")
    return rows


def text_lengths(rng: np.random.Generator, rows: int, spec: dict) -> np.ndarray:
    """Lengths in [len_min, seq_len] with a heavy tail towards short rows
    (a Pareto draw folded back from seq_len); one row is seq_len long, so
    that the padded length is seq_len on every seed."""
    lo, hi = int(spec["len_min"]), int(spec["seq_len"])
    draw = rng.pareto(float(spec["len_tail"]), rows)
    lens = np.clip(hi - np.floor(draw * (hi - lo) / 4.0), lo, hi).astype(np.int32)
    lens[0] = hi
    return lens


def make_rows(config: dict, traffic: dict, seed: int) -> dict:
    """Column dict for `MemorySource`, as the estimators build it:
    text: `input_ids`, `attention_mask` (int32, padded to seq_len), `labels`;
    image: `x` float32 [rows, H, W, C], `labels`."""
    rng = np.random.default_rng(int(seed))
    classes = int(config["num_labels"])
    if config["inputs"] == "text":
        spec = traffic["text"]
        t = int(spec["seq_len"])
        rows = n_rows(traffic, row_bytes=2 * 4 * t + 4)
        lens = text_lengths(rng, rows, spec)
        mask = (np.arange(t, dtype=np.int32)[None, :] < lens[:, None])
        ids = rng.integers(1, int(config["vocab_size"]), (rows, t), dtype=np.int32)
        data = {"input_ids": np.where(mask, ids, int(config["pad_token_id"])).astype(np.int32),
                "attention_mask": mask.astype(np.int32)}
    elif config["inputs"] == "image":
        size, c = int(config["image_size"]), int(config["num_channels"])
        rows = n_rows(traffic, row_bytes=4 * size * size * c)
        data = {"x": rng.standard_normal((rows, size, size, c), dtype=np.float32)}
    else:
        raise ValueError(f"unknown inputs kind {config['inputs']!r}")
    data["labels"] = rng.integers(0, classes, rows, dtype=np.int32)
    return data


class RowIndex:
    """Find which row of the generated data a fed row is, by content."""

    def __init__(self, data: dict, key_column: str):
        self.data = data
        self.key_column = key_column
        col = data[key_column]
        self._flat = col.reshape(col.shape[0], -1)
        self._head = min(self._flat.shape[1], 64)
        self._index = {}
        for i in range(col.shape[0]):
            self._index.setdefault(self._flat[i, : self._head].tobytes(), []).append(i)

    def find(self, batch: dict, row: int) -> int | None:
        """Index of the generated row that equals `batch`'s row in every
        column, or None."""
        fed = np.asarray(batch[self.key_column][row]).reshape(-1)
        for i in self._index.get(fed[: self._head].tobytes(), ()):
            if all(np.array_equal(np.asarray(batch[k][row]), v[i])
                   for k, v in self.data.items()):
                return i
        return None
