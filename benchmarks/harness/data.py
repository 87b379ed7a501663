"""The one traffic generator: a training job's batches from a mix file
and a seed, as numpy arrays on the host.

A mix (benchmarks/traffic/<mix>.json) gives `task` ("causal_lm" or
"mlm_nsp"), `batch`, `seq`, `lengths` {"lo", "hi"} as shares of `seq`,
`pool_batches`, and for "mlm_nsp" `max_predictions` and `mask_fraction`.
The loop reads three more, each with a default: `interval_steps` (over
how many steps one interval of `step_ms_p90` is taken: 1), `trace_steps`
(how many steps a traced run records: 10) and `reference_blocks` (in how
many blocks of rows one chip's reference takes a batch: 1).

Every seed sees the same set of sizes in another order: the lengths of a
batch are an even grid over [lo, hi] x seq, permuted per batch, so the
real tokens and the masked predictions of a step do not depend on the
seed and a rate is comparable across seeds. Token ids are drawn from the
published vocabulary (the table may be padded beyond it).
"""
from __future__ import annotations

import numpy as np

PAD_ID = 0
IGNORE = -1


def grid_lengths(mix: dict) -> np.ndarray:
    lo, hi = mix["lengths"]["lo"], mix["lengths"]["hi"]
    grid = np.linspace(lo, hi, mix["batch"]) * mix["seq"]
    return np.clip(np.round(grid).astype(np.int64), 1, mix["seq"])


def predictions_of(mix: dict, lengths: np.ndarray) -> np.ndarray:
    return np.clip((lengths * mix["mask_fraction"]).astype(np.int64), 1,
                   mix["max_predictions"])


def batch_stats(mix: dict) -> dict:
    """What one step holds, the same for every batch of the mix."""
    lengths = grid_lengths(mix)
    out = {"lengths": lengths, "tokens": int(lengths.sum()),
           "rows": mix["batch"] * mix["seq"]}
    if mix["task"] == "mlm_nsp":
        out["predictions"] = int(predictions_of(mix, lengths).sum())
    return out


def make_pool(mix: dict, vocab: int, seed: int) -> list:
    """`pool_batches` distinct batches; every row of every batch differs."""
    rng = np.random.default_rng([int(seed), 0x7261])
    b, s = mix["batch"], mix["seq"]
    grid = grid_lengths(mix)
    pool = []
    for _ in range(mix["pool_batches"]):
        lengths = rng.permutation(grid)
        valid = np.arange(s)[None, :] < lengths[:, None]
        ids = rng.integers(1, vocab, (b, s), dtype=np.int32)
        ids = np.where(valid, ids, PAD_ID).astype(np.int32)
        if mix["task"] == "causal_lm":
            nxt = rng.integers(1, vocab, (b, 1), dtype=np.int32)
            labels = np.concatenate([ids[:, 1:], nxt], axis=1)
            labels = np.where(valid, labels, IGNORE).astype(np.int32)
            pool.append({"ids": ids, "labels": labels})
            continue
        if mix["task"] != "mlm_nsp":
            raise ValueError(f"unknown task {mix['task']!r}")
        n_pred = predictions_of(mix, lengths)
        p_max = mix["max_predictions"]
        positions = np.zeros((b, p_max), np.int32)
        mlm_labels = np.full((b, p_max), IGNORE, np.int32)
        for r in range(b):
            pos = rng.choice(lengths[r], size=n_pred[r], replace=False)
            positions[r, :n_pred[r]] = np.sort(pos)
            mlm_labels[r, :n_pred[r]] = rng.integers(1, vocab, n_pred[r])
        # two segments: sentence B starts somewhere inside the real part
        split = (lengths * rng.uniform(0.3, 0.7, b)).astype(np.int64)
        types = ((np.arange(s)[None, :] >= split[:, None]) & valid)
        pool.append({"ids": ids, "types": types.astype(np.int32),
                     "valid": valid, "positions": positions,
                     "mlm_labels": mlm_labels,
                     "nsp": rng.integers(0, 2, (b,), dtype=np.int32)})
    return pool
