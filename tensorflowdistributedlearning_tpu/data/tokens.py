"""A token stream from a seed: documents of uneven length packed into fixed
sequences, for the decoder family (``backbone="decoder"``).

Every sequence is packed on its own: documents arrive with log-normal lengths
(clipped), are laid end to end in arrival order, and the one that crosses the
sequence's end is cut there, so every position holds a token. With the tokens
go what a packed sequence needs downstream: ``segment_ids`` (the document's
index inside its sequence: attention stays inside one document),
``positions`` (restarting at each document) and ``targets`` (the next token
of the same document, -1 at a document's last position: the loss never
crosses a boundary). Token ids are Zipf-distributed over the vocabulary, id 0
the commonest.

Batch ``i`` is a pure function of ``(seed, i)``, like the synthetic image
stream (data/synthetic.py): a run resumed at step k sees the batches the
uninterrupted run saw from step k.
"""

from __future__ import annotations

import functools
from typing import Dict, Iterator, Optional

import numpy as np

from tensorflowdistributedlearning_tpu.config import TokenStreamConfig

NO_TARGET = -1


@functools.lru_cache(maxsize=8)
def _zipf_cdf(vocab_size: int, exponent: float) -> np.ndarray:
    p = np.arange(1, vocab_size + 1, dtype=np.float64) ** -exponent
    return np.cumsum(p / p.sum())


def pack_sequence(rng: np.random.Generator, seq_len: int, vocab_size: int,
                  stream: TokenStreamConfig) -> Dict[str, np.ndarray]:
    """One packed sequence: tokens, segment_ids, positions, targets, each [seq_len]."""
    lengths = []
    while sum(lengths) < seq_len:
        drawn = rng.lognormal(np.log(stream.median_length), stream.sigma, size=8)
        lengths.extend(np.clip(np.rint(drawn), stream.min_length, stream.max_length).astype(int))
    ends = np.cumsum(lengths)
    n_docs = int(np.searchsorted(ends, seq_len)) + 1  # the last one is cut
    starts = np.concatenate([[0], ends[: n_docs - 1]])
    idx = np.arange(seq_len)
    segment = np.searchsorted(ends[:n_docs], idx, side="right").astype(np.int32)
    positions = (idx - starts[segment]).astype(np.int32)
    cdf = _zipf_cdf(vocab_size, stream.zipf_exponent)
    tokens = np.minimum(np.searchsorted(cdf, rng.random(seq_len)), vocab_size - 1).astype(np.int32)
    targets = np.full(seq_len, NO_TARGET, np.int32)
    same = segment[1:] == segment[:-1]
    targets[:-1][same] = tokens[1:][same]
    return {"tokens": tokens, "segment_ids": segment, "positions": positions, "targets": targets}


def packed_token_batches(
    batch_size: int,
    seq_len: int,
    vocab_size: int,
    stream: TokenStreamConfig,
    *,
    seed: int,
    steps: Optional[int] = None,
    start_index: int = 0,
) -> Iterator[Dict[str, np.ndarray]]:
    """Batches of ``batch_size`` packed sequences, each array [batch, seq_len]
    int32; batch ``i`` is keyed by ``(seed, i)``."""
    i = start_index
    while steps is None or i < start_index + steps:
        rng = np.random.default_rng((seed, i))
        rows = [pack_sequence(rng, seq_len, vocab_size, stream) for _ in range(batch_size)]
        yield {k: np.stack([r[k] for r in rows]) for k in rows[0]}
        i += 1
