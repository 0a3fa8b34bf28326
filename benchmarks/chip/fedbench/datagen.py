"""The benchmark's own copy of the synthetic Reddit-shaped client data.

A straight copy of the arithmetic of ``repro.data.synthetic`` (sample
counts, token draws, pseudo-word spellings, batching, the eval batch), so
that the yardstick neither imports the program nor trusts what it reports:
token counts and the reference's inputs come from here. A change to the
program's generator therefore shows as a failed comparison, not as a
silently different benchmark.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

MEAN_SAMPLES = 34.0
PARETO_SHAPE = 1.8


def num_samples(client_id: int, seed: int) -> int:
    """Rows of text that one client holds (Pareto tail, mean about 34)."""
    rng = np.random.default_rng(np.uint64(seed * 1_000_003 + client_id))
    scale = MEAN_SAMPLES * (PARETO_SHAPE - 1)
    n = int(rng.pareto(PARETO_SHAPE) * scale + 1)
    return max(2, min(n, 4096))


def batch_rows(n: int, batch: int, epochs: int, max_steps: int) -> List[int]:
    """Real (unpadded) rows of each local step a client trains."""
    per_epoch = [min(batch, n - i) for i in range(0, n, batch)]
    return (per_epoch * epochs)[:max_steps]


def real_rows(client_id: int, seed: int, batch: int, epochs: int,
              max_steps: int) -> int:
    return sum(batch_rows(num_samples(client_id, seed), batch, epochs,
                          max_steps))


class ClientData:
    """Client and eval batches, keyed by (seed, client id) alone."""

    def __init__(self, vocab_size: int, seq_len: int, seed: int,
                 char_vocab: int = 0, max_word_len: int = 16):
        self.vocab_size = vocab_size
        self.seq_len = seq_len
        self.seed = seed
        self.char_vocab = char_vocab
        self.max_word_len = max_word_len
        ranks = np.arange(1, min(vocab_size, 4096) + 1, dtype=np.float64)
        p = 1.0 / ranks ** 1.1
        self._probs = p / p.sum()

    def tokens(self, client_id: int, n: int) -> np.ndarray:
        rng = np.random.default_rng(
            np.uint64(self.seed * 7_777_777 + client_id * 13 + 1))
        V = self.vocab_size
        n_zipf = min(V, 4096)
        d_start = int(rng.integers(0, max(1, V - 512)))
        shift = int(rng.integers(0, V))
        total = n * self.seq_len
        base = rng.choice(n_zipf, size=total, p=self._probs)
        prev = np.roll(base, 1)
        bigram = rng.random(total) < 0.3
        base = np.where(bigram, (prev + shift) % n_zipf, base)
        use_dialect = rng.random(total) < 0.35
        dialect = d_start + (base % 512)
        toks = np.where(use_dialect, dialect, base).astype(np.int32) % V
        return toks.reshape(n, self.seq_len)

    def chars(self, word_ids: np.ndarray) -> np.ndarray:
        flat = word_ids.reshape(-1).astype(np.int64)
        W = self.max_word_len
        lens = np.clip(2 + (np.log1p(flat) * 1.7).astype(np.int64), 2, W)
        out = np.zeros((flat.size, W), dtype=np.int32)
        state = flat * 2654435761 % (2 ** 31)
        nchars = min(self.char_vocab - 1, 26)
        for i in range(W):
            state = (state * 1103515245 + 12345) % (2 ** 31)
            out[:, i] = 1 + (state % nchars)
        out = np.where(np.arange(W)[None, :] < lens[:, None], out, 0)
        return out.reshape(word_ids.shape + (W,)).astype(np.int32)

    def _batch(self, toks: np.ndarray, mask: np.ndarray) -> Dict:
        b = {"tokens": toks, "labels": toks, "mask": mask}
        if self.char_vocab:
            b["chars"] = self.chars(toks)
        return b

    def client_batches(self, client_id: int, batch: int, epochs: int,
                       max_steps: int) -> List[Dict[str, np.ndarray]]:
        """The batches of the local steps the client really trains (the
        program pads to ``max_steps`` with masked steps, which change
        nothing)."""
        toks = self.tokens(client_id, num_samples(client_id, self.seed))
        out = []
        for rows in batch_rows(len(toks), batch, epochs, max_steps):
            i = (len(out) % -(-len(toks) // batch)) * batch
            chunk = toks[i:i + rows]
            mask = np.zeros((batch, self.seq_len - 1), np.float32)
            mask[:rows] = 1.0
            if rows < batch:
                chunk = np.concatenate(
                    [chunk, np.zeros((batch - rows, self.seq_len),
                                     np.int32)])
            out.append(self._batch(chunk, mask))
        return out

    def eval_batch(self, n_clients: int, batch: int,
                   offset: int = 10_000_000) -> Dict[str, np.ndarray]:
        rows = [self.tokens(offset + c, max(1, batch // n_clients))
                for c in range(n_clients)]
        toks = np.concatenate(rows, axis=0)[:batch]
        if toks.shape[0] < batch:
            toks = np.tile(toks, (-(-batch // toks.shape[0]), 1))[:batch]
        return self._batch(toks, np.ones((batch, self.seq_len - 1),
                                         np.float32))
