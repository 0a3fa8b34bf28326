"""CPU rehearsal of a cell at a tiny size, for the benchmark's own tests.

The command (``run.py``) never reaches this: it refuses any device that is
not a TPU. Here the same ``harness.drive`` runs on the CPU with the
cell's configuration swapped for a same-family model a few hundred
parameters wide, so every path of a run (replay, warm-up, window, spans,
reference, check, result line) is exercised in seconds.
"""
from __future__ import annotations

import time
from pathlib import Path
from typing import Dict, Optional

from fedbench import harness

TINY = {
    "charlm": dict(name="tiny-charlm", family="charlm", num_layers=2,
                   d_model=32, num_heads=0, num_kv_heads=0, d_ff=32,
                   vocab_size=256, char_vocab=32, char_emb=8,
                   cnn_filters=[[1, 8], [2, 8]], lstm_hidden=32,
                   max_word_len=8, max_context=64),
    "dense": dict(name="tiny-llama", family="dense", num_layers=2,
                  d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                  vocab_size=512, max_context=2048, tie_embeddings=True,
                  rope_theta=10000.0),
}
CPU_PEAKS = {"cpu": {"bf16_flops_per_s": 1e12}}


def tiny_cell(name: str, root: Path = harness.ROOT,
              bench_dir: Path = harness.BENCH_DIR,
              traffic: Optional[Dict] = None) -> harness.Cell:
    cell = harness.load_cell(name, root, bench_dir)
    tiny = TINY[cell.config["model"]["family"]]
    cell.config = dict(cell.config, model=tiny, model_ref={"config": tiny})
    if traffic:
        cell.traffic = dict(cell.traffic, **traffic)
    return cell


def rehearse(cell: harness.Cell, seed: int = 3_000_000_123,
             seconds: float = 1.0, trace: bool = False,
             bench_dir: Path = harness.BENCH_DIR) -> Dict:
    return harness.drive(cell, seed, seconds, trace,
                         t_start=time.perf_counter(), platform="cpu",
                         peaks=CPU_PEAKS, bench_dir=bench_dir,
                         log=lambda s: None)
