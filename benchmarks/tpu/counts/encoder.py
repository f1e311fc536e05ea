"""Counts of a BERT-style encoder: every block is bidirectional
multi-head attention and a two-matrix MLP (``counts/mha.py``); the
readout is a pooler and a classifier on one position, both trained."""
from __future__ import annotations

from pathlib import Path

import flops

mha = flops.load_counts("mha", Path(__file__).resolve().parent)

CAUSAL, MLP_MATS = False, 2


def block_forward(cfg, s: int, i: int) -> int:
    return mha.block_forward(cfg, s, CAUSAL, MLP_MATS)


def block_backward(cfg, s: int, i: int, lowest: bool = False) -> int:
    return mha.block_backward(cfg, s, CAUSAL, MLP_MATS, lowest)


def head_forward(cfg, fed, s: int) -> int:
    d = cfg["hidden_size"]
    return 2 * d * d + 2 * d * fed["num_classes"]


def head_backward(cfg, fed, s: int) -> int:
    """Pooler and classifier are trained: input and weight gradients."""
    return 2 * head_forward(cfg, fed, s)


def frozen_bytes(cfg, itemsize: int = 4) -> int:
    """Every block's weights (the embeddings are gathered, not read
    whole)."""
    return cfg["num_hidden_layers"] * mha.block_weights(cfg, MLP_MATS) \
        * itemsize
