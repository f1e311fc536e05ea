"""Counts of a dense decoder: every block is causal multi-head attention
and a gated three-matrix MLP (``counts/mha.py``); the output head is
tied to the embedding and frozen."""
from __future__ import annotations

from pathlib import Path

import flops

mha = flops.load_counts("mha", Path(__file__).resolve().parent)

CAUSAL, MLP_MATS = True, 3


def block_forward(cfg, s: int, i: int) -> int:
    return mha.block_forward(cfg, s, CAUSAL, MLP_MATS)


def block_backward(cfg, s: int, i: int, lowest: bool = False) -> int:
    return mha.block_backward(cfg, s, CAUSAL, MLP_MATS, lowest)


def head_forward(cfg, fed, s: int) -> int:
    return 2 * s * cfg["hidden_size"] * cfg["vocab_size"]


def head_backward(cfg, fed, s: int) -> int:
    """The tied head is frozen: its input gradient only."""
    return head_forward(cfg, fed, s)


def frozen_bytes(cfg, itemsize: int = 4) -> int:
    """Every block's weights and the tied output head, read whole."""
    return (cfg["num_hidden_layers"] * mha.block_weights(cfg, MLP_MATS)
            + cfg["vocab_size"] * cfg["hidden_size"]) * itemsize
