"""Counts of the ``toy-moe`` test configuration: causal attention in
every layer; a gated MLP of ``intermediate_size`` in the first
``first_k_dense_replace`` layers, and in the others a router and
``num_experts_per_tok`` gated experts of ``moe_intermediate_size`` per
token. The head is tied and frozen. Attention's counts are
``counts/mha.py``'s with no MLP; every layer's backward is counted
whole."""
from __future__ import annotations

from pathlib import Path

import flops

mha = flops.load_counts("mha", Path(__file__).resolve().parent)


def _mlp(cfg, s, i):
    d = cfg["hidden_size"]
    if i < cfg["first_k_dense_replace"]:
        return 3 * 2 * s * d * cfg["intermediate_size"]
    return 2 * s * d * cfg["n_routed_experts"] \
        + cfg["num_experts_per_tok"] * 3 * 2 * s * d \
        * cfg["moe_intermediate_size"]


def block_forward(cfg, s: int, i: int) -> int:
    return mha.block_forward(cfg, s, True, 0) + _mlp(cfg, s, i)


def block_backward(cfg, s: int, i: int, lowest: bool = False) -> int:
    return mha.block_backward(cfg, s, True, 0) + _mlp(cfg, s, i)


def head_forward(cfg, fed, s: int) -> int:
    return 2 * s * cfg["hidden_size"] * cfg["vocab_size"]


def head_backward(cfg, fed, s: int) -> int:
    return head_forward(cfg, fed, s)


def frozen_bytes(cfg, itemsize: int = 4) -> int:
    d, n = cfg["hidden_size"], cfg["num_hidden_layers"]
    dense = cfg["first_k_dense_replace"]
    experts = cfg["n_routed_experts"] * 3 * d * cfg["moe_intermediate_size"]
    return (n * mha.block_weights(cfg, 0)
            + dense * 3 * d * cfg["intermediate_size"]
            + (n - dense) * (d * cfg["n_routed_experts"] + experts)
            + cfg["vocab_size"] * d) * itemsize
