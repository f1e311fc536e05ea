"""Counts of a block of multi-head attention and a dense MLP, shared by
the ``encoder`` and ``decoder`` count modules.

``causal`` counts the lower triangle of the attention scores only;
``mlp_mats`` is 2 for ``W_out act(W_in x)`` and 3 for a gated MLP. The
rest of the rules are ``flops.py``'s.
"""
from __future__ import annotations


def dims(cfg):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kv = cfg.get("num_key_value_heads", h)
    return d, h, kv, d // h, cfg["intermediate_size"]


def pairs(s: int, causal: bool) -> int:
    return s * (s + 1) // 2 if causal else s * s


def lora_io(cfg):
    """(input width, output width) of each adapted projection."""
    d, h, kv, e, _ = dims(cfg)
    io = {"q": (d, h * e), "k": (d, kv * e), "v": (d, kv * e),
          "o": (h * e, d)}
    return [io[t] for t in cfg["lora"]["targets"]]


def lora_forward(cfg, s: int) -> int:
    r = cfg["lora"]["rank"]
    return sum(2 * s * (i * r + r * o) for i, o in lora_io(cfg))


def mlp_forward(cfg, s: int, mlp_mats: int) -> int:
    d, _, _, _, f = dims(cfg)
    return mlp_mats * 2 * s * d * f


def attention_core(cfg, s: int, causal: bool) -> int:
    """Scores plus probability-times-values, all heads."""
    d, h, _, e, _ = dims(cfg)
    return 2 * (2 * pairs(s, causal) * e * h)


def block_forward(cfg, s: int, causal: bool, mlp_mats: int) -> int:
    d, h, kv, e, _ = dims(cfg)
    qkv = 2 * s * d * e * (h + 2 * kv)
    out = 2 * s * h * e * d
    return qkv + out + attention_core(cfg, s, causal) \
        + mlp_forward(cfg, s, mlp_mats) + lora_forward(cfg, s)


def block_backward(cfg, s: int, causal: bool, mlp_mats: int,
                   lowest: bool = False) -> int:
    """Activation gradients (each frozen product once more) and the
    adapters' weight gradients (their products twice more). The lowest
    block skips the input gradient of its q/k/v projections and the
    attention gradients no adapter needs."""
    d, h, kv, e, _ = dims(cfg)
    targets = set(cfg["lora"]["targets"])
    out = 2 * s * h * e * d
    one = 2 * pairs(s, causal) * e * h      # one attention product
    if lowest:
        attn = one * (("q" in targets or "k" in targets)
                      + ("v" in targets) + ("q" in targets)
                      + ("k" in targets))
        qkv = 0
    else:
        attn = 4 * one
        qkv = 2 * s * d * e * (h + 2 * kv)
    return qkv + out + attn + mlp_forward(cfg, s, mlp_mats) \
        + 2 * lora_forward(cfg, s)


def block_weights(cfg, mlp_mats: int) -> int:
    """Frozen weights of one block: the four projections and the MLP."""
    d, h, kv, e, f = dims(cfg)
    return d * e * (h + 2 * kv) + h * e * d + mlp_mats * d * f
