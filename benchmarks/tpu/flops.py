"""Operations and bytes of ELSA's device work, from shapes alone.

Counts are per sequence of ``s`` tokens and follow the configuration
file, never the program's code. Only matrix products are counted, at 2
operations per multiply-add; normalisation, softmax and other
elementwise work are left out, so every count is a lower bound on what
the device computes.

A local step is the split forward (every block, the head, and two
channel crossings) and the backward the step needs:

- activation gradients through every block down to the lowest adapted
  layer (block 0), where only what the adapters need is counted;
- the adapters' own weight gradients;
- no frozen-weight gradient, since frozen weights get none.

The channel is the SS-OP rotation ``h + ((h U) W) U^T`` before and after
the count sketch. The sketch is counted as the hash scatter it stands
for, ``s * d * Y`` additions each way, not as the dense selection
product the program runs it as. Causal attention counts the lower
triangle only.
"""
from __future__ import annotations


def _dims(cfg):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kv = cfg.get("num_key_value_heads", h)
    return d, h, kv, d // h, cfg["intermediate_size"]


def _causal(cfg) -> bool:
    return cfg["family"] == "decoder"


def _pairs(cfg, s: int) -> int:
    return s * (s + 1) // 2 if _causal(cfg) else s * s


def _lora_io(cfg):
    """(input width, output width) of each adapted projection."""
    d, h, kv, e, _ = _dims(cfg)
    io = {"q": (d, h * e), "k": (d, kv * e), "v": (d, kv * e),
          "o": (h * e, d)}
    return [io[t] for t in cfg["lora"]["targets"]]


def lora_forward(cfg, s: int) -> int:
    r = cfg["lora"]["rank"]
    return sum(2 * s * (i * r + r * o) for i, o in _lora_io(cfg))


def mlp_forward(cfg, s: int) -> int:
    d, _, _, _, f = _dims(cfg)
    mats = 2 if cfg["family"] == "encoder" else 3
    return mats * 2 * s * d * f


def attention_core(cfg, s: int) -> int:
    """Scores plus probability-times-values, all heads."""
    d, h, _, e, _ = _dims(cfg)
    return 2 * (2 * _pairs(cfg, s) * e * h)


def block_forward(cfg, s: int) -> int:
    d, h, kv, e, _ = _dims(cfg)
    qkv = 2 * s * d * e * (h + 2 * kv)
    out = 2 * s * h * e * d
    return qkv + out + attention_core(cfg, s) + mlp_forward(cfg, s) \
        + lora_forward(cfg, s)


def block_backward(cfg, s: int, lowest: bool = False) -> int:
    """Activation gradients (each frozen product once more) and the
    adapters' weight gradients (their products twice more). The lowest
    block skips the input gradient of its q/k/v projections and the
    attention gradients no adapter needs."""
    d, h, kv, e, _ = _dims(cfg)
    targets = set(cfg["lora"]["targets"])
    out = 2 * s * h * e * d
    one = 2 * _pairs(cfg, s) * e * h        # one attention product
    if lowest:
        attn = one * (("q" in targets or "k" in targets)
                      + ("v" in targets) + ("q" in targets)
                      + ("k" in targets))
        qkv = 0
    else:
        attn = 4 * one
        qkv = 2 * s * d * e * (h + 2 * kv)
    return qkv + out + attn + mlp_forward(cfg, s) + 2 * lora_forward(cfg, s)


def channel_forward(cfg, fed, s: int) -> int:
    """One crossing: rotation, sketch scatter, inverse rotation."""
    d = cfg["hidden_size"]
    r = fed["ssop_r"] if fed.get("use_ssop", True) else 0
    rot = 2 * s * (2 * d * r + r * r)
    return 2 * rot + s * d * fed["sketch_y"]


def head_forward(cfg, fed, s: int) -> int:
    d = cfg["hidden_size"]
    if cfg["family"] == "encoder":
        return 2 * d * d + 2 * d * fed["num_classes"]
    return 2 * s * d * cfg["vocab_size"]


def head_backward(cfg, fed, s: int) -> int:
    """Encoder: pooler and classifier are trained (input and weight
    gradients). Decoder: the tied head is frozen (input gradient only)."""
    mult = 2 if cfg["family"] == "encoder" else 1
    return mult * head_forward(cfg, fed, s)


def train_sequence(cfg, fed, s: int) -> int:
    """One sequence through one local step: split forward with the
    channel, and the backward down to block 0."""
    layers = cfg["num_hidden_layers"]
    crossings = 2 if fed.get("use_channel", True) else 0
    fwd = layers * block_forward(cfg, s) + head_forward(cfg, fed, s) \
        + crossings * channel_forward(cfg, fed, s)
    bwd = (layers - 1) * block_backward(cfg, s) \
        + block_backward(cfg, s, lowest=True) \
        + head_backward(cfg, fed, s) \
        + crossings * channel_forward(cfg, fed, s)
    return fwd + bwd


def forward_sequence(cfg, fed, s: int, logits: bool = True) -> int:
    """One sequence through the unsplit forward (eval; probe without
    the logits)."""
    out = cfg["num_hidden_layers"] * block_forward(cfg, s)
    return out + (head_forward(cfg, fed, s) if logits else 0)


def frozen_bytes(cfg, itemsize: int = 4) -> int:
    """Bytes of the frozen weights a local step has to read at least
    once: every block, and the tied output head of a decoder."""
    d, h, kv, e, f = _dims(cfg)
    mats = 2 if cfg["family"] == "encoder" else 3
    per_block = d * e * (h + 2 * kv) + h * e * d + mats * d * f
    total = cfg["num_hidden_layers"] * per_block
    if cfg["family"] == "decoder":
        total += cfg["vocab_size"] * d
    return total * itemsize


def dispatch_least_seconds(cfg, fed, steps: int, clients: int, batch: int,
                           s: int, peak_flops: float, peak_bw: float):
    """Least time of one local-round dispatch at the chip's peaks:
    ``(seconds, bound)`` with bound ``"compute"`` or ``"memory"``."""
    ops = steps * clients * batch * train_sequence(cfg, fed, s)
    t_ops = ops / peak_flops
    t_mem = steps * frozen_bytes(cfg) / peak_bw
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")


def rows_drawn(n_examples: int, batch: int, draws: int) -> int:
    """Real rows in the first ``draws`` batches of a client's endless
    stream: every epoch is ``ceil(n / batch)`` batches and only its last
    one is short."""
    per_epoch = -(-n_examples // batch)
    full, part = divmod(draws, per_epoch)
    return full * n_examples + part * batch
