"""Operations and bytes of ELSA's device work, from shapes alone.

What every architecture shares is here: the channel, the sum over a
stack of layers of any kind, and the least time of a dispatch. What
belongs to one architecture is in ``counts/<family>.py``, found by the
configuration file's ``family`` key. Such a module gives
``block_forward(cfg, s, i)`` and ``block_backward(cfg, s, i, lowest)``
for layer ``i``, ``head_forward(cfg, fed, s)``, ``head_backward(cfg,
fed, s)`` and ``frozen_bytes(cfg, itemsize)``.

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

import importlib.util
from pathlib import Path

COUNTS = Path(__file__).resolve().parent / "counts"

_LOADED = {}


def load_counts(name: str, directory: Path = COUNTS):
    """The module ``<directory>/<name>.py``, loaded once by path."""
    path = Path(directory) / f"{name}.py"
    if path not in _LOADED:
        if not path.exists():
            raise FileNotFoundError(f"no count module {path}")
        spec = importlib.util.spec_from_file_location(
            "counts_" + name.replace(".", "_").replace("-", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _LOADED[path] = mod
    return _LOADED[path]


def family(cfg):
    """The count module of the configuration's ``family``."""
    return load_counts(cfg["family"])


def channel_forward(cfg, fed, s: int) -> int:
    """One crossing: rotation, sketch scatter, inverse rotation."""
    d = cfg["hidden_size"]
    r = fed["ssop_r"] if fed.get("use_ssop", True) else 0
    rot = 2 * s * (2 * d * r + r * r)
    return 2 * rot + s * d * fed["sketch_y"]


def train_sequence(cfg, fed, s: int) -> int:
    """One sequence through one local step: split forward with the
    channel, and the backward down to the lowest adapted layer, layer
    0."""
    m = family(cfg)
    layers = range(cfg["num_hidden_layers"])
    crossings = 2 if fed.get("use_channel", True) else 0
    fwd = sum(m.block_forward(cfg, s, i) for i in layers) \
        + m.head_forward(cfg, fed, s) \
        + crossings * channel_forward(cfg, fed, s)
    bwd = sum(m.block_backward(cfg, s, i, lowest=(i == 0)) for i in layers) \
        + m.head_backward(cfg, fed, s) \
        + crossings * channel_forward(cfg, fed, s)
    return fwd + bwd


def forward_sequence(cfg, fed, s: int, logits: bool = True) -> int:
    """One sequence through the unsplit forward (eval; probe without
    the logits)."""
    m = family(cfg)
    out = sum(m.block_forward(cfg, s, i)
              for i in range(cfg["num_hidden_layers"]))
    return out + (m.head_forward(cfg, fed, s) if logits else 0)


def dispatch_least_seconds(cfg, fed, steps: int, clients: int, batch: int,
                           s: int, peak_flops: float, peak_bw: float):
    """Least time of one local-round dispatch at the chip's peaks:
    ``(seconds, bound)`` with bound ``"compute"`` or ``"memory"``."""
    ops = steps * clients * batch * train_sequence(cfg, fed, s)
    t_ops = ops / peak_flops
    t_mem = steps * family(cfg).frozen_bytes(cfg) / peak_bw
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")


def rows_drawn(n_examples: int, batch: int, draws: int) -> int:
    """Real rows in the first ``draws`` batches of a client's endless
    stream: every epoch is ``ceil(n / batch)`` batches and only its last
    one is short."""
    per_epoch = -(-n_examples // batch)
    full, part = divmod(draws, per_epoch)
    return full * n_examples + part * batch
