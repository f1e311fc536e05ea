"""``flops.py`` and the count modules against hand counts at published
widths, S = 128, and every count pinned to its integer."""
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import flops  # noqa: E402

S = 128
FED = json.loads((HERE / "traffic" / "steps16.json").read_text())[
    "federation"]


def cfg(name):
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def per_layer(c, count, **kw):
    """The count of every layer of ``c``, which has to be one number
    for a uniform stack."""
    m = flops.family(c)
    values = {getattr(m, count)(c, S, i, **kw)
              for i in range(c["num_hidden_layers"])}
    assert len(values) == 1, values
    return values.pop()


def test_bert_block_forward():
    d, f, h, e, r = 768, 3072, 12, 64, 8
    qkv = 3 * 2 * S * d * d                     # 452,984,832
    out = 2 * S * d * d                         # 150,994,944
    attn = 2 * (2 * S * S * e * h)              # full S x S, encoder
    mlp = 2 * (2 * S * d * f)                   # w_in, w_out
    lora = 2 * (2 * S * (d * r + r * d))        # q and v adapters
    assert qkv + out + attn + mlp + lora == 1_868_562_432
    assert per_layer(cfg("bert-base"), "block_forward") == 1_868_562_432


def test_olmo_block_forward():
    d, f, h, e, r = 2048, 8192, 16, 128, 16
    qkv = 3 * 2 * S * d * d
    out = 2 * S * d * d
    pairs = S * (S + 1) // 2                    # causal lower triangle
    attn = 2 * (2 * pairs * e * h)
    mlp = 3 * (2 * S * d * f)                   # gate, up, down
    lora = 4 * (2 * S * (d * r + r * d))        # q, k, v, o adapters
    assert qkv + out + attn + mlp + lora == 17_314_611_200
    assert per_layer(cfg("olmo-1b-v8"), "block_forward") \
        == 17_314_611_200


def test_bert_block_backward():
    c = cfg("bert-base")
    d, f, h, e = 768, 3072, 12, 64
    one = 2 * S * S * e * h
    lora2 = 2 * flops.load_counts("mha").lora_forward(c, S)
    upper = 3 * 2 * S * d * d + 2 * S * d * d + 4 * one \
        + 2 * 2 * S * d * f + lora2
    assert per_layer(c, "block_backward") == upper
    # block 0: no input gradient of q/k/v, no dK (k is not adapted)
    lowest = 2 * S * d * d + 3 * one + 2 * 2 * S * d * f + lora2
    assert per_layer(c, "block_backward", lowest=True) == lowest


def test_channel_and_heads():
    c = cfg("bert-base")
    d, r, y = 768, 8, 3
    assert flops.channel_forward(c, FED, S) == \
        2 * (2 * S * (2 * d * r + r * r)) + S * d * y
    assert flops.family(c).head_forward(c, FED, S) == 2 * d * d + 2 * d * 4
    o = cfg("olmo-1b-v8")
    dec = flops.family(o)
    assert dec.head_forward(o, FED, S) == 2 * S * 2048 * 6288
    assert dec.head_backward(o, FED, S) == dec.head_forward(o, FED, S)


def test_frozen_bytes():
    per_block = 768 * 64 * 36 + 768 * 768 + 2 * 768 * 3072
    c = cfg("bert-base")
    assert flops.family(c).frozen_bytes(c) == 12 * per_block * 4
    olmo = 16 * (4 * 2048 * 2048 + 3 * 2048 * 8192) + 6288 * 2048
    o = cfg("olmo-1b-v8")
    assert flops.family(o).frozen_bytes(o) == olmo * 4


def test_least_time_bound():
    c = cfg("bert-base")
    t, bound = flops.dispatch_least_seconds(c, FED, 16, 8, 16, S,
                                            1.97e14, 8.19e11)
    assert bound == "compute"
    assert t == 16 * 8 * 16 * flops.train_sequence(c, FED, S) / 1.97e14


def test_rows_drawn():
    # 37 examples, batch 16: epochs of 16, 16, 5
    assert flops.rows_drawn(37, 16, 2) == 32
    assert flops.rows_drawn(37, 16, 3) == 37
    assert flops.rows_drawn(37, 16, 5) == 37 + 32
    assert flops.rows_drawn(32, 16, 3) == 48


# Every count the metrics read, as the single-file flops.py of the
# first benchmark gave it, so that ``mfu`` and ``round_fn_roofline``
# read the same from the same timings.
PINNED = {
    "bert-base": {
        "block_forward": 1_868_562_432, "block_backward": 1_925_185_536,
        "block_backward_lowest": 1_447_034_880, "head_forward": 1_185_792,
        "head_backward": 2_371_584, "channel_forward": 6_619_136,
        "train_sequence": 45_076_858_880,
        "forward_sequence": 22_423_934_976,
        "forward_sequence_no_logits": 22_422_749_184,
        "frozen_bytes": 339_738_624},
    "olmo-1b-v8": {
        "block_forward": 17_314_611_200, "block_backward": 17_449_353_216,
        "block_backward_lowest": 14_228_127_744,
        "head_forward": 3_296_722_944, "head_backward": 3_296_722_944,
        "channel_forward": 17_596_416, "train_sequence": 559_666_036_736,
        "forward_sequence": 280_330_502_144,
        "forward_sequence_no_logits": 277_033_779_200,
        "frozen_bytes": 4_346_478_592},
}

COUNT = {
    "block_forward": lambda c: per_layer(c, "block_forward"),
    "block_backward": lambda c: per_layer(c, "block_backward"),
    "block_backward_lowest": lambda c: per_layer(c, "block_backward",
                                                 lowest=True),
    "head_forward": lambda c: flops.family(c).head_forward(c, FED, S),
    "head_backward": lambda c: flops.family(c).head_backward(c, FED, S),
    "channel_forward": lambda c: flops.channel_forward(c, FED, S),
    "train_sequence": lambda c: flops.train_sequence(c, FED, S),
    "forward_sequence": lambda c: flops.forward_sequence(c, FED, S),
    "forward_sequence_no_logits": lambda c: flops.forward_sequence(
        c, FED, S, logits=False),
    "frozen_bytes": lambda c: flops.family(c).frozen_bytes(c),
}


@pytest.mark.parametrize("name,count", [(n, k) for n in sorted(PINNED)
                                        for k in sorted(PINNED[n])])
def test_counts_are_pinned(name, count):
    value = COUNT[count](cfg(name))
    assert isinstance(value, int)
    assert value == PINNED[name][count]
