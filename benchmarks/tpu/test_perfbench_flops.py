"""``flops.py`` against hand counts at published widths, S = 128."""
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import flops  # noqa: E402

S = 128
FED = json.loads((HERE / "traffic" / "steps16.json").read_text())[
    "federation"]


def cfg(name):
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def test_bert_block_forward():
    d, f, h, e, r = 768, 3072, 12, 64, 8
    qkv = 3 * 2 * S * d * d                     # 452,984,832
    out = 2 * S * d * d                         # 150,994,944
    attn = 2 * (2 * S * S * e * h)              # full S x S, encoder
    mlp = 2 * (2 * S * d * f)                   # w_in, w_out
    lora = 2 * (2 * S * (d * r + r * d))        # q and v adapters
    assert qkv + out + attn + mlp + lora == 1_868_562_432
    assert flops.block_forward(cfg("bert-base"), S) == 1_868_562_432


def test_olmo_block_forward():
    d, f, h, e, r = 2048, 8192, 16, 128, 16
    qkv = 3 * 2 * S * d * d
    out = 2 * S * d * d
    pairs = S * (S + 1) // 2                    # causal lower triangle
    attn = 2 * (2 * pairs * e * h)
    mlp = 3 * (2 * S * d * f)                   # gate, up, down
    lora = 4 * (2 * S * (d * r + r * d))        # q, k, v, o adapters
    assert qkv + out + attn + mlp + lora == 17_314_611_200
    assert flops.block_forward(cfg("olmo-1b-v8"), S) == 17_314_611_200


def test_bert_block_backward():
    c = cfg("bert-base")
    d, f, h, e = 768, 3072, 12, 64
    one = 2 * S * S * e * h
    lora2 = 2 * flops.lora_forward(c, S)
    upper = 3 * 2 * S * d * d + 2 * S * d * d + 4 * one \
        + 2 * 2 * S * d * f + lora2
    assert flops.block_backward(c, S) == upper
    # block 0: no input gradient of q/k/v, no dK (k is not adapted)
    lowest = 2 * S * d * d + 3 * one + 2 * 2 * S * d * f + lora2
    assert flops.block_backward(c, S, lowest=True) == lowest


def test_channel_and_heads():
    c = cfg("bert-base")
    d, r, y = 768, 8, 3
    assert flops.channel_forward(c, FED, S) == \
        2 * (2 * S * (2 * d * r + r * r)) + S * d * y
    assert flops.head_forward(c, FED, S) == 2 * d * d + 2 * d * 4
    o = cfg("olmo-1b-v8")
    assert flops.head_forward(o, FED, S) == 2 * S * 2048 * 6288
    assert flops.head_backward(o, FED, S) == flops.head_forward(o, FED, S)


def test_frozen_bytes():
    per_block = 768 * 64 * 36 + 768 * 768 + 2 * 768 * 3072
    assert flops.frozen_bytes(cfg("bert-base")) == 12 * per_block * 4
    olmo = 16 * (4 * 2048 * 2048 + 3 * 2048 * 8192) + 6288 * 2048
    assert flops.frozen_bytes(cfg("olmo-1b-v8")) == olmo * 4


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
