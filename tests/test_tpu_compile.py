"""The Pallas kernels compile for a TPU v5e chip at real widths.

Interpret mode (``tests/test_kernels.py``) checks the kernels' numbers
but not what Mosaic accepts: block shapes that do not tile an axis, or
an output block whose last two dims the TPU cannot lay out.  These
tests ask the TPU compiler, for a described (not attached) chip, to
compile each kernel with ``interpret=False`` at BERT-base's width
(D=768) and at the dense decoders' width (D=2048), and check that the
program holds the kernel (``tpu_custom_call``).  Nothing runs.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and test workers
import every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.count_sketch.kernel import (sketch_compress_tz,
                                               sketch_decompress_tz)
from repro.kernels.flash_attention.kernel import flash_attention_bhsd
from repro.kernels.lora.kernel import lora_matmul_td
from repro.kernels.ssop.kernel import ssop_apply_td

T = 2048          # 16 sequences of 128 tokens
SKETCH_Y = 3
SSOP_R = 8
LORA_R = 8


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile_text(fn, sharding, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _sketch_z(d: int) -> int:
    # FedConfig's derived width: Z = D / (rho * Y) at rho = 2.1
    return max(4, int(d / (2.1 * SKETCH_Y)))


@pytest.mark.parametrize("d", [768, 2048])
def test_count_sketch_compress_compiles(one_chip, d):
    text = _compile_text(
        lambda h, s: sketch_compress_tz(h, s, interpret=False), one_chip,
        (T, d), (SKETCH_Y, d, _sketch_z(d)))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("d", [768, 2048])
def test_count_sketch_decompress_compiles(one_chip, d):
    z = _sketch_z(d)
    text = _compile_text(
        lambda u, s: sketch_decompress_tz(u, s, interpret=False), one_chip,
        (T, SKETCH_Y, z), (SKETCH_Y, d, z))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("d", [768, 2048])
def test_ssop_compiles(one_chip, d):
    text = _compile_text(
        lambda h, u, w: ssop_apply_td(h, u, w, interpret=False), one_chip,
        (T, d), (d, SSOP_R), (SSOP_R, SSOP_R))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("d", [768, 2048])
def test_lora_compiles(one_chip, d):
    text = _compile_text(
        lambda x, w, a, b: lora_matmul_td(x, w, a, b, 2.0, interpret=False),
        one_chip, (T, d), (d, d), (d, LORA_R), (LORA_R, d))
    assert "tpu_custom_call" in text


def test_flash_attention_bert_base_heads_compile(one_chip):
    # 16 sequences x 12 heads, S=128, head dim 64 (BERT-base)
    bh = (16 * 12, 128, 64)
    text = _compile_text(
        lambda q, k, v: flash_attention_bhsd(q, k, v, causal=False,
                                             interpret=False),
        one_chip, bh, bh, bh)
    assert "tpu_custom_call" in text
