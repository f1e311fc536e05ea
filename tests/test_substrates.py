"""Data pipeline, checkpointing, comm-model, and HLO-parser substrates."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.hlo_cost import analyze
from repro.checkpoint import restore, save
from repro.data.pipeline import batch_iterator
from repro.data.probe import make_probe_set
from repro.data.synthetic import (SyntheticTaskConfig, dirichlet_partition,
                                  make_federation_data, make_task,
                                  make_test_set, poison_labels,
                                  quantity_skew)


def test_dirichlet_partition_properties():
    props = dirichlet_partition(10, 4, alpha=0.1, seed=0)
    assert props.shape == (10, 4)
    np.testing.assert_allclose(props.sum(1), 1.0, atol=1e-9)
    # low alpha -> skewed: most clients dominated by one class
    assert (props.max(1) > 0.6).mean() > 0.5


def test_quantity_skew_monotone():
    sizes = quantity_skew(8, 1000)
    assert (np.diff(sizes) >= 0).all()
    assert sizes.sum() <= 1100


def test_poisoning_changes_labels():
    rng = np.random.default_rng(0)
    labels = rng.integers(0, 4, 200)
    poisoned = poison_labels(labels, 0.5, 4, rng)
    assert 0.25 < (poisoned != labels).mean() < 0.55


def test_task_is_learnable_classes_distinct():
    cfg = SyntheticTaskConfig(vocab_size=256, num_classes=4, seq_len=16)
    p = make_task(cfg)
    # class distributions concentrate on distinct segments
    for c in range(4):
        seg = slice(c * 64, (c + 1) * 64)
        assert p[c, seg].sum() > 0.5


def test_federation_data_end_to_end():
    cfg = SyntheticTaskConfig(vocab_size=128, num_classes=4, seq_len=12)
    data = make_federation_data(cfg, 6, 600, alpha=0.2,
                                poisoned_clients=(1,))
    assert set(data) == set(range(6))
    assert data[1].poisoned and not data[0].poisoned
    toks, labels = make_test_set(cfg, 64)
    assert toks.shape == (64, 12) and labels.shape == (64,)
    assert toks.max() < 128


def test_batch_iterator_covers_epoch():
    toks = np.arange(50)[:, None].repeat(3, 1)
    labels = np.arange(50) % 2
    seen = []
    for bt, bl in batch_iterator(toks, labels, 16, seed=1):
        seen.extend(bt[:, 0].tolist())
    assert sorted(seen) == list(range(50))


def test_probe_set_shapes():
    cfg = SyntheticTaskConfig(vocab_size=128, num_classes=4, seq_len=12)
    probe = make_probe_set(cfg, 20)
    assert probe.shape == (20, 12) and probe.max() < 128


def test_checkpoint_roundtrip():
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.asarray([1, 2], jnp.int32),
                  "d": [jnp.ones((2,), jnp.bfloat16)]}}
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "ckpt.msgpack")
        save(path, tree)
        back = restore(path)
    np.testing.assert_array_equal(np.asarray(back["a"]),
                                  np.asarray(tree["a"]))
    np.testing.assert_array_equal(np.asarray(back["b"]["c"]),
                                  np.asarray(tree["b"]["c"]))
    assert back["b"]["d"][0].dtype == jnp.bfloat16


def test_hlo_parser_matches_xla_on_unrolled():
    def f(x, w):
        for i in range(4):
            x = jnp.tanh(x @ w[i])
        return x.sum()
    c = jax.jit(f).lower(jnp.zeros((64, 128)),
                         jnp.zeros((4, 128, 128))).compile()
    parsed = analyze(c.as_text())
    xla = c.cost_analysis()["flops"]
    assert abs(parsed.flops - xla) / xla < 0.05


def test_hlo_parser_multiplies_scan_trips():
    def f(x, w):
        def body(c, wi):
            return jnp.tanh(c @ wi), None
        return jax.lax.scan(body, x, w)[0].sum()
    c = jax.jit(f).lower(jnp.zeros((64, 128)),
                         jnp.zeros((10, 128, 128))).compile()
    parsed = analyze(c.as_text())
    one_body = 2 * 64 * 128 * 128
    assert parsed.flops > 9 * one_body   # ~10x the single-body flops
