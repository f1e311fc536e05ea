"""Batched-engine parity vs the sequential reference backend.

Why float64 + small lr: the split-BERT gradient map is chaotic — a 1e-6
relative parameter perturbation changes the eager gradient by ~1e-1
(measured parameter-Lipschitz ~1e5 on the q_b LoRA leaf), and the
count-sketch median's subgradient is discontinuous.  Any fp-level
discrepancy between two compilation strategies (eager per-client loop vs
vmap/scan jit) therefore amplifies by roughly ``lr * 1e5`` per local
step.  Running parity in x64 with a small lr keeps backend discrepancies
at the 1e-12 level where trajectories stay glued for the whole run —
which is exactly what we want to verify: that the batched engine
computes the *same math* as the reference, the one thing a vmap/scan
rewrite can silently get wrong.  At the training lr we additionally
check single-step gradient parity (before chaos can amplify).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sketch import (SketchPlan, channel, compress, decompress,
                               make_plan, selection_matrices)

# small-lr / f64 parity configuration; total_examples=300 gives client 0
# a 14-example dataset so every one of its batches is a ragged, padded one
PARITY_KW = dict(n_clients=6, n_edges=2, alpha=0.2, poisoned=(4,),
                 total_examples=300, probe_q=8, local_warmup_steps=2,
                 lr=1e-4, layers=4, t_rounds=1, batch_size=16,
                 dtype="float64", seed=0)


def _max_tree_diff(a, b):
    return max(float(jnp.abs(x - y).max()) for x, y in
               zip(jax.tree_util.tree_leaves(a),
                   jax.tree_util.tree_leaves(b)))


@pytest.fixture(scope="module")
def x64_feds():
    from repro.federation.simulation import FedConfig, Federation
    with jax.enable_x64(True):
        fb = Federation(FedConfig(**PARITY_KW), backend="batched")
        fr = Federation(FedConfig(**PARITY_KW), backend="reference")
        yield fb, fr


def _assert_run_parity(fb, fr, method, rounds=2, steps=2):
    hb = fb.run(method, global_rounds=rounds, steps_per_round=steps)
    hr = fr.run(method, global_rounds=rounds, steps_per_round=steps)
    assert abs(hb["final_accuracy"] - hr["final_accuracy"]) <= 1e-4
    for n in range(fb.fed.n_clients):
        a = np.asarray(hb["client_losses"][n])
        b = np.asarray(hr["client_losses"][n])
        assert a.shape == b.shape
        if a.size:
            assert np.abs(a - b).max() <= 1e-5, f"client {n}"
    # end-of-run theta: the backends reassociate fp differently and the
    # chaotic map amplifies that over rounds*steps local steps, so how
    # far the trajectories sit apart at the end varies with XLA codegen
    # (~2e-5 under the elsa channel here); the single-step parity test
    # below holds the 1e-8-level same-math line
    assert _max_tree_diff(fb.last_theta, fr.last_theta) <= 1e-4


def test_engine_matches_reference_elsa(x64_feds):
    """Full Alg. 1 (clustered, SS-OP∘sketch channel on): batched == ref."""
    with jax.enable_x64(True):
        _assert_run_parity(*x64_feds, "elsa")


def test_engine_matches_reference_fedprox(x64_feds):
    """FedProx anchor term vectorizes identically (broadcast anchor)."""
    with jax.enable_x64(True):
        _assert_run_parity(*x64_feds, "fedprox")


def test_engine_single_step_parity_at_training_lr(x64_feds):
    """One local step at the real lr: gradient math identical to 1e-8
    (before chaotic trajectory amplification can kick in)."""
    from repro.data.pipeline import infinite_batches
    with jax.enable_x64(True):
        fb, fr = x64_feds
        lr0 = fb.fed.lr
        clients = list(range(fb.fed.n_clients))

        def its(f):
            return {n: infinite_batches(f.data[n].tokens, f.data[n].labels,
                                        f.fed.batch_size, seed=777 + n)
                    for n in clients}

        rb = fb.group_steps(clients, fb.lora0, 1, its(fb))
        rr = fr.group_steps(clients, fr.lora0, 1, its(fr))
        for n in clients:
            lb, sb = rb[n]
            lrr, sr = rr[n]
            assert abs(sb - sr) <= 1e-9
            # updates are lr-scaled; compare the implied gradient
            assert _max_tree_diff(lb, lrr) / lr0 <= 1e-6


def test_make_plan_selection_cache_regression():
    """Precomputing the signed-selection tensor on the plan must not
    change compress/decompress/channel outputs (bit-identical)."""
    plan = make_plan(64, 3, 16, seed=5)
    assert plan.selection is not None
    plain = SketchPlan(plan.bucket, plan.sign, plan.z)     # no cache
    assert plain.selection is None
    h = jax.random.normal(jax.random.PRNGKey(2), (7, 5, 64))
    np.testing.assert_array_equal(np.asarray(compress(h, plan)),
                                  np.asarray(compress(h, plain)))
    u = compress(h, plan)
    np.testing.assert_array_equal(np.asarray(decompress(u, plan)),
                                  np.asarray(decompress(u, plain)))
    np.testing.assert_array_equal(np.asarray(channel(h, plan)),
                                  np.asarray(channel(h, plain)))
    # cached tensor == rebuilt tensor, and scatter path stays bit-equal
    np.testing.assert_array_equal(np.asarray(selection_matrices(plan)),
                                  np.asarray(selection_matrices(plain)))
    np.testing.assert_allclose(
        np.asarray(compress(h, plan, via_matmul=False)),
        np.asarray(compress(h, plan)), atol=1e-6)


def test_weighted_loss_padding_matches_unpadded():
    """Zero-weight padded rows contribute exactly nothing to loss/grad."""
    from repro.configs import get_config
    from repro.core.split_training import (Channel, Split, split_loss,
                                           weighted_split_loss)
    from repro.models import bert as bert_mod
    from repro.models.params import init_tree

    cfg = get_config("bert-base").reduced().with_(num_layers=4)
    tree = init_tree(bert_mod.bert_specs(cfg, 4), jax.random.PRNGKey(0),
                     jnp.float32)
    frozen, lora = tree["frozen"], tree["lora"]
    toks = jax.random.randint(jax.random.PRNGKey(1), (9, 12), 0,
                              cfg.vocab_size)
    labels = jax.random.randint(jax.random.PRNGKey(2), (9,), 0, 4)
    split = Split(1, 1, 2)
    ref = {"tokens": toks, "labels": labels}
    pad_t = jnp.concatenate([toks, jnp.zeros((7, 12), toks.dtype)])
    pad_l = jnp.concatenate([labels, jnp.zeros((7,), labels.dtype)])
    w = jnp.concatenate([jnp.ones(9), jnp.zeros(7)])
    padded = {"tokens": pad_t, "labels": pad_l, "weights": w}

    l_ref, g_ref = jax.value_and_grad(
        lambda lp: split_loss(cfg, frozen, lp, ref, split))(lora)
    l_pad, g_pad = jax.value_and_grad(
        lambda lp: weighted_split_loss(cfg, frozen, lp, padded, split))(lora)
    assert abs(float(l_ref) - float(l_pad)) <= 1e-6
    assert _max_tree_diff(g_ref, g_pad) <= 1e-5
