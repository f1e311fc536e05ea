"""SplitModel protocol + registry: BERT bit-parity with the pre-refactor
path, causal-LM end-to-end federation, cohort bucket padding, and the
no-architecture-imports invariant of the refactor.

The BERT parity tests pin the acceptance criterion that routing the
paper's model through the model-agnostic API changes *nothing*:

- op-level: the generic ``split_forward`` emits bit-identical values to
  the pre-refactor BERT-inlined implementation (replicated here);
- run-level: ``Federation(FedConfig(model="bert-base"))`` reproduces the
  history recorded from the pre-refactor code (``tests/golden/
  bert_parity.json``, same seed, plain f32) to float precision.
"""
import json
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import golden_env
from repro.configs import get_config
from repro.core.sketch import make_plan
from repro.core.split_training import (Channel, IDENTITY_CHANNEL, Split,
                                       split_forward)
from repro.core.ssop import make_ssop
from repro.federation.simulation import FedConfig, Federation
from repro.models import bert as bert_mod
from repro.models.params import init_tree
from repro.models.split_api import (BertSplitModel, CausalLMSplitModel,
                                    as_split_model, available_split_models,
                                    get_split_model, split_model_for)

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "bert_parity.json")
GOLDEN_PRODUCT = os.path.join(os.path.dirname(__file__), "golden",
                              "bert_parity_product.json")


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_contents_and_resolution():
    names = available_split_models()
    assert "bert-base" in names and "llama3-8b" in names
    m = get_split_model("bert-base", num_layers=4)
    assert isinstance(m, BertSplitModel) and m.num_blocks == 4
    lm = get_split_model("llama3-8b", num_layers=4, dtype="float32")
    assert isinstance(lm, CausalLMSplitModel)
    assert lm.cfg.param_dtype == "float32" and lm.num_blocks == 4
    with pytest.raises(KeyError):
        get_split_model("not-a-model")
    # ArchConfig adaptation is cached per config
    cfg = get_config("bert-base").reduced()
    assert split_model_for(cfg) is split_model_for(cfg)
    assert as_split_model(split_model_for(cfg)) is split_model_for(cfg)
    # MoE / non-uniform decoders are rejected with a clear error
    with pytest.raises(NotImplementedError):
        split_model_for(get_config("grok-1-314b").reduced())


def test_fedconfig_bert_layers_shim_warns_and_maps_to_layers():
    import dataclasses

    with pytest.warns(DeprecationWarning):
        fc = FedConfig(n_clients=2, bert_layers=3)
    assert fc.layers == 3 and fc.bert_layers == 3
    with warnings.catch_warnings():
        warnings.simplefilter("error")          # no warning on the new name
        fc2 = FedConfig(n_clients=2, layers=5)
        # reconstruction round-trips (bert_layers mirrors layers after
        # resolution) must stay warning-free too
        fc3 = dataclasses.replace(fc, lr=1e-3)
        FedConfig(**dataclasses.asdict(fc))
    assert fc2.layers == 5 and fc3.layers == 3


def test_protocol_cost_facts():
    m = get_split_model("bert-base", num_layers=4)
    assert m.activation_shape(2, 16) == (2, 16, m.cfg.d_model)
    blk, head = m.block_param_count(4), m.head_param_count(4)
    assert blk > 0 and head > 0
    assert m.flops_per_token(num_classes=4) == pytest.approx(
        6.0 * (4 * blk + head))
    # a split bills only the client-side parts
    s = Split(1, 2, 1)
    assert m.flops_per_token(s, num_classes=4) == pytest.approx(
        6.0 * (2 * blk + head))
    lm = get_split_model("llama3-8b", num_layers=4)
    assert lm.task == "causal-lm" and lm.head_param_count() > 0


def test_no_arch_imports_in_core_federation_runtime():
    """Acceptance: core/, federation/, runtime/ never name BERT."""
    import repro
    root = list(repro.__path__)[0]
    for pkg in ("core", "federation", "runtime"):
        for fn in os.listdir(os.path.join(root, pkg)):
            if not fn.endswith(".py"):
                continue
            src = open(os.path.join(root, pkg, fn)).read()
            assert "models.bert" not in src and "import bert" not in src, \
                f"{pkg}/{fn} still imports repro.models.bert"


# ---------------------------------------------------------------------------
# BERT bit-parity with the pre-refactor path
# ---------------------------------------------------------------------------

def _legacy_bert_split_forward(cfg, frozen, lora, tokens, split, channel,
                               mask_valid=None):
    """The pre-refactor BERT-inlined split forward, verbatim."""
    x = bert_mod.embed(cfg, frozen, tokens)
    h_up = bert_mod.run_blocks(cfg, frozen, lora, x, 0, split.p, mask_valid)
    h_up_t = channel(h_up)
    h_down = bert_mod.run_blocks(cfg, frozen, lora, h_up_t,
                                 split.p, split.p + split.q, mask_valid)
    h_down_t = channel(h_down)
    x = bert_mod.run_blocks(cfg, frozen, lora, h_down_t,
                            split.p + split.q, cfg.num_layers, mask_valid)
    cls = x[:, 0, :]
    pooled = jnp.tanh(cls @ lora["pooler"]["w"].astype(cls.dtype)
                      + lora["pooler"]["b"].astype(cls.dtype))
    logits = pooled @ lora["head"]["w"].astype(cls.dtype) \
        + lora["head"]["b"].astype(cls.dtype)
    return cls, logits


def test_bert_split_forward_bitwise_matches_legacy_ops():
    cfg = get_config("bert-base").reduced().with_(num_layers=4)
    model = split_model_for(cfg)
    tree = init_tree(bert_mod.bert_specs(cfg, 4), jax.random.PRNGKey(0),
                     jnp.float32)
    frozen, lora = tree["frozen"], tree["lora"]
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 12), 0,
                              cfg.vocab_size)
    emb = jax.random.normal(jax.random.PRNGKey(3), (16, cfg.d_model))
    plan = make_plan(cfg.d_model, 3, cfg.d_model // 2, seed=2)
    for channel in (IDENTITY_CHANNEL,
                    Channel(make_ssop(emb, 4, "salt", 0), plan)):
        for split in (Split(1, 1, 2), Split(2, 1, 1)):
            cls_l, log_l = _legacy_bert_split_forward(
                cfg, frozen, lora, toks, split, channel)
            cls_n, log_n, _, _ = split_forward(model, frozen, lora, toks,
                                               split, channel)
            np.testing.assert_array_equal(np.asarray(cls_l),
                                          np.asarray(cls_n))
            np.testing.assert_array_equal(np.asarray(log_l),
                                          np.asarray(log_n))


def _assert_matches_golden(path):
    gold = json.load(open(path))
    kw = dict(gold["config"])
    if "bert_layers" in kw:
        kw["layers"] = kw.pop("bert_layers")    # golden predates the rename
    kw["poisoned"] = tuple(kw.get("poisoned", ()))
    fed = Federation(FedConfig(**kw), backend="batched")
    h = fed.run(gold["run"]["method"],
                global_rounds=gold["run"]["global_rounds"],
                steps_per_round=gold["run"]["steps_per_round"])
    # float precision (atol 1e-9 ~ bit-identical for f32) in the one
    # supported installation; re-pin after a toolchain upgrade with
    # tests/golden/regen_bert_parity.py
    msg = (f"golden recorded under {gold.get('env')}, running under "
           f"{golden_env.fingerprint()}")
    tol = dict(rtol=0, atol=1e-9, err_msg=msg)
    np.testing.assert_allclose(h["loss"], gold["loss"], **tol)
    np.testing.assert_allclose(h["accuracy"], gold["accuracy"], **tol)
    np.testing.assert_allclose(h["delta"], gold["delta"], **tol)
    assert h["round"] == gold["round"]
    for n, ref in gold["client_losses"].items():
        np.testing.assert_allclose(h["client_losses"][int(n)], ref, **tol)
    sums = [float(np.asarray(l, np.float64).sum())
            for l in jax.tree_util.tree_leaves(fed.last_theta)]
    np.testing.assert_allclose(sums, gold["theta_leaf_sums"], rtol=0,
                               atol=1e-7, err_msg=msg)


def test_bert_federation_matches_prerefactor_golden():
    """Run-level parity: same seed + f32 + the legacy factor-averaging
    flag reproduces the history recorded from the pre-refactor code
    (atol 1e-9 ≈ bit-identical for f32).  The golden's config carries
    ``aggregate: "factor"`` — it was recorded under factor averaging,
    and that path must stay bit-frozen under the product-space default."""
    _assert_matches_golden(GOLDEN)


def test_bert_federation_matches_product_golden():
    """The product-space aggregation path is pinned by its own golden
    (same config/seed as the legacy one, ``aggregate: "product"``), so
    future refactors of the delta-tree fusion are bit-anchored too."""
    _assert_matches_golden(GOLDEN_PRODUCT)


# ---------------------------------------------------------------------------
# causal LM end to end
# ---------------------------------------------------------------------------

def test_causal_lm_split_equals_full_forward_without_channel():
    model = get_split_model("llama3-8b", num_layers=4)
    tree = init_tree(model.specs(), jax.random.PRNGKey(0), jnp.float32)
    frozen, lora = tree["frozen"], tree["lora"]
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                              model.cfg.vocab_size)
    _, full_logits = model.forward(frozen, lora, toks)
    for split in (Split(1, 1, 2), Split(1, 2, 1), Split(2, 1, 1)):
        _, logits, h_up, h_down = split_forward(model, frozen, lora, toks,
                                                split, IDENTITY_CHANNEL)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(full_logits), atol=1e-5)
        assert h_up.shape == model.activation_shape(2, 12)
        assert h_down.shape == model.activation_shape(2, 12)
    # the LM loss is a finite per-example next-token CE
    batch = {"tokens": toks}
    per = model.per_example_loss(full_logits, batch)
    assert per.shape == (2,) and bool(np.isfinite(per).all())


CAUSAL_KW = dict(n_clients=4, n_edges=2, alpha=0.2, poisoned=(1,),
                 total_examples=400, probe_q=8, local_warmup_steps=2,
                 lr=5e-3, layers=4, t_rounds=1, batch_size=8, seed=0,
                 model="llama3-8b")


@pytest.mark.parametrize("backend", ["batched", "reference"])
def test_causal_lm_fed_round_smoke(backend):
    """Acceptance: a causal LM completes a full Federation.run —
    clustering, dynamic splits, SS-OP∘sketch channel, edge/cloud
    aggregation — on both backends."""
    fed = Federation(FedConfig(**CAUSAL_KW), backend=backend)
    h = fed.run("elsa", global_rounds=1, steps_per_round=2)
    assert np.isfinite(h["loss"]).all()
    assert 0.0 <= h["final_accuracy"] <= 1.0
    assert any(len(v) for v in h["client_losses"].values())
    # poisoned client 1 carries scrambled *tokens* under the LM task
    assert fed.data[1].poisoned


# ---------------------------------------------------------------------------
# cohort bucket padding (deadline recompile-churn fix)
# ---------------------------------------------------------------------------

def test_engine_bucket_ladder():
    from repro.federation.engine import BUCKET_LADDER, bucket_size
    assert all(bucket_size(n) == n for n in range(1, 9))   # small = exact
    for n in (9, 11, 13, 17, 33):
        s = bucket_size(n)
        assert s >= n and s in BUCKET_LADDER
        assert (s - n) / n <= 0.25 + 1e-9                  # bounded waste
    # beyond the ladder's top entry: the next shard-multiple of n itself
    # (the old lcm(16, multiple) stepping over-padded, e.g. 65 -> 80)
    assert bucket_size(65) == 65 and bucket_size(100) == 100
    assert bucket_size(65, 3) == 66 and bucket_size(100, 8) == 104
    assert bucket_size(9, 3) == 12                         # ladder + multiple


def test_engine_padded_cohorts_share_one_compile_and_stay_exact():
    """Cohorts of 9 and 10 clients pad to the same bucket (10): one
    compiled executable serves both, and phantom rows change nothing for
    the real clients (bitwise)."""
    from repro.data.pipeline import infinite_batches

    kw = dict(n_clients=10, n_edges=2, alpha=0.5, poisoned=(),
              total_examples=800, probe_q=8, local_warmup_steps=2,
              lr=5e-3, layers=4, t_rounds=1, batch_size=8, seed=0)

    def run(pad, clients):
        fed = Federation(FedConfig(**kw))
        fed.engine.pad_cohorts = pad
        iters = {n: infinite_batches(fed.data[n].tokens,
                                     fed.data[n].labels, 8, seed=100 + n)
                 for n in range(10)}
        res = fed.group_steps(clients, fed.lora0, 2, iters,
                              use_split=False)
        return fed, res

    fed_p, res_p = run(True, list(range(9)))       # 9 -> padded to 10
    _, res_u = run(False, list(range(9)))          # 9 exact (no padding)
    for n in range(9):
        (lp, l1), (lu, l2) = res_p[n], res_u[n]
        assert l1 == l2
        for a, b in zip(jax.tree_util.tree_leaves(lp),
                        jax.tree_util.tree_leaves(lu)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # a 10-cohort on the padded engine reuses the 9-cohort's executable
    iters = {n: infinite_batches(fed_p.data[n].tokens,
                                 fed_p.data[n].labels, 8, seed=200 + n)
             for n in range(10)}
    fed_p.group_steps(list(range(10)), fed_p.lora0, 2, iters,
                      use_split=False)
    sizes = fed_p.engine.compile_cache_sizes()
    assert all(v == 1 for v in sizes.values()), sizes
