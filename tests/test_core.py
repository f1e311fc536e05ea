"""Unit tests for the ELSA core modules (Eqs. 4–24)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aggregation as agg
from repro.core import clustering as clus
from repro.core import comm_model as cm
from repro.core import splitting as sp
from repro.core import ssop as ssop_mod
from repro.core import trust as trust_mod
from repro.core.fingerprint import (Fingerprint, divergence_matrix,
                                    fingerprint, kl_gaussian, sym_kl)
from repro.core.sketch import compress, decompress, make_plan


def test_fingerprint_kl_properties():
    a = fingerprint(jax.random.normal(jax.random.PRNGKey(0), (64, 12)))
    b = fingerprint(3.0 + jax.random.normal(jax.random.PRNGKey(1), (64, 12)))
    assert abs(float(kl_gaussian(a, a))) < 1e-3
    assert float(kl_gaussian(a, b)) > 1.0
    assert abs(float(sym_kl(a, b)) - float(sym_kl(b, a))) < 1e-3


def test_divergence_matrix_shape_and_symmetry():
    fps = [fingerprint(jax.random.normal(jax.random.PRNGKey(i), (32, 8)))
           for i in range(4)]
    d = divergence_matrix(fps)
    assert d.shape == (4, 4)
    np.testing.assert_allclose(d, d.T, atol=1e-6)
    assert (np.diag(d) == 0).all()


@pytest.mark.parametrize("n,q,chunk_bytes", [
    (1, 8, None), (2, 8, None), (6, 8, None),
    (1, 32, None), (2, 32, None), (6, 32, None),
    # 4 pairs per chunk of 30: seven scanned chunks and a remainder of 2
    (6, 32, 4 * (4 * 64 * 64 * 4)),
])
def test_divergence_matrix_matches_pairwise_sym_kl(monkeypatch, n, q,
                                                   chunk_bytes):
    """The one-program matrix equals a loop of ``sym_kl`` over pairs."""
    import repro.core.fingerprint as fp_mod
    d = 64
    if chunk_bytes is not None:
        monkeypatch.setattr(fp_mod, "_CHUNK_BYTES", chunk_bytes)
        assert fp_mod._pair_chunk(d, 4) == 4 < n * (n - 1)
    fps = [fingerprint(0.3 * i + (1.0 + 0.2 * i) * jax.random.normal(
        jax.random.PRNGKey(i), (q, d))) for i in range(n)]
    got = divergence_matrix(fps)
    want = np.zeros((n, n))
    for i in range(n):
        for j in range(i + 1, n):
            want[i, j] = want[j, i] = float(sym_kl(fps[i], fps[j]))
    assert got.dtype == np.float64 and got.shape == (n, n)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_array_equal(got, got.T)
    assert (np.diag(got) == 0).all()


def test_trust_downweights_outlier():
    n = 6
    div = np.full((n, n), 1.0)
    np.fill_diagonal(div, 0.0)
    div[5, :] = div[:, 5] = 10.0   # behavioral outlier
    div[5, 5] = 0.0
    norms = np.full((n, 16), 10.0)
    t = trust_mod.trust_scores(div, norms)
    assert t[5] < t[:5].min()


def test_clustering_groups_similar_clients():
    rng = np.random.default_rng(0)
    n, k = 12, 3
    div = np.abs(rng.normal(5, 0.5, (n, n)))
    div = (div + div.T) / 2
    np.fill_diagonal(div, 0)
    for g in range(3):
        idx = np.arange(4 * g, 4 * g + 4)
        div[np.ix_(idx, idx)] *= 0.02
    trust = np.ones(n)
    lat = np.full((n, k), 500.0)
    for g in range(3):
        lat[4 * g:4 * g + 4, g] = 30.0
    res = clus.cluster_clients(div, trust, lat, tau_max=200.0, w_min=0.1)
    for g in range(3):
        members = res.groups[g]
        assert set(members) == set(range(4 * g, 4 * g + 4))


def test_clustering_excludes_unreachable():
    div = np.zeros((3, 3))
    trust = np.ones(3)
    lat = np.array([[50.0], [60.0], [900.0]])
    res = clus.cluster_clients(div, trust, lat, tau_max=200.0, w_min=0.1)
    assert res.assignment[2] is None


def test_split_policy_bounds_and_privacy():
    pol = sp.SplitPolicy(num_blocks=12, o_fix=2, p_min=1, p_max=6)
    for h, bw in [(1e9, 1e6), (1e12, 1e9), (5e10, 5e7)]:
        p, q, o = sp.split_for_client(h, bw, 1e12, 1e9, pol)
        assert 1 <= p <= 6 and o == 2 and p + q + o == 12
    # weak compute + fat uplink -> offload more (small p)
    p_weak, _, _ = sp.split_for_client(1e9, 1e9, 1e12, 1e9, pol)
    p_strong, _, _ = sp.split_for_client(1e12, 1e6, 1e12, 1e9, pol)
    assert p_weak <= p_strong


def test_ssop_orthogonal_and_exact_inverse():
    j = jax.random.normal(jax.random.PRNGKey(0), (50, 48))
    so = ssop_mod.make_ssop(j, 8, "salt", 3)
    q = ssop_mod.q_matrix(so)
    np.testing.assert_allclose(np.asarray(q.T @ q), np.eye(48), atol=1e-5)
    h = jax.random.normal(jax.random.PRNGKey(1), (10, 48))
    np.testing.assert_allclose(
        np.asarray(ssop_mod.apply_ssop_inverse(ssop_mod.apply_ssop(h, so), so)),
        np.asarray(h), atol=1e-5)


def test_ssop_seed_determinism_and_secrecy():
    v1 = ssop_mod.random_orthogonal(8, ssop_mod.client_seed("s", 1))
    v1b = ssop_mod.random_orthogonal(8, ssop_mod.client_seed("s", 1))
    v2 = ssop_mod.random_orthogonal(8, ssop_mod.client_seed("s", 2))
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v1b))
    assert float(jnp.abs(v1 - v2).max()) > 0.1


def test_sketch_roundtrip_identity_when_lossless():
    """Z == D with Y=1 is a signed permutation -> exact recovery."""
    plan = make_plan(16, 1, 16, seed=1)
    # force injective buckets
    import jax.numpy as jnp2
    plan = plan._replace(bucket=jnp2.arange(16, dtype=jnp2.int32)[None, :])
    h = jax.random.normal(jax.random.PRNGKey(0), (4, 16))
    rec = decompress(compress(h, plan), plan)
    np.testing.assert_allclose(np.asarray(rec), np.asarray(h), atol=1e-6)


def test_sketch_error_grows_with_rho():
    h = jax.random.normal(jax.random.PRNGKey(0), (32, 256))
    errs = []
    for z in (128, 32, 8):
        plan = make_plan(256, 3, z, seed=2)
        rec = decompress(compress(h, plan), plan)
        errs.append(float(jnp.linalg.norm(rec - h) / jnp.linalg.norm(h)))
    assert errs[0] < errs[1] < errs[2]


def test_edge_weight_and_cloud_aggregate():
    assert agg.edge_weight(0.0, 1.0) == 1.0
    assert agg.edge_weight(1.0, 1.0) == 0.5
    trees = {0: {"w": jnp.ones(3)}, 1: {"w": 3 * jnp.ones(3)}}
    out = agg.cloud_aggregate(trees, {0: 1.0, 1: 1.0})
    np.testing.assert_allclose(np.asarray(out["w"]), 2 * np.ones(3))


def test_convergence_criterion():
    a = {"w": jnp.zeros(4)}
    b = {"w": jnp.full(4, 1e-6)}
    assert agg.converged(a, b, xi=1e-3)
    assert not agg.converged(a, {"w": jnp.ones(4)}, xi=1e-3)


def test_comm_model_eq22_24():
    cc = cm.CommConfig(t_rounds=2, bytes_per_param=4, seq_len=128,
                       d_hidden=768, rho=2.0, lora_bytes=1_000_000)
    vol = cm.round_volume_bytes(cc, {0: [8, 8], 1: [16]}, n_edges=2)
    expect = 2 * 2 * 4 * 128 * 768 / 2.0 * 32 + 2 * 1_000_000
    assert abs(vol - expect) < 1e-6
    t = cm.client_comm_time(cc, 8, 1e7)
    assert abs(t - (2 * 2 * 8 * 128 * 4 * 768 / 2.0) / 1e7) < 1e-9
    total = cm.total_comm_time(cc, [8, 16], [1e7, 1e7], 10)
    assert total == 10 * cm.client_comm_time(cc, 16, 1e7)


def test_comm_model_monotonicity_and_straggler_bound():
    import dataclasses

    base = cm.CommConfig(t_rounds=2, bytes_per_param=4, seq_len=64,
                         d_hidden=768, rho=1.0, lora_bytes=500_000)
    # Eq. 23: time strictly decreases as rho grows (more compression)...
    times = [cm.client_comm_time(dataclasses.replace(base, rho=r), 16, 1e7)
             for r in (1.0, 2.0, 3.3, 8.0)]
    assert all(a > b for a, b in zip(times, times[1:]))
    # ...and as bandwidth grows
    bws = [cm.client_comm_time(base, 16, bw) for bw in (1e6, 1e7, 1e8)]
    assert all(a > b for a, b in zip(bws, bws[1:]))
    # Eq. 24 is the straggler max: total >= G * every client's own time
    batches, bands = [8.0, 16.0, 24.0], [2e7, 1e7, 5e6]
    total = cm.total_comm_time(base, batches, bands, 7)
    for b, bw in zip(batches, bands):
        assert total >= 7 * cm.client_comm_time(base, b, bw) - 1e-12
    # Eq. 22 volume scales linearly in the summed batch sizes
    v1 = cm.round_volume_bytes(base, {0: [8.0]}, n_edges=1)
    v2 = cm.round_volume_bytes(base, {0: [16.0]}, n_edges=1)
    assert abs((v2 - base.lora_bytes) - 2 * (v1 - base.lora_bytes)) < 1e-6


def test_comm_config_from_derives_real_shapes():
    import numpy as np

    from repro.configs import get_config
    from repro.core.sketch import make_plan
    from repro.federation.simulation import FedConfig
    from repro.models.bert import bert_specs
    from repro.models.params import init_tree
    import jax

    cfg = get_config("bert-base").reduced().with_(
        num_layers=4, param_dtype="float32", activation_dtype="float32")
    fed = FedConfig(n_clients=4, t_rounds=3, seq_len=48, num_classes=4)
    plan = make_plan(cfg.d_model, 3, 20, seed=0)

    cc = cm.comm_config_from(cfg, fed, plan)
    assert cc.d_hidden == cfg.d_model
    assert cc.seq_len == 48 and cc.t_rounds == 3
    assert cc.bytes_per_param == 4.0
    assert abs(cc.rho - cfg.d_model / (3 * 20)) < 1e-9
    # lora_bytes from the spec tree == bytes of the materialized tree
    tree = init_tree(bert_specs(cfg, 4)["lora"], jax.random.PRNGKey(0))
    manual = sum(l.size * l.dtype.itemsize
                 for l in jax.tree_util.tree_leaves(tree))
    assert cc.lora_bytes == manual
    assert cm.lora_tree_bytes(tree) == manual
    # no plan -> uncompressed (rho = 1)
    assert cm.comm_config_from(cfg, fed, None).rho == 1.0
    # per-dtype zeta: bf16 halves the activation bytes
    cfg16 = cfg.with_(activation_dtype="bfloat16")
    assert cm.comm_config_from(cfg16, fed, plan).bytes_per_param == 2.0
