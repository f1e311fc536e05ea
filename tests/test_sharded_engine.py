"""Mesh-sharded engine: sharded-vs-unsharded parity, placement, donation.

Methodology mirrors ``tests/test_engine.py``: partitioned compilation
perturbs f32 bits at partition boundaries (measured ~1e-6 after a single
local step on 8 forced host devices), and the split-model gradient map
is chaotic (parameter-Lipschitz ~1e5), so f32 trajectories under real
multi-device sharding diverge *by design*.  Multi-device trajectory
parity therefore runs in x64 with a small lr (discrepancies stay at the
1e-12 level and trajectories stay glued), while the f32 golden history
pins the mesh *code path* — fused cross-group dispatch, shard-multiple
cohort padding, NamedSharding placement — on a 1-device mesh, where
placement is bitwise-inert.

Run single-device these tests cover the mesh path degenerately; the CI
``multi-device`` job re-runs them under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` where the
leading client axis really splits 8 ways.
"""
import json
import os

import jax
import numpy as np
import pytest

import golden_env
from repro.federation.engine import (bucket_size, donate_buffers,
                                     is_client_map, placement_platform)
from repro.federation.simulation import FedConfig, Federation
from repro.launch.mesh import client_axes, make_federation_mesh

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "bert_parity.json")
N_DEV = len(jax.devices())

# same chaos-safe configuration as tests/test_engine.py
PARITY_KW = dict(n_clients=6, n_edges=2, alpha=0.2, poisoned=(4,),
                 total_examples=300, probe_q=8, local_warmup_steps=2,
                 lr=1e-4, layers=4, t_rounds=1, batch_size=16,
                 dtype="float64", seed=0)
# smaller causal-LM variant (second registered model family)
PARITY_KW_LM = dict(PARITY_KW, model="llama3-8b", n_clients=4,
                    total_examples=200)


def _max_tree_diff(a, b):
    """Works across placements: pulls both trees to host first."""
    return max(float(np.abs(np.asarray(x) - np.asarray(y)).max())
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


# ---------------------------------------------------------------------------
# helpers: bucket sizing, client-map detection, donation gating
# ---------------------------------------------------------------------------

def test_bucket_size_shard_multiples():
    assert bucket_size(5) == 5                 # unchanged without a mesh
    assert bucket_size(5, 1) == 5
    assert bucket_size(5, 8) == 8              # next ladder size % 8 == 0
    assert bucket_size(9, 8) == 16
    assert bucket_size(17, 8) == 24
    assert bucket_size(5, 3) == 6
    assert bucket_size(65, 8) == 72            # beyond the ladder: next
    assert bucket_size(65, 6) == 66            # multiple of n itself
    for mult in (2, 3, 4, 8):
        for n in (1, 7, 33, 100):
            s = bucket_size(n, mult)
            assert s >= n and s % mult == 0


def test_is_client_map_distinguishes_lora_trees():
    assert is_client_map({0: "t", 3: "t"})
    # fedavg-random cohorts come out of rng.choice as numpy ints
    assert is_client_map({np.int64(2): "t", np.int32(5): "t"})
    assert not is_client_map({"q_a": 1})       # LoRA pytree node
    assert not is_client_map({True: 1})
    assert not is_client_map({})
    assert not is_client_map([1, 2])


def test_group_steps_client_map_both_backends():
    """group_steps' documented {client: tree} theta form works on both
    backends and, with every entry the shared tree, matches the
    shared-theta call exactly."""
    from repro.data.pipeline import infinite_batches
    kw = dict(n_clients=3, n_edges=1, total_examples=120, layers=4,
              local_warmup_steps=1, probe_q=8, use_channel=False)
    for backend in ("batched", "reference"):
        fed = Federation(FedConfig(**kw), backend=backend)
        clients = [0, 1, 2]

        def its():
            return {n: infinite_batches(fed.data[n].tokens,
                                        fed.data[n].labels,
                                        fed.fed.batch_size, seed=n)
                    for n in clients}

        r_shared = fed.group_steps(clients, fed.lora0, 1, its())
        r_map = fed.group_steps(clients, {n: fed.lora0 for n in clients},
                                1, its())
        for n in clients:
            assert r_shared[n][1] == r_map[n][1]
            assert _max_tree_diff(r_shared[n][0], r_map[n][0]) == 0.0


def test_donation_gates_on_placement():
    assert not donate_buffers("cpu")
    assert donate_buffers("tpu") and donate_buffers("gpu")
    mesh = make_federation_mesh()
    assert placement_platform(mesh) == mesh.devices.flat[0].platform
    assert placement_platform(None) == jax.default_backend()


def test_engine_donation_decision_matches_backend():
    """The engine's donate flag follows the arrays' actual placement
    (mesh devices when sharding, default backend otherwise)."""
    fed = Federation(FedConfig(n_clients=2, n_edges=1, total_examples=64,
                               layers=4), mesh=make_federation_mesh())
    eng = fed.engine
    assert eng.platform == jax.devices()[0].platform
    assert eng.donate == donate_buffers(eng.platform)
    fed2 = Federation(FedConfig(n_clients=2, n_edges=1, total_examples=64,
                                layers=4))
    assert fed2.engine.donate == donate_buffers(jax.default_backend())


# ---------------------------------------------------------------------------
# constructor validation
# ---------------------------------------------------------------------------

def test_reference_backend_rejects_mesh():
    with pytest.raises(ValueError, match="batched"):
        Federation(FedConfig(n_clients=2, total_examples=64, layers=4),
                   backend="reference", mesh=make_federation_mesh())


def test_engine_rejects_mesh_without_client_axis():
    from jax.sharding import Mesh
    cfg = FedConfig(n_clients=2, n_edges=1, total_examples=64, layers=4)
    for axes in (("data",), ("pod",)):   # pod-only: production mesh shape
        bad = Mesh(np.asarray(jax.devices()[:1]), axes)
        fed = Federation(cfg, mesh=bad)
        with pytest.raises(ValueError, match="clients"):
            fed.engine


# ---------------------------------------------------------------------------
# golden parity: the mesh code path is bitwise-inert on a 1-device mesh
# ---------------------------------------------------------------------------

def test_sharded_federation_matches_prerefactor_golden():
    """Fused cross-group dispatch + shard-multiple padding + NamedSharding
    placement are bitwise-inert on a 1-device mesh: the sharded history
    equals a same-environment unsharded run exactly, and — single-device,
    where the committed record's environment is reproduced — the
    pre-refactor golden history at f32/1e-9.  (Forcing multiple host
    devices changes CPU f32 bits globally, sharded or not, so the golden
    anchor only binds at one device.)"""
    gold = json.load(open(GOLDEN))
    kw = dict(gold["config"])
    if "bert_layers" in kw:
        kw["layers"] = kw.pop("bert_layers")   # golden predates the rename
    kw["poisoned"] = tuple(kw["poisoned"])
    run_kw = dict(global_rounds=gold["run"]["global_rounds"],
                  steps_per_round=gold["run"]["steps_per_round"])
    fed = Federation(FedConfig(**kw), backend="batched",
                     mesh=make_federation_mesh(1))
    h = fed.run(gold["run"]["method"], **run_kw)
    fu = Federation(FedConfig(**kw), backend="batched")
    hu = fu.run(gold["run"]["method"], **run_kw)
    np.testing.assert_array_equal(h["loss"], hu["loss"])
    np.testing.assert_array_equal(h["accuracy"], hu["accuracy"])
    np.testing.assert_array_equal(h["delta"], hu["delta"])
    assert _max_tree_diff(fed.last_theta, fu.last_theta) == 0.0
    if N_DEV == 1:
        # float precision in the one supported installation
        msg = (f"golden recorded under {gold.get('env')}, running under "
               f"{golden_env.fingerprint()}")
        tol = dict(rtol=0, atol=1e-9, err_msg=msg)
        np.testing.assert_allclose(h["loss"], gold["loss"], **tol)
        np.testing.assert_allclose(h["accuracy"], gold["accuracy"], **tol)
        np.testing.assert_allclose(h["delta"], gold["delta"], **tol)
    assert h["round"] == gold["round"]


@pytest.mark.parametrize("method", ["elsa", "fedavg-random"])
def test_fused_dispatch_bitwise_inert_multi_round(method):
    """The 1-device-mesh fused path stays bitwise-identical with
    t_rounds > 1 (loss recording order is group-major like the
    per-group path) and with numpy-int cohorts (fedavg-random samples
    clients via rng.choice)."""
    kw = dict(n_clients=6, n_edges=2, alpha=0.2, poisoned=(4,),
              total_examples=300, probe_q=8, local_warmup_steps=2,
              layers=4, t_rounds=2, batch_size=16, seed=0)
    fu = Federation(FedConfig(**kw), backend="batched")
    hu = fu.run(method, global_rounds=1, steps_per_round=2)
    fs = Federation(FedConfig(**kw), backend="batched",
                    mesh=make_federation_mesh(1))
    hs = fs.run(method, global_rounds=1, steps_per_round=2)
    np.testing.assert_array_equal(hs["loss"], hu["loss"])
    np.testing.assert_array_equal(hs["accuracy"], hu["accuracy"])
    for n in range(kw["n_clients"]):
        np.testing.assert_array_equal(hs["client_losses"][n],
                                      hu["client_losses"][n])
    assert _max_tree_diff(fs.last_theta, fu.last_theta) == 0.0


# ---------------------------------------------------------------------------
# x64 parity: real multi-device sharding computes the same math
# ---------------------------------------------------------------------------

def _assert_sharded_parity(kw, method="elsa", rounds=2, steps=2):
    mesh = make_federation_mesh()        # all available devices
    with jax.enable_x64(True):
        fu = Federation(FedConfig(**kw), backend="batched")
        hu = fu.run(method, global_rounds=rounds, steps_per_round=steps)
        fs = Federation(FedConfig(**kw), backend="batched", mesh=mesh)
        hs = fs.run(method, global_rounds=rounds, steps_per_round=steps)
    assert abs(hu["final_accuracy"] - hs["final_accuracy"]) <= 1e-4
    for n in range(kw["n_clients"]):
        a = np.asarray(hu["client_losses"][n])
        b = np.asarray(hs["client_losses"][n])
        assert a.shape == b.shape
        if a.size:
            assert np.abs(a - b).max() <= 1e-5, f"client {n}"
    assert _max_tree_diff(fu.last_theta, fs.last_theta) <= 1e-5
    return fs


def test_sharded_matches_unsharded_x64_bert():
    fs = _assert_sharded_parity(PARITY_KW)
    assert fs.engine.n_shards == N_DEV


def test_sharded_matches_unsharded_x64_causal_lm():
    _assert_sharded_parity(PARITY_KW_LM, method="fedavg", rounds=1)


def test_sharded_fedprox_matches_unsharded_x64():
    """The replicated FedProx anchor broadcasts against sharded stacks."""
    _assert_sharded_parity(PARITY_KW, method="fedprox", rounds=1)


# ---------------------------------------------------------------------------
# placement: arrays really shard across the mesh
# ---------------------------------------------------------------------------

@pytest.mark.skipif(N_DEV < 2, reason="needs >= 2 devices (run under "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
def test_sharded_round_places_arrays_on_all_devices():
    mesh = make_federation_mesh()
    fed = Federation(FedConfig(n_clients=8, n_edges=2, total_examples=320,
                               layers=4, local_warmup_steps=2, probe_q=8),
                     mesh=mesh)
    assert fed.engine.n_shards == N_DEV
    h = fed.run("fedavg", global_rounds=1, steps_per_round=2)
    assert np.isfinite(h["loss"]).all()
    # the aggregated theta came from mesh-resident shards
    leaf = jax.tree_util.tree_leaves(fed.last_theta)[0]
    assert leaf.sharding.device_set == set(mesh.devices.flat)
    # frozen params were replicated up front, not sharded
    froz = jax.tree_util.tree_leaves(fed.engine.frozen)[0]
    assert froz.sharding.is_fully_replicated


@pytest.mark.skipif(N_DEV < 2, reason="needs >= 2 devices")
def test_pod_mesh_runs():
    """("pod", "clients") meshes shard over the composite axes."""
    mesh = make_federation_mesh(pods=2)
    assert client_axes(mesh) == ("pod", "clients")
    fed = Federation(FedConfig(n_clients=4, n_edges=2, total_examples=160,
                               layers=4, local_warmup_steps=2, probe_q=8),
                     mesh=mesh)
    assert fed.engine.n_shards == N_DEV
    h = fed.run("fedavg", global_rounds=1, steps_per_round=2)
    assert np.isfinite(h["loss"]).all()


# ---------------------------------------------------------------------------
# event-driven runtime over the sharded engine
# ---------------------------------------------------------------------------

def test_runtime_schedulers_run_sharded():
    """Every scheduler's dispatches route through the sharded engine
    (cohort padding keeps compiles bounded; placement is invisible to
    the event loop)."""
    from repro.runtime import RuntimeConfig
    mesh = make_federation_mesh()
    for policy in ("sync", "deadline"):
        fed = Federation(FedConfig(n_clients=6, n_edges=2,
                                   total_examples=240, layers=4,
                                   local_warmup_steps=2, probe_q=8),
                         mesh=mesh)
        h = fed.run("fedavg", global_rounds=1, steps_per_round=2,
                    runtime=RuntimeConfig(policy=policy))
        assert h["policy"] == policy
        assert np.isfinite(h["loss"]).all()
        assert fed.engine.n_shards == N_DEV
