"""Launcher helpers: batch partitioning, ELSA boundaries, mesh factory."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import ASSIGNED, get_config
from repro.launch.mesh import (chips, client_axes, data_axes,
                               make_federation_mesh)
from repro.launch.train import batch_pspec, elsa_boundaries, elsa_channel_specs


MESH = AbstractMesh((16, 16), ("data", "model"))
MESH3 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def test_batch_pspec_divisible():
    assert batch_pspec(MESH, 256) == P("data")
    assert batch_pspec(MESH3, 256) == P(("pod", "data"))


def test_batch_pspec_indivisible_replicates():
    assert batch_pspec(MESH, 1) == P()
    assert batch_pspec(MESH3, 8) == P()


def test_data_axes():
    assert data_axes(MESH) == ("data",)
    assert data_axes(MESH3) == ("pod", "data")


def test_chips():
    assert chips(MESH) == 256
    assert chips(MESH3) == 512


def test_client_axes():
    fm = make_federation_mesh(1)
    assert client_axes(fm) == ("clients",)
    assert client_axes(MESH) == ()           # production mesh: no clients
    assert client_axes(MESH3) == ("pod",)    # pod folds into the stack


def test_make_federation_mesh_defaults_to_all_devices():
    n = len(jax.devices())
    mesh = make_federation_mesh()
    assert tuple(mesh.shape) == ("clients",)
    assert mesh.shape["clients"] == n
    assert chips(mesh) == n


def test_make_federation_mesh_subset_and_validation():
    assert chips(make_federation_mesh(1)) == 1
    with pytest.raises(ValueError):
        make_federation_mesh(len(jax.devices()) + 1)
    with pytest.raises(ValueError):
        make_federation_mesh(0)


def test_make_federation_mesh_pods():
    devs = jax.devices()
    if len(devs) % 2 == 0 and len(devs) >= 2:
        mesh = make_federation_mesh(pods=2)
        assert tuple(mesh.shape) == ("pod", "clients")
        assert mesh.shape["pod"] == 2
        assert chips(mesh) == len(devs)
    with pytest.raises(ValueError):
        make_federation_mesh(1, pods=3)      # 1 device, 3 pods


def test_elsa_boundaries_valid_for_all_archs():
    for arch in ASSIGNED:
        cfg = get_config(arch)
        if cfg.family not in ("dense", "moe"):
            continue
        p, q = elsa_boundaries(cfg)
        n = cfg.num_layers - (cfg.moe.first_dense_layers if cfg.moe else 0)
        assert 1 <= p <= 6
        assert p + q + 2 == n          # o_fix = 2 (label privacy)
        assert q >= 1


def test_elsa_channel_specs_shapes():
    cfg = get_config("llama3-8b")
    specs, z = elsa_channel_specs(cfg, r=16, y=3, rho=2.1)
    d = cfg.d_model
    assert specs["u"].shape == (d, 16)
    assert specs["v"].shape == (16, 16)
    assert specs["bucket"].shape == (3, d)
    assert specs["bucket"].dtype == jnp.int32
    # rho = D / (Y Z) within ~20% of the requested 2.1
    rho = d / (3 * z)
    assert 1.6 < rho < 2.6
