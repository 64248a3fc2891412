"""Sharding-rule unit tests (no multi-device mesh needed: the rules are
pure functions of shapes + a mesh object built on 1 device via AbstractMesh
semantics — we use a real 1×1 mesh but with fake axis sizes through
jax.sharding.AbstractMesh)."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import SMOKES
from repro.launch.sharding import (DistConfig, batch_specs, chain_axes,
                                   dp_axes, param_specs)
from repro.models import init_params


def mesh_single():
    # AbstractMesh takes (axis_sizes, axis_names)
    return AbstractMesh((16, 16), ("data", "model"))


def mesh_multi():
    return AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def test_chain_axes_mapping():
    assert chain_axes(mesh_single(), 1) == ()
    assert chain_axes(mesh_single(), 16) == ("data",)
    assert chain_axes(mesh_multi(), 2) == ("pod",)
    assert chain_axes(mesh_multi(), 32) == ("pod", "data")
    with pytest.raises(ValueError):
        chain_axes(mesh_single(), 4)


def test_dp_axes_complement():
    assert dp_axes(mesh_single(), 1) == ("data",)
    assert dp_axes(mesh_single(), 16) == ()
    assert dp_axes(mesh_multi(), 2) == ("data",)
    assert dp_axes(mesh_multi(), 1) == ("pod", "data")


@pytest.mark.parametrize("name", sorted(SMOKES))
def test_param_specs_cover_tree_and_divide(name):
    """Every param leaf gets a spec whose sharded dims divide evenly."""
    cfg = SMOKES[name]
    mesh = AbstractMesh((4, 4), ("data", "model"))
    params = jax.eval_shape(
        lambda k: init_params(k, cfg, 4), jax.ShapeDtypeStruct((2,),
                                                               jnp.uint32))
    dist = DistConfig(n_chains=4, fsdp=False)
    specs = param_specs(params, mesh, dist)
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))

    def check(leaf, spec):
        assert isinstance(spec, P)
        assert len(spec) <= leaf.ndim
        for dim, entry in zip(leaf.shape, tuple(spec)):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            total = 1
            for a in axes:
                total *= sizes[a]
            assert dim % total == 0, (name, leaf.shape, spec)

    jax.tree.map(check, params, specs,
                 is_leaf=lambda x: isinstance(x, P))
    # chain dim must be sharded over 'data' on every leaf (axis 0, or
    # axis 1 for scanned stacks whose leading dim is the layer index)
    leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert all("data" in tuple(s)[:2] for s in leaves if len(tuple(s))), \
        "all leaves carry the chain axis"


def test_batch_specs_train_vs_serve():
    mesh = mesh_multi()
    batch = {"tokens": jax.ShapeDtypeStruct((2, 128, 512), jnp.int32)}
    train_spec = batch_specs(batch, mesh, DistConfig(n_chains=2))
    assert tuple(train_spec["tokens"]) == ("pod", "data", None)
    serve_spec = batch_specs(batch, mesh, DistConfig(n_chains=2),
                             replicated_serve=True)
    assert tuple(serve_spec["tokens"]) == ("pod", None, None)


def test_fsdp_only_when_data_free():
    """FSDP must silently disable when chains occupy the data axis."""
    mesh = mesh_single()
    params = {"lm_head": jax.ShapeDtypeStruct((16, 64, 64), jnp.float32)}
    spec_fsdp = param_specs(params, mesh, DistConfig(n_chains=1, fsdp=True))
    assert tuple(spec_fsdp["lm_head"]) == (None, "data", "model")
    spec_chain = param_specs(params, mesh, DistConfig(n_chains=16,
                                                      fsdp=True))
    assert tuple(spec_chain["lm_head"]) == ("data", None, "model")
