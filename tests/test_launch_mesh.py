"""Launch plumbing shared by the entry points: Auto-axis meshes, the mesh
the examples derive from the devices they find, the persistent compile
cache's location, state built straight into its rest sharding, and the
train() entry that chip_smoke.py drives."""
import math

import jax
import numpy as np
import pytest
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.launch import compile_cache, train
from repro.launch.mesh import local_mesh_shape, make_mesh


def test_make_mesh_axes_are_auto():
    mesh = make_mesh((1, 1), ("data", "model"))
    assert mesh.axis_names == ("data", "model")
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)


@pytest.mark.parametrize("n,shape", [(1, (1, 1)), (4, (4, 1)), (8, (2, 4)),
                                     (16, (4, 4)), (6, (6, 1))])
def test_local_mesh_shape_uses_every_device(n, shape):
    assert local_mesh_shape(n) == shape


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_defaults_to_repo_dir(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    d = compile_cache.enable_compile_cache()
    assert d == str(compile_cache.REPO_CACHE_DIR)
    assert compile_cache.REPO_CACHE_DIR.parent.joinpath("pyproject.toml").exists()
    assert jax.config.jax_compilation_cache_dir == d


def test_compile_cache_env_dir_wins_and_sets_nothing(monkeypatch, tmp_path,
                                                     restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_init_train_state_on_mesh_matches_host_init(mesh11):
    from repro import configs
    from repro.core.qsdp import MeshSpec, QSDPConfig
    from repro.models.transformer import Model
    from repro.optim import AdamWConfig, make_adamw
    from repro.train.step import init_train_state, state_pspecs

    model = Model(configs.get_smoke("gpt-125m"),
                  MeshSpec(axes=("data", "model"), shape=(1, 1)),
                  QSDPConfig(min_quant_size=256))
    opt = make_adamw(AdamWConfig(lr=1e-3))
    k = jax.random.PRNGKey(3)
    # jitted both ways: placement must not change a bit (eager and jitted
    # init may differ in the last ulp of the scaled normals)
    plain = jax.jit(lambda k: init_train_state(model, opt, k))(k)
    placed = init_train_state(model, opt, k, mesh11)
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(placed)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    specs = jax.tree.leaves(state_pspecs(model),
                            is_leaf=lambda x: isinstance(x, P))
    for leaf, spec in zip(jax.tree.leaves(placed), specs):
        assert leaf.sharding.is_equivalent_to(NamedSharding(mesh11, spec),
                                              leaf.ndim)


def test_train_entry_returns_log_and_compiled_step():
    args = train.parse_args(["--arch", "gpt-125m", "--smoke", "--steps", "3",
                             "--batch", "2", "--seq", "16", "--log-every", "1"])
    res = train.train(args)
    assert [r["step"] for r in res["log"]] == [0, 1, 2]
    assert all(math.isfinite(r["loss"]) and r["step_s"] > 0 for r in res["log"])
    assert res["compile_s"] > 0
    assert "ENTRY" in res["compiled"].as_text()
