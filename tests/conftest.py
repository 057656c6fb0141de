"""Shared fixtures.  NOTE: no XLA_FLAGS here on purpose — unit/smoke tests
run on the real single CPU device; distributed behaviour is covered by
subprocess tests (test_distributed.py) that set their own device count,
and by the dry-run (launch/dryrun.py) which forces 512 in-process.
"""
import jax
import pytest

from repro.launch.mesh import make_mesh

try:
    import hypothesis  # noqa: F401  (preferred: pip install -e .[test])
except ImportError:  # hermetic environment — run properties as seeded sweeps
    from _hypothesis_stub import install as _install_hypothesis_stub

    _install_hypothesis_stub()


@pytest.fixture(scope="session")
def mesh11():
    """Trivial (data=1, model=1) mesh — exercises the full shard_map code
    path (collectives degenerate to identity) on one device."""
    return make_mesh((1, 1), ("data", "model"))


@pytest.fixture(scope="session")
def key():
    return jax.random.PRNGKey(0)
