"""The control, the plain reference computed at the next precision below
the configuration's (4-bit weights and gradients for its 8-bit wire), put
in the program's place, reads at least three times what the program reads
at a tiny size on the CPU, for every cell, on three seeds; for the
training cells it also fails the cell's own limits there."""
import time

import jax
import pytest

from bench.drivers import serve, train
from bench.tests import tiny
from bench.tests.test_faults import SERVE_CELLS, TRAIN_CELLS

SEEDS = [3, 2**31 + 11, 2**40 + 5]


@pytest.mark.parametrize("workload", TRAIN_CELLS)
@pytest.mark.parametrize("seed", SEEDS)
def test_train_control_fails_a_limit(workload, seed):
    spec = tiny.spec(workload)
    chips = spec["chips"]
    model, opt, mesh, ms, state, step, key = train.build(
        spec["config"], chips, seed, jax.devices()[:chips])
    feed = train.Feed(spec["config"]["vocab_size"], spec["traffic"], seed,
                      spec["config"]["train"]["batch_per_chip"] * chips, mesh,
                      ms.fsdp_axes)
    step_key = lambda i: jax.random.fold_in(jax.random.PRNGKey(seed % 2147483647 + 1), i)
    with mesh:
        _, prog = train.first_steps(model, state, step, feed, step_key, key,
                                    spec["cell"]["check_steps"],
                                    spec["config"]["train"]["b1"])
    ref = train.reference_run(spec, seed, feed)
    ctl = train.gap_numbers(train.reference_run(spec, seed, feed, bits=4), ref)
    own = train.gap_numbers(prog, ref)
    lim = spec["cell"]["limits"]
    assert any(ctl[k] > lim[k] for k in lim), (ctl, lim)
    assert any(ctl[k] >= 3 * own[k] for k in lim), (ctl, own)


@pytest.mark.parametrize("workload", SERVE_CELLS)
@pytest.mark.parametrize("seed", SEEDS)
def test_serve_control_fails_the_limit(workload, seed, monkeypatch):
    spec = tiny.spec(workload)
    res = {}

    def compare(spec, seed, served):
        picked = serve.sample(served, seed, spec["cell"]["check_tokens"])
        res["own"] = max(serve.reference_gaps(spec, seed, picked))
        res["control"] = max(serve.reference_gaps(spec, seed, picked, bits=4))
        return {}

    monkeypatch.setattr(serve, "compare", compare)
    serve.run(spec, seed, 2.0, False, time.perf_counter(), tiny.DEVICE)
    # a narrow model's logits are smaller, so the absolute gap is held to
    # the program's own reading at this size, not to the cell's limit
    assert res["control"] >= 3 * res["own"], res
