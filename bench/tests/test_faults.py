"""A run of each cell, at a tiny size on the CPU with the look for a chip
skipped and the cell's own limits, comes out not correct when the timed
path is broken underneath in each way the cell can break; and the broken
run reads at least three times what the sound run of the same seed reads
on some compared number, so the comparison, not the tiny size, fails it."""
import time

import pytest

from bench import harness
from bench.drivers import serve, train
from bench.tests import tiny

_BENCH = harness.load_json(harness.ROOT / "BENCHMARK.json")
_KIND = {w["name"]: harness.load_json(harness.BENCH / "traffic" / f"{w['traffic']}.json")["kind"]
         for w in _BENCH["workloads"]}
TRAIN_CELLS = [w for w, k in _KIND.items() if k == "train"]
SERVE_CELLS = [w for w, k in _KIND.items() if k == "serve"]
TRAIN_FAULTS = [(w, f) for w in TRAIN_CELLS for f in ["state_unchanged", "half_batch"]]


def _train(workload, monkeypatch, fault=None):
    import repro.train.step as step_mod

    monkeypatch.undo()
    orig = step_mod.make_jitted_train_step

    def broken(model, opt, mesh, **kw):
        real = orig(model, opt, mesh, donate=False, **kw)
        if fault == "state_unchanged":
            return lambda state, batch, key: (state, real(state, batch, key)[1])
        if fault == "half_batch":  # the mean over the first half of the rows
            return lambda state, batch, key: real(
                state, {k: v[: v.shape[0] // 2] for k, v in batch.items()}, key)
        return real

    monkeypatch.setattr(step_mod, "make_jitted_train_step", broken)
    return train.run(tiny.spec(workload), 7, 0.5, False, time.perf_counter(), tiny.DEVICE)


def _separated(broken: dict, sound: dict) -> bool:
    return any(broken[k]["value"] >= 3 * sound[k]["value"] for k in sound)


@pytest.mark.parametrize("workload,fault", TRAIN_FAULTS)
def test_train_fault_is_not_correct(workload, fault, monkeypatch):
    sound = _train(workload, monkeypatch)
    res = _train(workload, monkeypatch, fault)
    assert not res["correct"], res["checks"]
    assert _separated(res["checks"], sound["checks"]), (res["checks"], sound["checks"])


def _serve(workload, monkeypatch, fault=None):
    from repro.serve.engine import ServeEngine

    monkeypatch.undo()
    orig = ServeEngine.decode_step

    def decode_step(self):
        real = orig(self)
        if fault != "token_altered":
            return real

        def altered(*args):  # every decoded token moved to its neighbour id
            nxt, cache = real(*args)
            return (nxt + 1) % self.model.cfg.vocab_size, cache
        return altered

    monkeypatch.setattr(ServeEngine, "decode_step", decode_step)
    return serve.run(tiny.spec(workload), 7, 2.0, False, time.perf_counter(), tiny.DEVICE)


@pytest.mark.parametrize("workload", SERVE_CELLS)
def test_serve_token_altered_is_not_correct(workload, monkeypatch):
    sound = _serve(workload, monkeypatch)
    res = _serve(workload, monkeypatch, "token_altered")
    assert not res["correct"], res["checks"]
    gap = "served_logit_gap"
    assert res["checks"][gap]["value"] >= 3 * sound["checks"][gap]["value"]
