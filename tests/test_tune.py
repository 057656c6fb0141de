"""repro.tune — deployment-plan autotuner tier-1 coverage.

  * DeploymentPlan save/load round-trip, versioning, mesh validation,
    QSDPConfig round-trip (unknown-field rejection)
  * the per-layer coalesce byte-threshold policy in the QSDP engine
    (the coalesced small-scale regression fix) + bit-exactness of a
    MIXED threshold policy against the per-tensor path
  * cost-model conformance: predicted HLO all-gather counts vs the
    compiled train step on the (1,1) mesh (multi-device counts are pinned
    analytically here and against real compiled HLO by
    scripts/check_tune_costmodel.py via test_distributed.py)
  * search determinism (exhaustive + simulated annealing) and candidate
    space validity
  * the emitted plan round-trips through BOTH launchers (autotune ->
    train --plan / serve --plan)
"""
import dataclasses
from functools import partial

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.core.qsdp import MeshSpec, QSDPConfig, layer_gather_launches
from repro.models.config import ModelConfig
from repro.models.transformer import Model
from repro.roofline.hlo_analyzer import analyze_hlo
from repro.tune import (
    PLAN_VERSION,
    Candidate,
    DeploymentPlan,
    HW_PRESETS,
    LayerPolicy,
    crossover_bytes,
    enumerate_space,
    exhaustive_search,
    plan_layer_policies,
    predict_hlo_gather_counts,
    predict_step_time,
    simulated_annealing,
)
from repro.tune.cost_model import CPU_SMOKE, TPU_V5E, layer_groups

MCFG = ModelConfig(name="t", arch_type="dense", n_layers=3, d_model=64,
                   vocab_size=256, n_heads=4, n_kv_heads=2, head_dim=16,
                   d_ff=128)
MS11 = MeshSpec(axes=("data", "model"), shape=(1, 1))
MS42 = MeshSpec(axes=("data", "model"), shape=(4, 2))


def _engine(ms=MS11, **qkw):
    qkw.setdefault("min_quant_size", 128)
    return Model(MCFG, ms, QSDPConfig(**qkw)).engine


def _layer_names(engine):
    return tuple(n for n in sorted(engine.specs) if n.startswith("layers/"))


# ---------------------------------------------------------------------------
# DeploymentPlan
# ---------------------------------------------------------------------------


def _mk_plan(**over):
    base = dict(
        version=PLAN_VERSION, arch="t", mesh_axes=("data", "model"),
        mesh_shape=(4, 2), hw="cpu-smoke",
        qsdp={"weight_bits": 4, "grad_bits": 8, "coalesce": True,
              "coalesce_max_bytes": 1024, "min_quant_size": 128,
              "prefetch": False},
        serve={"slots": 4, "prefill_chunk": 8, "prefill_buckets": 2},
        layers=(LayerPolicy(group="layers", coalesce=False,
                            wire_buffer_bytes=4096, launches_per_tensor=23,
                            launches_coalesced=1),),
        predicted={"step_ms": 1.23456789}, measured={},
    )
    base.update(over)
    return DeploymentPlan(**base)


def test_plan_save_load_roundtrip(tmp_path):
    p = str(tmp_path / "plan.json")
    plan = _mk_plan()
    plan.save(p)
    loaded = DeploymentPlan.load(p)
    assert loaded.version == PLAN_VERSION
    assert loaded.mesh_axes == ("data", "model")
    assert loaded.mesh_shape == (4, 2)
    assert loaded.qsdp == plan.qsdp
    assert loaded.serve == plan.serve
    assert loaded.layers == plan.layers
    # floats are rounded to 4 decimals on disk (stable artifact diffs)
    assert loaded.predicted["step_ms"] == 1.2346


def test_plan_version_mismatch(tmp_path):
    d = _mk_plan().to_dict()
    d["version"] = PLAN_VERSION + 1
    with pytest.raises(ValueError, match="regenerate"):
        DeploymentPlan.from_dict(d)


def test_plan_validate_mesh():
    plan = _mk_plan()
    plan.validate_mesh(("data", "model"), (4, 2))  # tuned mesh: fine
    with pytest.raises(ValueError, match="re-run repro.tune.autotune"):
        plan.validate_mesh(("data", "model"), (1, 1))
    with pytest.raises(ValueError):
        plan.validate_mesh(("pod", "data", "model"), (1, 4, 2))


def test_plan_to_qsdp_config():
    qsdp = _mk_plan().to_qsdp_config(QSDPConfig())
    assert qsdp.weight_bits == 4 and qsdp.grad_bits == 8
    assert qsdp.coalesce and qsdp.coalesce_max_bytes == 1024
    assert qsdp.min_quant_size == 128
    with pytest.raises(ValueError, match="unknown fields"):
        _mk_plan(qsdp={"bogus_knob": 1}).to_qsdp_config(QSDPConfig())


# ---------------------------------------------------------------------------
# Engine threshold policy (the regression fix mechanism)
# ---------------------------------------------------------------------------


def test_layer_coalesced_threshold():
    eng = _engine(MS42, coalesce=True)
    names = _layer_names(eng)
    buf = eng.layer_wire_bytes(names)
    assert buf > 0
    assert eng.layer_coalesced(names)  # no threshold = always coalesce
    at = _engine(MS42, coalesce=True, coalesce_max_bytes=buf)
    below = _engine(MS42, coalesce=True, coalesce_max_bytes=buf - 1)
    never = _engine(MS42, coalesce=True, coalesce_max_bytes=0)
    off = _engine(MS42, coalesce=False, coalesce_max_bytes=10 ** 9)
    assert at.layer_coalesced(names)
    assert not below.layer_coalesced(names)
    assert not never.layer_coalesced(names)
    assert not off.layer_coalesced(names)  # coalesce=False wins


def test_layer_gather_launches_respects_threshold():
    names = list(_layer_names(_engine()))
    per_tensor = layer_gather_launches(_engine(coalesce=False), names)
    assert per_tensor == 23  # 7 quantized x 3 + 2 fp norms
    assert layer_gather_launches(
        _engine(coalesce=True, coalesce_max_bytes=0), names) == per_tensor
    assert layer_gather_launches(
        _engine(coalesce=True, coalesce_max_bytes=10 ** 9), names) == 1


def _loss_and_grads(mesh11, qcfg):
    model = Model(MCFG, MS11, qcfg)
    params = model.init_params(jax.random.PRNGKey(1))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0, 256)
    batch = {"tokens": tokens, "labels": tokens}

    @partial(shard_map, mesh=mesh11,
             in_specs=(model.param_pspecs(),
                       {"tokens": P(("data",)), "labels": P(("data",))}, P()),
             out_specs=(P(), model.param_pspecs()), check_vma=False)
    def f(p, b, k):
        loss, g = jax.value_and_grad(model.loss_fn)(p, b, k)
        return jax.lax.pmean(loss, ("data", "model")), g

    loss, g = jax.jit(f)(params, batch, jax.random.PRNGKey(3))
    return float(loss), jax.device_get(g)


def test_mixed_threshold_policy_bitexact(mesh11):
    """A threshold that coalesces SOME groups and not others must still be
    bit-exact vs the per-tensor path (same per-tensor quantization keys)."""
    eng = _engine(coalesce=True)
    bufs = sorted(eng.layer_wire_bytes(tuple(ns))
                  for _, ns, _ in layer_groups(eng))
    mid = bufs[len(bufs) // 2]  # between the smallest and largest group
    assert bufs[0] <= mid < bufs[-1]
    l0, g0 = _loss_and_grads(mesh11, QSDPConfig(min_quant_size=128,
                                                coalesce=False))
    l1, g1 = _loss_and_grads(mesh11, QSDPConfig(
        min_quant_size=128, coalesce=True, coalesce_max_bytes=mid))
    assert l0 == l1
    for k in g0:
        assert (np.asarray(g0[k]) == np.asarray(g1[k])).all(), k


# ---------------------------------------------------------------------------
# Cost model
# ---------------------------------------------------------------------------


def test_cost_model_explains_the_regression():
    """On the tiny CPU mesh the model must veto coalescing (the headline
    bugfix); on the TPU preset it must keep it."""
    eng = _engine(MS42, coalesce=True)
    names = list(_layer_names(eng))
    assert crossover_bytes(eng, names, CPU_SMOKE) < \
        eng.layer_wire_bytes(tuple(names))
    assert crossover_bytes(eng, names, TPU_V5E) > \
        eng.layer_wire_bytes(tuple(names))
    # step-time ordering flips between the presets
    pt = _engine(MS42, coalesce=False)
    co = _engine(MS42, coalesce=True)
    assert predict_step_time(pt, CPU_SMOKE) < predict_step_time(co, CPU_SMOKE)
    assert predict_step_time(co, TPU_V5E) < predict_step_time(pt, TPU_V5E)


def test_plan_layer_policies_thresholds():
    eng = _engine(MS42, coalesce=True)
    cpu_pol, cpu_thresh = plan_layer_policies(eng, CPU_SMOKE)
    assert cpu_pol and not any(p.coalesce for p in cpu_pol)
    assert cpu_thresh is not None
    assert cpu_thresh < min(p.wire_buffer_bytes for p in cpu_pol)
    tpu_pol, tpu_thresh = plan_layer_policies(eng, TPU_V5E)
    assert all(p.coalesce for p in tpu_pol)
    assert tpu_thresh is None  # everything coalesces: no threshold needed
    # the threshold reproduces the decisions through the engine predicate
    cut = _engine(MS42, coalesce=True, coalesce_max_bytes=cpu_thresh)
    for _, ns, _ in layer_groups(cut):
        assert not cut.layer_coalesced(tuple(ns))


def test_predict_hlo_counts_analytic_multidevice():
    """Launch counts the compiled HLO will show on real multi-device meshes
    (conformance against actual compiled HLO runs in the slow subprocess
    check; these pin the closed forms)."""
    names = list(_layer_names(_engine(MS42)))
    pt = _engine(MS42, coalesce=False)
    assert predict_hlo_gather_counts(pt, names, coalesced=False) == 23
    assert predict_hlo_gather_counts(pt, names, coalesced=True) == 1
    ms_pod = MeshSpec(axes=("pod", "data", "model"), shape=(2, 2, 2))
    hier = _engine(ms_pod, coalesce=True, hierarchical=True)
    assert predict_hlo_gather_counts(hier, names, coalesced=True) == 2
    assert predict_hlo_gather_counts(hier, names, coalesced=False) == \
        3 * 7 * 2 + 2  # 3 per quantized tensor per level + 1 per fp payload


def _hlo_counts(mesh11, qcfg):
    model = Model(MCFG, MS11, qcfg)
    params = model.init_params(jax.random.PRNGKey(1))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0, 256)
    batch = {"tokens": tokens, "labels": tokens}

    @partial(shard_map, mesh=mesh11,
             in_specs=(model.param_pspecs(),
                       {"tokens": P(("data",)), "labels": P(("data",))}, P()),
             out_specs=(P(), model.param_pspecs()), check_vma=False)
    def f(p, b, k):
        loss, g = jax.value_and_grad(model.loss_fn)(p, b, k)
        return jax.lax.pmean(loss, ("data", "model")), g

    compiled = jax.jit(f).lower(params, batch, jax.random.PRNGKey(3)).compile()
    return analyze_hlo(compiled.as_text())["collectives"]["counts"], model


@pytest.mark.parametrize("qkw", [
    dict(coalesce=False),
    dict(coalesce=True),
    dict(coalesce=True, coalesce_max_bytes=2048),
], ids=["per-tensor", "coalesced", "thresholded"])
def test_hlo_conformance_trivial_mesh(mesh11, qkw):
    """(1,1) conformance: the analyzer only counts collectives with replica
    groups > 1, so every gather is invisible on the trivial mesh — and the
    predictor agrees (returns 0 for each group)."""
    counts, model = _hlo_counts(mesh11, QSDPConfig(min_quant_size=128, **qkw))
    predicted = sum(predict_hlo_gather_counts(model.engine, ns)
                    for _, ns, _ in layer_groups(model.engine))
    assert predicted == 0
    assert counts["all-gather"] == predicted
    assert counts["reduce-scatter"] == 0


# ---------------------------------------------------------------------------
# Candidate space + search
# ---------------------------------------------------------------------------


def _toy_cost(c: Candidate) -> float:
    return (1.0 * c.coalesce + 0.25 * c.prefetch + 0.01 * c.weight_bits
            + (0.001 if c.coalesce_max_bytes else 0.0))


def test_enumerate_space_valid_and_unique():
    cands = list(enumerate_space(thresholds=(None, 4096)))
    assert len(cands) == len(set(cands))
    assert all(c.valid() for c in cands)
    assert any(not c.coalesce for c in cands)
    assert any(c.coalesce and c.coalesce_max_bytes == 4096 for c in cands)
    full = list(enumerate_space(thresholds=(None,), full_space=True))
    assert len(full) > len(list(enumerate_space(thresholds=(None,))))
    assert all(c.valid() for c in full)


def test_exhaustive_search_deterministic():
    cands = list(enumerate_space(thresholds=(None, 4096), full_space=True))
    r1 = exhaustive_search(cands, _toy_cost)
    r2 = exhaustive_search(cands, _toy_cost)
    assert r1 == r2
    assert [t for t, _ in r1] == sorted(t for t, _ in r1)
    assert not r1[0][1].coalesce  # toy cost: per-tensor wins


def test_annealing_deterministic_and_finds_optimum():
    cands = list(enumerate_space(thresholds=(None, 4096), full_space=True))
    r1 = simulated_annealing(cands, _toy_cost, seed=0, iters=300)
    r2 = simulated_annealing(cands, _toy_cost, seed=0, iters=300)
    assert r1 == r2
    best = exhaustive_search(cands, _toy_cost)[0]
    assert r1[0][0] == best[0]


# ---------------------------------------------------------------------------
# End-to-end: autotune -> plan -> both launchers (acceptance round-trip)
# ---------------------------------------------------------------------------


def test_autotune_plan_roundtrips_through_launchers(tmp_path, capsys):
    from repro.launch import serve as serve_mod
    from repro.launch import train as train_mod
    from repro.tune import autotune

    out = str(tmp_path / "plan.json")
    rc = autotune.main(["--smoke", "--data-par", "1", "--model-par", "1",
                        "--measure-top", "0", "--min-quant-size", "256",
                        "--out", out, "--assert-choice", "per-tensor"])
    assert rc == 0
    plan = DeploymentPlan.load(out)
    # normalized policy: always thresholded coalesce (0 = never coalesce)
    assert plan.qsdp["coalesce"] is True
    assert plan.qsdp["coalesce_max_bytes"] == 0
    assert plan.layers and not any(lp.coalesce for lp in plan.layers)

    assert train_mod.main(["--plan", out, "--smoke", "--steps", "1",
                           "--batch", "2", "--seq", "16",
                           "--log-every", "1"]) == 0
    assert serve_mod.main(["--plan", out, "--smoke", "--batch", "2",
                           "--prompt-len", "8", "--gen", "2"]) == 0
    assert "QSDP plan" in capsys.readouterr().out
