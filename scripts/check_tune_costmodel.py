"""Cost-model conformance on REAL multi-device meshes (8 emulated CPU
devices).  Invoked by tests/test_distributed.py.

``repro.tune.cost_model.predict_hlo_gather_counts`` claims to predict the
all-gather launch count the compiled HLO shows for one gather of a layer
group.  The deployment-plan autotuner ranks candidates with it, so pin the
prediction against ``roofline.hlo_analyzer`` counts of actually-compiled
programs:

  1. (2,4) mesh, full forward: the per-layer MARGINAL all-gather count
     (stack 4 vs stack 2) equals the prediction for per-tensor (23),
     coalesced (1), and both threshold policies (veto -> 23, accept -> 1).
  2. (2,4) mesh, mixed per-layer policy: a threshold between the embed
     buffer and the layers buffer coalesces the small group while the big
     one falls back to per-tensor — single-gather compiles show 1 vs 23.
  3. (2,2,2) pod mesh, hierarchical engine gathers: per-tensor quantized
     = 3 launches per level (6), coalesced = 1 per level (2).

Exit code 0 + 'ALL-OK' on success.
"""
from _mesh_common import check, finish, force_host_devices, mesh_and_spec

force_host_devices(8)

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.core.qsdp import MeshSpec, QSDPConfig
from repro.launch.mesh import make_mesh
from repro.models.config import ModelConfig
from repro.models.transformer import Model
from repro.roofline.hlo_analyzer import analyze_hlo
from repro.tune.cost_model import layer_groups, predict_hlo_gather_counts

mesh24, ms24 = mesh_and_spec((2, 4))
mcfg = ModelConfig(name="t", arch_type="dense", n_layers=2, d_model=128,
                   vocab_size=256, n_heads=8, n_kv_heads=4, head_dim=16,
                   d_ff=256)


def fwd_ag_counts(qcfg, n_layers):
    """All-gather count of the compiled forward at a given stack depth."""
    c = dataclasses.replace(mcfg, n_layers=n_layers)
    model = Model(c, ms24, qcfg)
    params = model.init_params(jax.random.PRNGKey(30))

    @partial(shard_map, mesh=mesh24,
             in_specs=(model.param_pspecs(),
                       {"tokens": P(("data",)), "labels": P(("data",))}, P()),
             out_specs=P(), check_vma=False)
    def f(p, b, k):
        return jax.lax.pmean(model.loss_fn(p, b, k), ("data", "model"))

    tokens = jnp.zeros((4, 16), jnp.int32)
    batch = {"tokens": tokens, "labels": tokens}
    hlo = jax.jit(f).lower(params, batch,
                           jax.random.PRNGKey(31)).compile().as_text()
    return analyze_hlo(hlo)["collectives"]["counts"]["all-gather"]


def marginal(qcfg):
    return (fwd_ag_counts(qcfg, 4) - fwd_ag_counts(qcfg, 2)) / 2


def single_gather_counts(model, name, qkey=40):
    """All-gather count of ONE compiled engine.gather of `name`."""
    params = model.init_params(jax.random.PRNGKey(qkey))
    pspec = model.param_pspecs()[name]
    eng = model.engine

    @partial(shard_map, mesh=make_mesh(model.ms.shape, model.ms.axes),
             in_specs=(pspec, P()), out_specs=P(), check_vma=False)
    def f(w, k):
        full = eng.gather(name, w, k[0])
        return jax.lax.psum(jnp.sum(full.astype(jnp.float32)), model.ms.axes)

    hlo = jax.jit(f).lower(params[name],
                           jax.random.PRNGKey(41)[None]).compile().as_text()
    return analyze_hlo(hlo)["collectives"]["counts"]["all-gather"]


# ---------------------------------------------------------------------------
# 1. (2,4) forward marginals vs predictions
# ---------------------------------------------------------------------------

probe = Model(mcfg, ms24, QSDPConfig(min_quant_size=256, coalesce=True)).engine
layer_names = [n for n in sorted(probe.specs) if n.startswith("layers/")]
buf_layers = probe.layer_wire_bytes(tuple(layer_names))
buf_embed = probe.layer_wire_bytes(("embed",))
assert buf_embed < buf_layers, (buf_embed, buf_layers)

for tag, qkw, forced in (
    ("per-tensor", dict(coalesce=False), False),
    ("coalesced", dict(coalesce=True), True),
    ("threshold-veto", dict(coalesce=True, coalesce_max_bytes=0), None),
    ("threshold-accept",
     dict(coalesce=True, coalesce_max_bytes=buf_layers), None),
):
    qcfg = QSDPConfig(min_quant_size=256, **qkw)
    eng = Model(mcfg, ms24, qcfg).engine
    pred = predict_hlo_gather_counts(eng, layer_names, coalesced=forced)
    got = marginal(qcfg)
    check(f"fwd-marginal-{tag}", got == pred, f"hlo={got} predicted={pred}")

# ---------------------------------------------------------------------------
# 2. (2,4) mixed per-layer policy under one threshold
# ---------------------------------------------------------------------------

mid = (buf_embed + buf_layers) // 2
q_mid = QSDPConfig(min_quant_size=256, coalesce=True, coalesce_max_bytes=mid)
m_mid = Model(mcfg, ms24, q_mid)
check("policy-embed-coalesces", m_mid.engine.layer_coalesced(("embed",)))
check("policy-layers-fall-back",
      not m_mid.engine.layer_coalesced(tuple(layer_names)))
got_embed = single_gather_counts(m_mid, "embed")
pred_embed = predict_hlo_gather_counts(m_mid.engine, ["embed"])
check("single-gather-embed-coalesced", got_embed == pred_embed == 1,
      f"hlo={got_embed} predicted={pred_embed}")
got_marg = marginal(q_mid)
pred_marg = predict_hlo_gather_counts(m_mid.engine, layer_names)
check("fwd-marginal-mixed-policy", got_marg == pred_marg == 23,
      f"hlo={got_marg} predicted={pred_marg}")
# and the small fp singleton is invisible either way (1 launch)
got_fn = single_gather_counts(m_mid, "final_norm")
pred_fn = predict_hlo_gather_counts(m_mid.engine, ["final_norm"])
check("single-gather-final-norm", got_fn == pred_fn == 1,
      f"hlo={got_fn} predicted={pred_fn}")

# ---------------------------------------------------------------------------
# 3. (2,2,2) pod mesh: hierarchical gathers
# ---------------------------------------------------------------------------

ms_pod = MeshSpec(axes=("pod", "data", "model"), shape=(2, 2, 2))
for tag, qkw, pred_want in (
    ("hier-per-tensor", dict(coalesce=False, hierarchical=True), 6),
    ("hier-coalesced", dict(coalesce=True, hierarchical=True), 2),
):
    model = Model(mcfg, ms_pod, QSDPConfig(min_quant_size=256, **qkw))
    pred = predict_hlo_gather_counts(model.engine, ["embed"])
    got = single_gather_counts(model, "embed")
    check(f"single-gather-{tag}", got == pred == pred_want,
          f"hlo={got} predicted={pred} want={pred_want}")

# sanity: the groups the autotuner iterates exist and cover the model
groups = {g for g, _, _ in layer_groups(probe)}
check("layer-groups-cover", {"layers", "embed", "final_norm"} <= groups,
      str(sorted(groups)))

finish()
