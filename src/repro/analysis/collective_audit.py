"""Collective audit: compiled HLO vs the cost model's promises (QC301-303).

Generalizes the PR 2 "23 -> 1" HLO launch assertion into a reusable
checker: compile the forward at two stack depths on the requested mesh,
take the per-layer MARGINAL collective counts / wire bytes from
`roofline.hlo_analyzer`, and diff them against
`tune.cost_model.predict_hlo_gather_counts` plus the engine's analytic
wire-byte budget.  On a (1,1) mesh every collective degenerates to group
size 1 and is compiled away, so both sides must read zero — any surviving
launch is itself a finding.  With a DeploymentPlan the engine is built
from the plan's qsdp section and the plan's recorded per-group policies
are cross-checked against that engine (drift = a stale plan).

The count/byte differs are pure functions of analyzer output, so seeded
regression tests drive them with hand-written HLO text.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

from .findings import Finding

# marginal-byte slack: XLA may pad buffers to tile boundaries
WIRE_SLACK_FRAC = 0.10
WIRE_SLACK_BYTES = 4096


def diff_gather_counts(marginal_counts: dict, predicted: int,
                       tag: str) -> list[Finding]:
    """QC301 on any divergence between measured marginal collective counts
    and the cost model's prediction.  `marginal_counts` is the per-layer
    marginal of ``analyze_hlo(...)['collectives']['counts']``."""
    out = []
    got = marginal_counts.get("all-gather", 0)
    if got != predicted:
        out.append(Finding(
            "QC301", f"{tag}::all-gather",
            f"compiled marginal all-gather count {got} != cost-model "
            f"prediction {predicted}"))
    for kind, n in sorted(marginal_counts.items()):
        if kind not in ("all-gather", "reduce-scatter", "all-reduce") and n:
            out.append(Finding(
                "QC301", f"{tag}::unexpected::{kind}",
                f"{n} unexplained '{kind}' launch(es) in the forward "
                f"marginal (only gathers belong on this path)"))
    return out


def diff_wire_bytes(marginal_wire: float, budget: float,
                    tag: str) -> list[Finding]:
    """QC302 when marginal on-the-wire bytes exceed the analytic budget."""
    limit = budget * (1 + WIRE_SLACK_FRAC) + WIRE_SLACK_BYTES
    if marginal_wire > limit:
        return [Finding(
            "QC302", f"{tag}::all-gather-bytes",
            f"compiled marginal all-gather wire bytes {marginal_wire:.0f} "
            f"exceed analytic budget {budget:.0f} (+slack {limit:.0f})")]
    return []


def check_plan_drift(plan, engine, tag: str) -> list[Finding]:
    """QC303: the plan's recorded per-group policy/bytes must match the
    engine its own qsdp section builds."""
    out = []
    for lp in plan.layers:
        names = tuple(sorted(n for n in engine.specs
                             if n.startswith(f"{lp.group}/")))
        if not names:
            if lp.group in engine.specs:
                names = (lp.group,)
            else:
                out.append(Finding(
                    "QC303", f"{tag}::{lp.group}::missing",
                    f"plan records group '{lp.group}' absent from the "
                    f"engine's spec tree"))
                continue
        got_co = engine.layer_coalesced(names)
        got_bytes = engine.layer_wire_bytes(names)
        if got_co != lp.coalesce:
            out.append(Finding(
                "QC303", f"{tag}::{lp.group}::coalesce",
                f"plan says coalesce={lp.coalesce} for '{lp.group}' but the "
                f"engine built from the plan decides {got_co}"))
        if got_bytes != lp.wire_buffer_bytes:
            out.append(Finding(
                "QC303", f"{tag}::{lp.group}::wire-bytes",
                f"plan records {lp.wire_buffer_bytes} wire bytes for "
                f"'{lp.group}', engine computes {got_bytes}"))
    return out


# ---------------------------------------------------------------------------
# Compilation harness
# ---------------------------------------------------------------------------


def _fwd_collectives(mcfg, ms, qcfg, n_layers: int, mesh) -> dict:
    """Collectives section of the compiled forward at a stack depth."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    from ..models.transformer import Model
    from ..roofline.hlo_analyzer import analyze_hlo

    c = dataclasses.replace(mcfg, n_layers=n_layers)
    model = Model(c, ms, qcfg)
    params = model.init_params(jax.random.PRNGKey(30))

    @partial(shard_map, mesh=mesh,
             in_specs=(model.param_pspecs(),
                       {"tokens": P(ms.fsdp_axes), "labels": P(ms.fsdp_axes)},
                       P()),
             out_specs=P(), check_vma=False)
    def f(p, b, k):
        return jax.lax.pmean(model.loss_fn(p, b, k), ms.axes)

    b = max(2, ms.fsdp_size)
    tokens = jnp.zeros((b, 16), jnp.int32)
    batch = {"tokens": tokens, "labels": tokens}
    hlo = jax.jit(f).lower(params, batch,
                           jax.random.PRNGKey(31)).compile().as_text()
    return analyze_hlo(hlo)["collectives"]


def audit(arch: str = "gpt-125m", mesh_shape=(1, 1),
          plan_path: Optional[str] = None, smoke: bool = True,
          report: Optional[dict] = None) -> list[Finding]:
    import jax

    from .. import configs
    from ..core.qsdp import MeshSpec, QSDPConfig
    from ..launch.mesh import make_mesh
    from ..models.transformer import Model
    from ..tune.cost_model import predict_hlo_gather_counts

    ms = MeshSpec(axes=("data", "model"), shape=tuple(mesh_shape))
    mesh = make_mesh(ms.shape, ms.axes)
    mcfg = configs.get_smoke(arch) if smoke else configs.get_config(arch)
    tag = f"{mcfg.name}@{ms.shape[0]}x{ms.shape[1]}"

    findings: list[Finding] = []
    if plan_path:
        from ..tune.plan import DeploymentPlan
        plan = DeploymentPlan.load(plan_path)
        plan.validate_mesh(ms.axes, ms.shape)
        qcfg = plan.to_qsdp_config(QSDPConfig(min_quant_size=256))
        engine = Model(mcfg, ms, qcfg).engine
        findings.extend(check_plan_drift(plan, engine, tag))
    else:
        qcfg = QSDPConfig(min_quant_size=256, coalesce=True)
        engine = Model(mcfg, ms, qcfg).engine

    layer_names = sorted(n for n in engine.specs if n.startswith("layers/"))
    predicted = predict_hlo_gather_counts(engine, layer_names)

    lo, hi = 2, 4
    c_lo = _fwd_collectives(mcfg, ms, qcfg, lo, mesh)
    c_hi = _fwd_collectives(mcfg, ms, qcfg, hi, mesh)
    marg_counts = {
        k: (c_hi["counts"].get(k, 0) - c_lo["counts"].get(k, 0)) / (hi - lo)
        for k in set(c_hi["counts"]) | set(c_lo["counts"])
    }
    marg_wire = (c_hi.get("all-gather", 0) - c_lo.get("all-gather", 0)) \
        / (hi - lo)

    findings.extend(diff_gather_counts(marg_counts, predicted, tag))
    # analytic budget: the gathered wire buffer crosses the ring once
    # -> B * (p-1)/p bytes on the wire per gather
    p = ms.fsdp_size
    buf = engine.layer_wire_bytes(tuple(layer_names))
    budget = buf * (p - 1) / p if p > 1 else 0.0
    findings.extend(diff_wire_bytes(marg_wire, budget, tag))

    if report is not None:
        report[tag] = {
            "predicted_marginal_all_gather": predicted,
            "marginal_counts": {k: v for k, v in sorted(marg_counts.items())
                                if v},
            "marginal_all_gather_wire_bytes": marg_wire,
            "analytic_wire_budget_bytes": budget,
            "layer_wire_buffer_bytes": buf,
        }
    return findings


def run(arch: str = "gpt-125m", mesh_shape=(1, 1),
        plan_path: Optional[str] = None,
        report: Optional[dict] = None) -> list[Finding]:
    return audit(arch, mesh_shape, plan_path, report=report)
