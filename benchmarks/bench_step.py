"""Benchmark: end-to-end train-step cost of the wire formats.

Compares, on an emulated (4 data x 2 model) 8-device CPU mesh (Pallas
kernels in interpret mode — the *structure* of the compiled program is what
matters here, the absolute ms are CPU numbers):

  baseline-fsdp            fp32 weights / bf16 grads, per-tensor launches
  qsdp                     W8G8, per-tensor launches (3 per quantized tensor)
  qsdp-coalesced           W8G8, ONE u8 launch per layer gather / RS
  qsdp-coalesced-prefetch  + double-buffered layer prefetch pipeline
  qsdp-autoplan            W8G8 under the repro.tune cost-model policy:
                           coalesce only layers whose gathered wire buffer
                           stays under coalesce_max_bytes — on this mesh
                           that falls back to per-tensor everywhere (the
                           coalesced small-scale regression fix)

For each variant this measures
  * per-step wall ms (median over --steps timed steps after a warmup),
  * HLO collective-launch counts (trip-count-aware, per kind and per
    operand dtype, via roofline.hlo_analyzer),
  * HLO collective wire bytes + the engine's analytic per-step wire bytes,
  * the analytic per-layer gather launch count (3 x n_params -> 1),
  * train-state bytes (total + per-device) and checkpoint payload bytes,
    so BENCH_step.json tracks the quantized-state memory win,

and writes everything to BENCH_step.json (uploaded as a CI artifact by the
workflow, so the perf trajectory accumulates across commits).

``--quantized-state`` adds the qsdp-quantized-state row: the coalesced
schedule with the train state resting in packed wire-code form
(QuantizedParam masters + 8-bit Adam moments, ckpt format v2).

Run:  PYTHONPATH=src python benchmarks/bench_step.py --smoke --quantized-state
"""
import os

if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    # 8 emulated devices when the backend is the CPU; appended, so a
    # caller's own XLA_FLAGS survive, and ignored by a TPU backend.
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")
# ^ MUST precede every other import: jax locks the device count on first init.

import argparse
import dataclasses
import json
import tempfile
import time

try:
    from . import bench_schema
except ImportError:  # run as a script: sys.path[0] is benchmarks/
    import bench_schema

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.qsdp import MeshSpec, QSDPConfig, layer_gather_launches, step_comm_bytes
from repro.tune.cost_model import CPU_SMOKE, plan_layer_policies
from repro.data import SyntheticLM
from repro.launch.mesh import make_mesh
from repro.models.config import ModelConfig
from repro.models.transformer import Model
from repro.optim import AdamWConfig, make_adamw
from repro.roofline.hlo_analyzer import analyze_hlo
from repro.train.checkpoint import checkpoint_payload_bytes, save_checkpoint
from repro.train.step import (init_train_state, make_jitted_train_step,
                              quantize_train_state)


def _round_floats(obj, ndigits=4):
    """Round every float in the output tree (stable artifact diffs)."""
    if isinstance(obj, float):
        return round(obj, ndigits)
    if isinstance(obj, dict):
        return {k: _round_floats(v, ndigits) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_round_floats(v, ndigits) for v in obj]
    return obj


def autoplan_config(mcfg, ms) -> tuple[QSDPConfig, int]:
    """The deployment-plan policy for this bench's mesh: per-layer coalesce
    decisions from the repro.tune cost model (cpu-smoke preset), expressed
    as the coalesce_max_bytes threshold.  On the tiny CPU mesh every layer
    buffer exceeds the crossover, so the policy falls back to per-tensor
    gathers — the coalesced small-scale regression fix, bit-exact by
    construction (both paths draw identical per-tensor quantization keys)."""
    probe = Model(mcfg, ms,
                  QSDPConfig(coalesce=True, min_quant_size=256)).engine
    _, thresh = plan_layer_policies(probe, CPU_SMOKE)
    return QSDPConfig(coalesce=True, coalesce_max_bytes=thresh), thresh


def variants(mcfg, ms, quantized_state=False):
    v = {
        "baseline-fsdp": QSDPConfig.baseline(),
        "qsdp": QSDPConfig(coalesce=False),
        "qsdp-coalesced": QSDPConfig(coalesce=True),
        "qsdp-coalesced-prefetch": QSDPConfig(coalesce=True, prefetch=True),
        "qsdp-autoplan": autoplan_config(mcfg, ms)[0],
    }
    if quantized_state:
        # train state rests as packed wire codes: QuantizedParam masters
        # + 8-bit Adam moments (checkpoint format v2)
        v["qsdp-quantized-state"] = QSDPConfig(coalesce=True)
    return v


def state_and_ckpt_bytes(state, n_devices):
    """Exact train-state bytes (device arrays) + checkpoint payload bytes."""
    total = sum(l.nbytes for l in jax.tree.leaves(state))
    with tempfile.TemporaryDirectory() as td:
        save_checkpoint(td, state)
        ckpt = sum(checkpoint_payload_bytes(td).values())
    return {"train_state_bytes": int(total),
            "train_state_bytes_per_device": int(total) // n_devices,
            "ckpt_payload_bytes": int(ckpt)}


def bench_variant(name, qcfg, mcfg, mesh, ms, batch, n_micro, steps):
    qcfg = dataclasses.replace(qcfg, min_quant_size=256)
    quantized_state = name == "qsdp-quantized-state"
    model = Model(mcfg, ms, qcfg)
    opt = make_adamw(AdamWConfig(lr=1e-3,
                                 moment_bits=8 if quantized_state else None))
    state = init_train_state(model, opt, jax.random.PRNGKey(0))
    if quantized_state:
        state = quantize_train_state(state, model, jax.random.PRNGKey(1))
    step = make_jitted_train_step(model, opt, mesh, n_micro=n_micro,
                                  quantized_state=quantized_state)

    key = jax.random.PRNGKey(7)
    with mesh:
        t0 = time.perf_counter()
        lowered = step.lower(state, batch, key)
        compiled = lowered.compile()
        compile_s = time.perf_counter() - t0
        hlo = analyze_hlo(compiled.as_text())

        state, metrics = step(state, batch, key)  # warmup (donated state)
        float(metrics["loss"])
        times = []
        for i in range(steps):
            t0 = time.perf_counter()
            state, metrics = step(state, batch, jax.random.fold_in(key, i))
            float(metrics["loss"])  # forces completion
            times.append(1e3 * (time.perf_counter() - t0))

    layer_names = [n for n in model.specs if n.startswith("layers/")]
    comm = step_comm_bytes(model.engine, gathers_per_param=2 * n_micro,
                           reduces_per_param=n_micro)
    counts = hlo["collectives"]["counts"]
    mem = state_and_ckpt_bytes(state, len(mesh.devices.flat))
    return {
        **mem,
        "compile_s": float(compile_s),
        "step_ms_median": float(np.median(times)),
        "step_ms_all": [float(t) for t in times],
        "loss_final": float(metrics["loss"]),
        "layer_gather_launches_analytic": layer_gather_launches(
            model.engine, layer_names),
        "wire_bytes_analytic_per_step": comm,
        "hlo_collective_bytes": hlo["collectives"]["total"],
        "hlo_collective_launches": counts,
        "hlo_launches_by_dtype": hlo["collectives"]["counts_by_dtype"],
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny config for CI (fast compile, 3 timed steps)")
    ap.add_argument("--quantized-state", action="store_true",
                    help="add the qsdp-quantized-state row (packed masters "
                         "+ 8-bit moments)")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--out", default="BENCH_step.json")
    args = ap.parse_args(argv)

    if args.smoke:
        dims = dict(n_layers=2, d_model=128, d_ff=256, seq=32, batch=8, micro=1)
        steps = args.steps or 3
    else:
        dims = dict(n_layers=4, d_model=256, d_ff=512, seq=64, batch=8, micro=2)
        steps = args.steps or 10

    mesh = make_mesh((4, 2), ("data", "model"))
    ms = MeshSpec(axes=("data", "model"), shape=(4, 2))
    mcfg = ModelConfig(name="bench", arch_type="dense", n_layers=dims["n_layers"],
                       d_model=dims["d_model"], vocab_size=512, n_heads=8,
                       n_kv_heads=4, head_dim=dims["d_model"] // 8,
                       d_ff=dims["d_ff"])
    data = SyntheticLM(vocab_size=512, seq_len=dims["seq"],
                       global_batch=dims["batch"], seed=1)
    tokens, labels = data.sample(0)
    batch = {"tokens": tokens, "labels": labels}

    out = {"config": {**dims, "mesh": "4x2", "steps": steps,
                      "smoke": bool(args.smoke),
                      "autoplan_coalesce_max_bytes":
                          autoplan_config(mcfg, ms)[1]},
           "variants": {}}
    for name, qcfg in variants(mcfg, ms, args.quantized_state).items():
        r = bench_variant(name, qcfg, mcfg, mesh, ms, batch, dims["micro"], steps)
        out["variants"][name] = r
        c = r["hlo_collective_launches"]
        print(f"{name:24s} step {r['step_ms_median']:8.1f}ms  "
              f"launches/layer-gather {r['layer_gather_launches_analytic']:2d}  "
              f"HLO ag={c['all-gather']} a2a={c['all-to-all']} "
              f"rs={c['reduce-scatter']} ar={c['all-reduce']}  "
              f"wire {r['wire_bytes_analytic_per_step']['total'] / 2**20:.2f}MB  "
              f"state {r['train_state_bytes'] / 2**20:.2f}MB "
              f"ckpt {r['ckpt_payload_bytes'] / 2**20:.2f}MB")

    base = out["variants"]["qsdp"]
    co = out["variants"]["qsdp-coalesced"]
    ap_row = out["variants"]["qsdp-autoplan"]
    out["summary"] = {
        "ag_launch_reduction": (base["hlo_collective_launches"]["all-gather"]
                                / max(co["hlo_collective_launches"]["all-gather"], 1)),
        "wire_bytes_ratio_co_vs_per_tensor": (
            co["wire_bytes_analytic_per_step"]["total"]
            / base["wire_bytes_analytic_per_step"]["total"]),
        "autoplan_vs_qsdp_step_ratio": (ap_row["step_ms_median"]
                                        / base["step_ms_median"]),
        "autoplan_vs_coalesced_step_ratio": (ap_row["step_ms_median"]
                                             / co["step_ms_median"]),
    }
    print(f"autoplan: {out['summary']['autoplan_vs_qsdp_step_ratio']:.3f}x "
          f"plain qsdp, {out['summary']['autoplan_vs_coalesced_step_ratio']:.3f}x "
          f"always-coalesced (threshold "
          f"{out['config']['autoplan_coalesce_max_bytes']} B)")
    if "qsdp-quantized-state" in out["variants"]:
        qs = out["variants"]["qsdp-quantized-state"]
        out["summary"]["state_bytes_ratio_qstate_vs_f32"] = (
            qs["train_state_bytes"] / co["train_state_bytes"])
        out["summary"]["ckpt_bytes_ratio_qstate_vs_f32"] = (
            qs["ckpt_payload_bytes"] / co["ckpt_payload_bytes"])
        print(f"quantized state: {out['summary']['state_bytes_ratio_qstate_vs_f32']:.3f}x "
              f"train-state bytes, {out['summary']['ckpt_bytes_ratio_qstate_vs_f32']:.3f}x "
              f"checkpoint bytes vs f32")
    print(f"coalescing: {out['summary']['ag_launch_reduction']:.1f}x fewer "
          f"all-gather launches at {out['summary']['wire_bytes_ratio_co_vs_per_tensor']:.3f}x "
          f"the wire bytes")

    doc = _round_floats(bench_schema.stamp(out))
    bench_schema.validate_bench_step(doc)
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
