import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import: jax locks the device count on first init.
"""Multi-pod dry-run: lower + compile every (architecture x input-shape)
step on the production mesh, prove it partitions, and extract the roofline
terms from the compiled artifact.

  python -m repro.launch.dryrun --arch yi-6b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all --out results/dryrun

Per pair this prints (and optionally JSON-dumps):
  * compiled.memory_analysis()  — proves the step fits per-device HBM
  * compiled.cost_analysis()    — per-device FLOPs / bytes
  * parsed collective wire bytes (roofline's collective term)
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from .. import configs
from ..configs.inputs import input_specs
from ..core.qsdp import QSDPConfig
from ..models.config import SHAPES
from ..models.decode import DecodeModel, make_decode_spec
from ..models.transformer import Model
from ..optim import AdamWConfig, make_adamw
from ..roofline import HW_V5E, collective_bytes_from_hlo, roofline
from ..train.step import build_train_step, state_pspecs
from .mesh import make_mesh_spec, make_production_mesh

# decode shapes are skipped for archs where they do not apply; none of the
# ten assigned archs skip anything (DESIGN.md §5): dense archs run long_500k
# via their sliding-window cache, SSM/hybrid natively.
DEFAULT_QSDP = dict(weight_bits=8, grad_bits=8, bucket_size=1024)


def build_step(arch: str, shape_name: str, multi_pod: bool, qsdp: QSDPConfig,
               n_micro: int | None = None):
    """Returns (fn, arg_structs) ready for jax.jit(fn).lower(*arg_structs)."""
    ms = make_mesh_spec(multi_pod=multi_pod)
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = configs.get_config(arch)
    shape = SHAPES[shape_name]
    model = Model(cfg, ms, qsdp)
    kind, structs, specs = input_specs(model, shape)

    if kind == "train":
        if n_micro is None:
            n_micro = max(1, shape.global_batch // ms.fsdp_size)  # 1-row microbatches
        opt = make_adamw(AdamWConfig())
        step = build_train_step(model, opt, n_micro=n_micro)
        sspec = state_pspecs(model)
        params_struct = {
            name: jax.ShapeDtypeStruct(spec.rest_shape(ms), jnp.float32)
            for name, spec in model.specs.items()
        }
        from ..optim import OptState
        from ..train.step import TrainState
        state_struct = TrainState(
            params=params_struct,
            opt=OptState(step=jax.ShapeDtypeStruct((), jnp.int32),
                         mu=params_struct, nu=params_struct),
        )
        batch_struct, key_struct = structs
        batch_spec, key_spec = specs
        fn = shard_map(step, mesh=mesh,
                           in_specs=(sspec, batch_spec, key_spec),
                           out_specs=(sspec, {"loss": P(), "grad_norm": P(), "step": P()}),
                           check_vma=False)
        return fn, (state_struct, batch_struct, key_struct), mesh, model

    dspec = make_decode_spec(model, shape)
    dm = DecodeModel(model, dspec)
    pspecs = model.param_pspecs()
    params_struct = {
        name: jax.ShapeDtypeStruct(spec.rest_shape(ms), jnp.float32)
        for name, spec in model.specs.items()
    }
    bax = ms.fsdp_axes if dspec.batch_sharded else None

    if kind == "prefill":
        batch_struct, key_struct = structs
        batch_spec, key_spec = specs
        _, cache_specs = dm.cache_struct()
        fn = shard_map(dm.prefill_fn, mesh=mesh,
                           in_specs=(pspecs, batch_spec, key_spec),
                           out_specs=(P(bax), cache_specs),
                           check_vma=False)
        return fn, (params_struct, batch_struct, key_struct), mesh, model

    # decode
    cache_structs, tok, pos, key_struct = structs
    cache_specs, tok_spec, pos_spec, key_spec = specs
    fn = shard_map(dm.decode_fn, mesh=mesh,
                       in_specs=(pspecs, cache_specs, tok_spec, pos_spec, key_spec),
                       out_specs=(tok_spec, cache_specs),
                       check_vma=False)
    return fn, (params_struct, cache_structs, tok, pos, key_struct), mesh, model


def run_one(arch: str, shape_name: str, multi_pod: bool = False,
            qsdp: QSDPConfig | None = None, verbose: bool = True,
            n_micro: int | None = None, hlo_dir: str | None = None,
            tag: str = "") -> dict:
    qsdp = qsdp or QSDPConfig(**DEFAULT_QSDP)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    n_chips = 512 if multi_pod else 256
    t0 = time.time()
    fn, arg_structs, mesh, model = build_step(arch, shape_name, multi_pod, qsdp,
                                              n_micro=n_micro)
    # donate the mutable state (TrainState / decode cache) so XLA may alias
    # buffers in place — matches how the real launchers jit these steps.
    donate = (0,) if SHAPES[shape_name].kind == "train" else (
        (1,) if SHAPES[shape_name].kind == "decode" else ())
    lowered = jax.jit(fn, donate_argnums=donate).lower(*arg_structs)
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    cost = compiled.cost_analysis() or {}
    try:
        mem = compiled.memory_analysis()
        peak = getattr(mem, "temp_size_in_bytes", None)
        arg_b = getattr(mem, "argument_size_in_bytes", None)
        out_b = getattr(mem, "output_size_in_bytes", None)
    except Exception:
        mem, peak, arg_b, out_b = None, None, None, None
    hlo = compiled.as_text()
    if hlo_dir:
        import gzip
        os.makedirs(hlo_dir, exist_ok=True)
        name = f"{tag + '_' if tag else ''}{arch}_{shape_name}_{mesh_name}.hlo.gz"
        with gzip.open(os.path.join(hlo_dir, name), "wt") as f:
            f.write(hlo)

    shape = SHAPES[shape_name]
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        mf = 6.0 * model.cfg.n_active_params() * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        mf = 2.0 * model.cfg.n_active_params() * tokens
    else:  # decode: one token per sequence
        tokens = shape.global_batch
        mf = 2.0 * model.cfg.n_active_params() * tokens

    rep = roofline(arch, shape_name, mesh_name, cost, hlo, n_chips, mf,
                   HW_V5E, peak_memory=peak)
    result = rep.to_dict()
    result.update(
        ok=True, t_lower_s=t_lower, t_compile_s=t_compile,
        memory=dict(temp=peak, args=arg_b, out=out_b),
        qsdp=dict(w=qsdp.weight_bits if qsdp.quantize_weights else "fp32",
                  g=qsdp.grad_bits if qsdp.quantize_grads else "bf16",
                  hierarchical=qsdp.hierarchical),
    )
    if verbose:
        print(rep.summary())
        print(f"  lower {t_lower:.1f}s compile {t_compile:.1f}s  "
              f"mem(temp)={_fmt(peak)} args={_fmt(arg_b)}  "
              f"coll={_fmt(result['collective_bytes'])} "
              f"({result['collectives']['counts']})")
    return result


def _fmt(b):
    if b is None:
        return "n/a"
    for u in ("B", "KB", "MB", "GB", "TB"):
        if b < 1024:
            return f"{b:.1f}{u}"
        b /= 1024
    return f"{b:.1f}PB"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--baseline-fsdp", action="store_true",
                    help="lower the unquantized FSDP baseline instead of QSDP")
    ap.add_argument("--hierarchical", action="store_true")
    ap.add_argument("--bits", type=int, default=8)
    ap.add_argument("--out", type=str, default=None)
    args = ap.parse_args()

    if args.baseline_fsdp:
        qsdp = QSDPConfig.baseline()
    else:
        qsdp = QSDPConfig(weight_bits=args.bits, grad_bits=args.bits,
                          hierarchical=args.hierarchical)

    archs = configs.ASSIGNED if args.all else [args.arch]
    shapes = list(SHAPES) if args.all or args.shape is None else [args.shape]
    meshes = [False, True] if (args.both_meshes or args.all) else [args.multi_pod]

    results = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch} x {shape} x {'2x16x16' if mp else '16x16'}"
                try:
                    results.append(run_one(arch, shape, mp, qsdp))
                except Exception as e:
                    print(f"FAIL {tag}: {e}")
                    traceback.print_exc()
                    results.append(dict(arch=arch, shape=shape,
                                        mesh="2x16x16" if mp else "16x16",
                                        ok=False, error=str(e)))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print(f"wrote {args.out}")
    n_ok = sum(r.get("ok") for r in results)
    print(f"{n_ok}/{len(results)} pairs lowered+compiled OK")
    return 0 if n_ok == len(results) else 1


if __name__ == "__main__":
    raise SystemExit(main())
