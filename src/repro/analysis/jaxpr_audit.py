"""Jaxpr audit: walk traced programs for wire-format hazards (QJ101-103).

The audited programs are the jitted train step (f32 and quantized-state)
and the serve-side `decode_fn` / `prefill_chunk_fn` / `verify_fn` — traced
with `jax.make_jaxpr` on a (1,1) mesh, so nothing is compiled or executed.
Detection leans on two structural facts:

  * the quantizer/dequantizer entry points are jit-wrapped
    (`core.quant._quantize_jnp` / `_dequantize_jnp`,
    `kernels.ops.quantize_packed` / `dequantize_packed`), so inside any
    traced program they appear as `pjit` equations with stable names;
  * wire pack/unpack moves bytes with layout ops only (reshape / slice /
    concatenate / bitcast), so "no intervening compute" is checkable as
    reachability through a transparent-op whitelist.

Rules:
  QJ101  a dequantizer's output reaches a quantizer's input through
         transparent ops only — a redundant re-quantization round-trip
         (the SDP4Bit failure mode: extra noise draw + an extra bias term,
         invisible to shape checks)
  QJ102  a `convert_element_type` from u8 to a float dtype whose result
         reaches a collective operand through transparent ops — the wire
         was silently widened 2-4x
  QJ103  nondeterminism-hazard primitives inside programs guarded by the
         bit-identity serve invariant (decode/prefill/verify must replay
         exactly on every rank/run)
"""
from __future__ import annotations

from typing import Iterable, Optional

from .findings import Finding

QUANTIZER_NAMES = ("_quantize_jnp", "quantize_packed", "quantize_buckets")
DEQUANTIZER_NAMES = ("_dequantize_jnp", "dequantize_packed",
                     "dequantize_buckets")

# pure data-movement: values pass through unchanged (bits may be re-laid-out
# or reinterpreted, never combined)
TRANSPARENT_PRIMS = {
    "reshape", "transpose", "broadcast_in_dim", "squeeze", "expand_dims",
    "slice", "dynamic_slice", "concatenate", "rev", "pad", "copy",
    "convert_element_type", "bitcast_convert_type", "gather",
    "dynamic_update_slice",
}

COLLECTIVE_PRIMS = {
    "all_gather", "psum_scatter", "all_to_all", "ppermute", "psum",
    "reduce_scatter",
}

# primitives whose device-to-device / run-to-run determinism is not
# guaranteed on every backend (float atomics, legacy stateful RNG)
HAZARD_PRIMS = {"rng_uniform"}
HAZARD_FLOAT_PRIMS = {"scatter-add", "scatter_add", "scatter-mul",
                      "scatter_mul"}


def _subjaxprs(params: dict):
    import jax.extend.core as jcore
    ClosedJaxpr = jcore.ClosedJaxpr
    Jaxpr = jcore.Jaxpr
    for v in params.values():
        if isinstance(v, ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, Jaxpr):
            yield v
        elif isinstance(v, (list, tuple)):
            for x in v:
                if isinstance(x, ClosedJaxpr):
                    yield x.jaxpr
                elif isinstance(x, Jaxpr):
                    yield x


def iter_jaxprs(jaxpr):
    """Yield `jaxpr` and every nested sub-jaxpr (pjit / scan / while /
    cond / custom_vjp / shard_map bodies), depth-first."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for sub in _subjaxprs(eqn.params):
            yield from iter_jaxprs(sub)


def _eqn_callee(eqn) -> str:
    """The function name a call-like equation wraps ('' otherwise)."""
    name = eqn.params.get("name")
    if isinstance(name, str):
        return name
    for k in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        sub = eqn.params.get(k)
        if sub is not None and hasattr(sub, "jaxpr"):
            return getattr(sub.jaxpr, "name", "") or ""
    return ""


def _match(name: str, catalog: tuple) -> bool:
    return any(c in name for c in catalog)


def _level_findings(jaxpr, tag: str) -> list[Finding]:
    """Run all three detectors on ONE jaxpr level (dataflow within a level;
    iter_jaxprs visits every level of the program)."""
    out = []
    producer = {}  # var -> eqn
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            producer[id(v)] = eqn

    def _reaches_back(var, want: str, seen) -> Optional[str]:
        """Walk producers through transparent ops; return the matched
        callee name if `var` derives from a `want`-class call."""
        if id(var) in seen:
            return None
        seen.add(id(var))
        eqn = producer.get(id(var))
        if eqn is None:
            return None
        callee = _eqn_callee(eqn)
        if want == "dequantize" and _match(callee, DEQUANTIZER_NAMES):
            return callee
        prim = eqn.primitive.name
        if prim in TRANSPARENT_PRIMS or (prim == "pjit" and not callee):
            for iv in eqn.invars:
                if hasattr(iv, "aval"):
                    hit = _reaches_back(iv, want, seen)
                    if hit:
                        return hit
        return None

    # forward reachability through transparent ops, for QJ102
    consumers: dict[int, list] = {}
    for eqn in jaxpr.eqns:
        for iv in eqn.invars:
            if hasattr(iv, "aval"):
                consumers.setdefault(id(iv), []).append(eqn)

    def _reaches_collective(var, seen) -> Optional[str]:
        if id(var) in seen:
            return None
        seen.add(id(var))
        for eqn in consumers.get(id(var), ()):
            prim = eqn.primitive.name
            if prim in COLLECTIVE_PRIMS:
                return prim
            if prim in TRANSPARENT_PRIMS:
                for ov in eqn.outvars:
                    hit = _reaches_collective(ov, seen)
                    if hit:
                        return hit
        return None

    for eqn in jaxpr.eqns:
        callee = _eqn_callee(eqn)
        # QJ101: quantizer fed (transparently) by a dequantizer
        if _match(callee, QUANTIZER_NAMES):
            for iv in eqn.invars:
                if not hasattr(iv, "aval"):
                    continue
                hit = _reaches_back(iv, "dequantize", set())
                if hit:
                    out.append(Finding(
                        "QJ101", f"{tag}::{hit}->{callee}",
                        f"'{callee}' consumes '{hit}' output with no "
                        f"intervening compute — redundant QDQ round-trip"))
                    break
        # QJ102: u8 -> float widen that reaches a collective
        if eqn.primitive.name == "convert_element_type":
            src = eqn.invars[0].aval
            dst = eqn.outvars[0].aval
            if (str(src.dtype) == "uint8"
                    and str(dst.dtype) in ("float32", "bfloat16", "float16")):
                coll = _reaches_collective(eqn.outvars[0], set())
                if coll:
                    out.append(Finding(
                        "QJ102",
                        f"{tag}::u8->{dst.dtype}->{coll}",
                        f"u8 wire buffer widened to {dst.dtype} before "
                        f"'{coll}' — wire bytes multiplied"))
    return out


def hazard_findings(jaxpr, tag: str) -> list[Finding]:
    out = []
    for sub in iter_jaxprs(jaxpr):
        for eqn in sub.eqns:
            prim = eqn.primitive.name
            if prim in HAZARD_PRIMS:
                out.append(Finding(
                    "QJ103", f"{tag}::{prim}",
                    f"nondeterminism-hazard primitive '{prim}' inside a "
                    f"bit-identity-guarded program"))
            elif prim in HAZARD_FLOAT_PRIMS:
                if any(hasattr(ov, "aval") and "float" in str(ov.aval.dtype)
                       for ov in eqn.outvars):
                    out.append(Finding(
                        "QJ103", f"{tag}::{prim}:float",
                        f"float '{prim}' (atomic-ordering hazard on GPU "
                        f"backends) inside a bit-identity-guarded program"))
    return out


def audit_jaxpr(closed, tag: str, bit_identity: bool = False) -> list[Finding]:
    """All jaxpr-level detectors over one traced program."""
    jaxpr = closed.jaxpr if hasattr(closed, "jaxpr") else closed
    out = []
    seen_sites = set()
    for sub in iter_jaxprs(jaxpr):
        for f in _level_findings(sub, tag):
            if f.site not in seen_sites:
                seen_sites.add(f.site)
                out.append(f)
    if bit_identity:
        for f in hazard_findings(jaxpr, tag):
            if f.site not in seen_sites:
                seen_sites.add(f.site)
                out.append(f)
    return out


# ---------------------------------------------------------------------------
# Program construction (trace-only, (1,1) mesh)
# ---------------------------------------------------------------------------


def trace_train_step(arch: str = "gpt-125m", quantized_state: bool = False,
                     n_micro: int = 1):
    import jax
    import jax.numpy as jnp

    from .. import configs
    from ..core.qsdp import MeshSpec, QSDPConfig
    from ..launch.mesh import make_mesh
    from ..models.transformer import Model
    from ..optim import AdamWConfig, make_adamw
    from ..train.step import (init_train_state, make_jitted_train_step,
                              quantize_train_state)

    cfg = configs.get_smoke(arch)
    ms = MeshSpec(axes=("data", "model"), shape=(1, 1))
    mesh = make_mesh(ms.shape, ms.axes)
    model = Model(cfg, ms, QSDPConfig(min_quant_size=256, coalesce=True))
    opt = make_adamw(AdamWConfig(lr=1e-3))
    key = jax.random.PRNGKey(0)
    state = init_train_state(model, opt, key)
    if quantized_state:
        state = quantize_train_state(state, model, key)
    step = make_jitted_train_step(model, opt, mesh, n_micro=n_micro,
                                  donate=False,
                                  quantized_state=quantized_state)
    tokens = jnp.zeros((4, 16), jnp.int32)
    batch = {"tokens": tokens, "labels": tokens}
    return jax.make_jaxpr(step)(state, batch, key)


def trace_serve_programs(arch: str = "gpt-125m"):
    """{tag: ClosedJaxpr} for decode / chunked-prefill / verify on (1,1)."""
    import jax
    import jax.numpy as jnp

    from ..serve.common import build_serve_setup
    from ..serve.engine import prepare_wire_params

    setup = build_serve_setup(arch, data_par=1, model_par=1, smoke=True,
                              batch=2, prompt_len=8, gen=4,
                              draft_bits=4, draft_depth=2)
    eng = setup.engine
    params = prepare_wire_params(setup.model, setup.params)
    cache = eng.init_cache()
    b = setup.spec.batch_global
    key = jax.random.PRNGKey(0)
    toks = jnp.zeros((b,), jnp.int32)
    pos = jnp.zeros((b,), jnp.int32)
    out = {}
    out["decode_fn"] = jax.make_jaxpr(eng.decode_step())(
        params, cache, toks, pos, key)
    bucket = 8
    out["prefill_chunk_fn"] = jax.make_jaxpr(eng.prefill_chunk_step(bucket))(
        params, cache, jnp.zeros((b, bucket), jnp.int32), pos,
        jnp.full((b,), bucket, jnp.int32), key)
    k = max(1, setup.spec.draft_depth)
    out["verify_fn"] = jax.make_jaxpr(eng.verify_step(k))(
        params, cache, jnp.zeros((b, k), jnp.int32), pos,
        jnp.full((b,), k, jnp.int32), key)
    return out


def run(arch: str = "gpt-125m") -> list[Finding]:
    findings = []
    for qs in (False, True):
        tag = f"train-step[{'qstate' if qs else 'f32'}]"
        findings.extend(audit_jaxpr(
            trace_train_step(arch, quantized_state=qs), tag))
    for tag, closed in trace_serve_programs(arch).items():
        findings.extend(audit_jaxpr(closed, tag, bit_identity=True))
    return findings
