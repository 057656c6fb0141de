"""The QSDP engine: fully-sharded data-parallel parameters with quantized
communication (the paper's contribution, as a composable JAX module).

Layout
------
Every logical parameter (logical shape ``spec.shape``, optionally a scanned
stack of ``spec.stack`` layers, optionally tensor-parallel along
``spec.tp_axis``) is stored *at rest* in the distributed layout

    (stack?, MODEL, FSDP, n_local)

where ``n_local = ceil(prod(tp_local_shape) / FSDP)`` (zero-padded).  The
shard_map in_spec for such a leaf is ``P(None?, "model", fsdp_axes, None)``,
i.e. each device holds a flat f32 1/FSDP-slice of its tensor-parallel shard
— exactly torch-FSDP's flat-parameter sharding, composed with Megatron TP.

Inside the step (per device), :meth:`QSDPEngine.gather` reconstructs the
TP-local tensor for one layer:

    forward :  quantize(local shard) -> all-gather(codes+scales) -> dequant
    backward:  quantize(grad chunks) -> all-to-all -> dequant-sum  (= quantized
               reduce-scatter), divided by the FSDP size (data-parallel mean),
               plus a psum over "model" for TP-replicated params.

wrapped in ``jax.custom_vjp`` so the paper's 2×AllGather + 1×ReduceScatter
per layer per step emerges naturally from ``jax.checkpoint``-rematerialized
scan-over-layers.

Coalesced wire format (``QSDPConfig.coalesce``, default on)
-----------------------------------------------------------
The per-tensor collectives cost 3 launches per quantized tensor (codes,
scale, zero) — ~23 all-gather launches per transformer layer per direction.
With ``coalesce=True`` every gather/reduce-scatter ships ONE contiguous u8
wire buffer (``core.collectives.WireLayout``): :meth:`QSDPEngine.gather`
coalesces a single tensor's three components, and
:meth:`QSDPEngine.gather_layer` coalesces *all* params of a layer dict —
quantized payloads and full-precision (filtered) ones alike — into one
collective per layer.  The bytes on the wire and the decoded values are
bit-identical to the per-tensor path (same per-tensor quantization keys);
only the launch count changes: 3 × n_params -> 1.

Double-buffered prefetch (``QSDPConfig.prefetch``, default off)
---------------------------------------------------------------
:meth:`QSDPEngine.gather_layer_start` issues the coalesced all-gather and
returns the *wire buffer* (u8); :meth:`QSDPEngine.gather_layer_finish`
decodes a previously gathered buffer and owns the backward reduce-scatter.
``models.transformer._scan_layers`` uses the pair to run a software
pipeline: the gather for layer i+1 is issued while layer i computes, the
(compact, ``~bits/32``-sized) wire buffer rides the scan carry, and the
rematerialized backward replays the same schedule — so the collective for
the next layer can overlap the current layer's compute in both directions.

Filtering (paper Section 5): normalization layers / biases / any tensor
smaller than ``min_quant_size`` travel in full precision, as do all tensors
when the engine is configured as the *baseline FSDP* (fp32 weights / bf16
gradients).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from jax.lax import axis_size
from ..kernels.ops import RowQuantWeight
from ..obs import scope, scoped
from . import collectives as coll
from .quant import (QuantConfig, QuantizedParam, dequantize, quantize,
                    unpack_codes, wire_unpack)

# ---------------------------------------------------------------------------
# Mesh description
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Static view of the training mesh.

    axes/shape: as built by launch.mesh.make_production_mesh — either
    ("data", "model") or ("pod", "data", "model").
    """

    axes: tuple[str, ...]
    shape: tuple[int, ...]

    @property
    def multi_pod(self) -> bool:
        return "pod" in self.axes

    @property
    def fsdp_axes(self) -> tuple[str, ...]:
        # data-major ordering so hierarchical collectives (gather pod first,
        # then data) land in the same element order as the flat tuple form.
        return ("data", "pod") if self.multi_pod else ("data",)

    @property
    def model_axis(self) -> str:
        return "model"

    @property
    def fsdp_size(self) -> int:
        s = dict(zip(self.axes, self.shape))
        return s["data"] * (s.get("pod", 1))

    @property
    def model_size(self) -> int:
        return dict(zip(self.axes, self.shape))["model"]

    @property
    def batch_spec(self) -> P:
        return P(self.fsdp_axes)


# ---------------------------------------------------------------------------
# Parameter specification
# ---------------------------------------------------------------------------

InitKind = str  # "normal" | "zeros" | "ones" | "scaled_normal"


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    """One logical parameter of the model."""

    shape: tuple[int, ...]  # logical (TP-global) shape, without stack dim
    tp_axis: Optional[int] = None  # axis sharded over "model" (None = replicated)
    stack: Optional[int] = None  # scan-over-layers length
    init: InitKind = "normal"
    init_scale: float = 0.02
    quantize: bool = True  # False => always full-precision comm (norms/bias)
    # True for model-REPLICATED params whose outputs are consumed
    # rank-specifically (e.g. replicated KV projections, Mamba B/C): each
    # model rank's gradient is then only a partial sum and the engine psums
    # it over the model axis to keep the replicas consistent.
    grad_sync_model: bool = False

    def tp_local_shape(self, model_size: int) -> tuple[int, ...]:
        if self.tp_axis is None:
            return self.shape
        assert self.shape[self.tp_axis] % model_size == 0, (self.shape, self.tp_axis, model_size)
        s = list(self.shape)
        s[self.tp_axis] //= model_size
        return tuple(s)

    def n_logical_local(self, model_size: int) -> int:
        return int(np.prod(self.tp_local_shape(model_size)))

    def n_local(self, ms: MeshSpec) -> int:
        n = self.n_logical_local(ms.model_size)
        return -(-n // ms.fsdp_size)  # ceil

    def rest_shape(self, ms: MeshSpec) -> tuple[int, ...]:
        base = (ms.model_size, ms.fsdp_size, self.n_local(ms))
        return (self.stack, *base) if self.stack is not None else base

    def rest_pspec(self, ms: MeshSpec) -> P:
        base = ("model", ms.fsdp_axes, None)
        return P(None, *base) if self.stack is not None else P(*base)

    @property
    def logical_size(self) -> int:
        n = int(np.prod(self.shape))
        return n * (self.stack or 1)


def to_rest(full: jax.Array, spec: ParamSpec, ms: MeshSpec) -> jax.Array:
    """Logical layout -> distributed rest layout (host-side / init / ckpt)."""
    lead = 1 if spec.stack is not None else 0
    x = full
    if spec.tp_axis is not None:
        ax = spec.tp_axis + lead
        tp = ms.model_size
        s = list(x.shape)
        x = x.reshape(*s[:ax], tp, s[ax] // tp, *s[ax + 1 :])
        x = jnp.moveaxis(x, ax, lead)  # (stack?, model, ...tp_local...)
    else:
        x = jnp.expand_dims(x, lead)
        x = jnp.broadcast_to(x, (*x.shape[:lead], ms.model_size, *x.shape[lead + 1 :]))
    # flatten tp-local part, pad, split over fsdp
    batch_dims = x.shape[: lead + 1]
    flat = x.reshape(*batch_dims, -1)
    n = flat.shape[-1]
    n_local = -(-n // ms.fsdp_size)
    pad = n_local * ms.fsdp_size - n
    if pad:
        flat = jnp.pad(flat, [(0, 0)] * len(batch_dims) + [(0, pad)])
    return flat.reshape(*batch_dims, ms.fsdp_size, n_local)


def from_rest(rest: jax.Array, spec: ParamSpec, ms: MeshSpec) -> jax.Array:
    """Distributed rest layout -> logical layout (checkpoint export/eval)."""
    lead = 1 if spec.stack is not None else 0
    batch_dims = rest.shape[: lead + 1]
    flat = rest.reshape(*batch_dims, -1)
    n = int(np.prod(spec.tp_local_shape(ms.model_size)))
    flat = flat[..., :n]
    x = flat.reshape(*batch_dims, *spec.tp_local_shape(ms.model_size))
    if spec.tp_axis is None:
        return x[:, 0] if lead else x[0]
    ax = spec.tp_axis + lead
    x = jnp.moveaxis(x, lead, ax)  # (stack?, ..., model, tp_local_dim, ...)
    s = list(x.shape)
    out = x.reshape(*s[:ax], s[ax] * s[ax + 1], *s[ax + 2 :])
    return out


def init_param(key: jax.Array, spec: ParamSpec, ms: MeshSpec, dtype=jnp.float32) -> jax.Array:
    shape = ((spec.stack,) if spec.stack is not None else ()) + spec.shape
    if spec.init == "zeros":
        full = jnp.zeros(shape, dtype)
    elif spec.init == "ones":
        full = jnp.ones(shape, dtype)
    elif spec.init == "constant":
        full = jnp.full(shape, spec.init_scale, dtype)
    elif spec.init == "normal":
        full = jax.random.normal(key, shape, dtype) * spec.init_scale
    elif spec.init == "scaled_normal":  # 1/sqrt(fan_in) init
        fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
        full = jax.random.normal(key, shape, dtype) * (spec.init_scale / math.sqrt(fan_in))
    else:
        raise ValueError(spec.init)
    return to_rest(full, spec, ms)


# ---------------------------------------------------------------------------
# Engine configuration
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class QSDPConfig:
    """Communication policy.  The paper's QSDP default is W8G8 bucket=1024;
    `baseline()` reproduces the paper's FSDP baseline (fp32 weights / half-
    precision gradients)."""

    quantize_weights: bool = True
    quantize_grads: bool = True
    weight_bits: int = 8
    grad_bits: int = 8
    bucket_size: int = 1024
    weight_mode: str = "shift"  # Definition 1
    grad_mode: str = "stochastic"  # Definition 12
    min_quant_size: int = 2048  # smaller tensors go full precision
    weight_wire_dtype: str = "float32"  # fp path wire dtype for weights
    grad_wire_dtype: str = "bfloat16"  # fp path wire dtype for grads (paper: fp16)
    hierarchical: bool = False  # 2-level collectives over (pod, data)
    compute_dtype: str = "bfloat16"
    # activation-checkpoint policy for the scan-over-layers:
    #   "full" — recompute everything in backward (min memory),
    #   "dots" — save matmul outputs (jax.checkpoint_policies
    #            .dots_with_no_batch_dims_saveable): ~25% less recompute
    #            FLOPs for ~1 extra activation set per layer (§Perf).
    remat_policy: str = "full"
    # §Perf knob: bf16 attention matmul operands w/ f32 accumulation
    attn_bf16: bool = False
    # §Perf knob: dequantize gathered weights straight to the compute dtype
    # (bf16), skipping the f32 intermediate — halves materialized weight
    # bytes with zero information loss (codes are <=8 bits).
    dequant_to_compute: bool = False
    # §Perf knob: u16 stochastic-rounding thresholds (4x less RNG traffic)
    rand_bits: int = 32
    # §Perf knob: coalesced wire format — serialize codes + (scale, zero)
    # metadata of every tensor of a gather (and of a whole layer dict via
    # gather_layer) into ONE contiguous u8 buffer, so each layer gather /
    # reduce-scatter is ONE collective launch instead of 3 x n_params.
    # Bit-exact vs. the per-tensor collectives (same keys, same wire bytes).
    coalesce: bool = True
    # Per-layer byte threshold on the coalesced path (None = coalesce every
    # layer when coalesce=True).  Coalescing trades 3*n_params-1 launch
    # overheads for extra serialization passes over ONE gathered buffer of
    # P * layout.nbytes bytes (segment concat, f32<->u8 bitcasts, vmap'd
    # per-shard decode) — a win only while that buffer is small relative to
    # the launch overhead it saves.  On the tiny smoke CPU mesh the
    # serialization side dominates (qsdp-coalesced 370 ms vs plain qsdp
    # 204 ms median), so the deployment-plan autotuner (repro.tune) sets
    # this threshold from its cost model: layers whose per-device gathered
    # wire buffer exceeds it fall back to per-tensor gathers.  Because the
    # two paths are bit-identical (same per-tensor quantization keys), the
    # policy can flip per layer without changing a single gradient bit.
    coalesce_max_bytes: Optional[int] = None
    # §Perf knob: double-buffered layer prefetch — the scan-over-layers
    # issues the coalesced gather for layer i+1 while layer i computes,
    # carrying the u8 wire buffer through the scan carry (forward AND the
    # rematerialized backward).  Requires coalesce=True.  Costs one extra
    # (discarded) gather per stack traversal and one wire buffer of
    # residency per live layer.
    prefetch: bool = False
    # on-wire dtype of the per-bucket (scale, zero) quantization metadata:
    # "float32" (exact) or "bfloat16" (halves metadata bytes; perturbs the
    # decode affine by ~2^-8 relative).  Accounted by gather_wire_bytes /
    # reduce_scatter_wire_bytes and the Fig-4 bandwidth model.
    meta_wire_dtype: str = "float32"

    @classmethod
    def baseline(cls) -> "QSDPConfig":
        """The paper's FSDP baseline: fp32 weights / bf16 grads, per-tensor
        collectives (no wire coalescing — torch-FSDP launches per leaf)."""
        return cls(quantize_weights=False, quantize_grads=False, coalesce=False)

    @classmethod
    def w8g8(cls, **kw) -> "QSDPConfig":
        return cls(**kw)

    def wcfg(self) -> QuantConfig:
        return QuantConfig(bits=self.weight_bits, bucket_size=self.bucket_size,
                           mode=self.weight_mode, rand_bits=self.rand_bits,
                           meta_dtype=self.meta_wire_dtype)

    def gcfg(self) -> QuantConfig:
        return QuantConfig(bits=self.grad_bits, bucket_size=self.bucket_size,
                           mode=self.grad_mode, rand_bits=self.rand_bits,
                           meta_dtype=self.meta_wire_dtype)


@dataclasses.dataclass(frozen=True)
class _GatherStatic:
    """Hashable static payload for the custom_vjp gather."""

    fsdp_axes: tuple[str, ...]
    model_axis: str
    grad_sync_model: bool
    wcfg: Optional[QuantConfig]  # None => full-precision weight path
    gcfg: Optional[QuantConfig]  # None => full-precision grad path
    weight_wire_dtype: str
    grad_wire_dtype: str
    hierarchical: bool
    gather_out_dtype: Optional[str] = None  # None => shard dtype (f32)

    @property
    def pod_axis(self) -> Optional[str]:
        return "pod" if "pod" in self.fsdp_axes else None

    @property
    def inner_axes(self) -> tuple[str, ...]:
        return tuple(a for a in self.fsdp_axes if a != "pod")


# ---------------------------------------------------------------------------
# The gather primitive (per-device; used inside shard_map)
# ---------------------------------------------------------------------------


@scoped("qsdp_gather")
def _gather_fwd_impl(flat: jax.Array, key: jax.Array, st: _GatherStatic) -> jax.Array:
    out_dt = getattr(jnp, st.gather_out_dtype) if st.gather_out_dtype else None
    if st.wcfg is None:
        return coll.all_gather_fp(flat, st.fsdp_axes, getattr(jnp, st.weight_wire_dtype))
    if st.hierarchical and st.pod_axis is not None:
        return coll.all_gather_hierarchical(flat, st.pod_axis, st.inner_axes,
                                            st.wcfg, key, out_dtype=out_dt)
    return coll.all_gather_quantized(flat, st.fsdp_axes, st.wcfg, key,
                                     out_dtype=out_dt)


@scoped("qsdp_reduce_scatter")
def _grad_rs_impl(ct: jax.Array, key: jax.Array, st: _GatherStatic) -> jax.Array:
    # Gradient semantics (see core/tp.py docstring): the loss function returns
    # the per-device local-batch mean with no collectives on the loss path;
    # the cotangent arriving here is d(local loss)/d(full weight).  The
    # reduce-scatter sums over the FSDP group and we divide by its size, so
    # the result is exactly d(global-batch-mean loss)/d(shard).  Model-axis
    # sums for TP-replicated params are owned by tp_copy's backward; the
    # cotangent here is already identical across model ranks.
    p = 1
    for a in st.fsdp_axes:
        p *= axis_size(a)
    if st.gcfg is None:
        g = coll.reduce_scatter_fp(ct, st.fsdp_axes, getattr(jnp, st.grad_wire_dtype))
    elif st.hierarchical and st.pod_axis is not None:
        g = coll.reduce_scatter_hierarchical(ct, st.pod_axis, st.inner_axes, st.gcfg, key)
    else:
        g = coll.reduce_scatter_quantized(ct, st.fsdp_axes, st.gcfg, key)
    g = g.astype(jnp.float32) / p
    if st.grad_sync_model:
        g = lax.psum(g, st.model_axis)
    return g


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def qsdp_gather(flat: jax.Array, key: jax.Array, st: _GatherStatic) -> jax.Array:
    """(n_local,) f32 shard -> (FSDP * n_local,) full flat tensor."""
    return _gather_fwd_impl(flat, key, st)


def _qsdp_gather_fwd(flat, key, st):
    return _gather_fwd_impl(flat, key, st), key


def _qsdp_gather_bwd(st, key, ct):
    bkey = jax.random.fold_in(key, 0x5D)
    d_flat = _grad_rs_impl(ct.astype(jnp.float32), bkey, st)
    return d_flat, jnp.zeros_like(key)


qsdp_gather.defvjp(_qsdp_gather_fwd, _qsdp_gather_bwd)


# ---------------------------------------------------------------------------
# Coalesced layer gather: ONE collective for all params of a layer dict.
#
# Three entry points (all over a tuple of flat shards, ordered by st.names):
#
#   qsdp_gather_layer(shards, key, st)          fused encode+gather+decode
#   qsdp_gather_layer_start(shards, key, st)    encode + all-gather -> u8 wire
#   qsdp_gather_layer_finish(shards, wire, key, st)   decode a carried wire
#
# start/finish split the op across scan iterations for the prefetch
# pipeline: `start` has no custom VJP (its u8 output is non-differentiable,
# so AD never touches the launch), while `finish` owns the whole backward —
# its cotangent is reduce-scattered (coalesced, one launch) back to the
# shards, exactly like the fused form.  The `shards` argument of `finish` is
# unused in the primal (the wire already holds their quantized bytes); it
# exists to give the VJP a differentiable path back to the parameters.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _LayerStatic:
    """Hashable static payload for the coalesced layer gather."""

    names: tuple[str, ...]  # full param names (buffer segment order)
    n_locals: tuple[int, ...]  # per-device shard sizes
    quant: tuple[bool, ...]  # weight path quantized per param
    gquant: tuple[bool, ...]  # grad path quantized per param
    gsync: tuple[bool, ...]  # psum grads over the model axis per param
    fsdp_axes: tuple[str, ...]
    model_axis: str
    wcfg: Optional[QuantConfig]
    gcfg: Optional[QuantConfig]
    weight_wire_dtype: str
    grad_wire_dtype: str
    hierarchical: bool
    gather_out_dtype: Optional[str] = None

    @property
    def pod_axis(self) -> Optional[str]:
        return "pod" if "pod" in self.fsdp_axes else None

    @property
    def inner_axes(self) -> tuple[str, ...]:
        return tuple(a for a in self.fsdp_axes if a != "pod")

    def fsdp_size(self) -> int:
        p = 1
        for a in self.fsdp_axes:
            p *= axis_size(a)
        return p

    def gather_layout(self) -> coll.WireLayout:
        return coll.WireLayout(tuple(
            coll.WireSegment(n, self.wcfg if q else None, self.weight_wire_dtype)
            for n, q in zip(self.n_locals, self.quant)
        ))

    def rs_layout(self, chunk_div: int) -> coll.WireLayout:
        """Layout of the grad RS rows when each tensor's full size
        (p * n_local) is split into chunk_div chunks (one per destination
        of the level being reduced)."""
        p = self.fsdp_size()
        return coll.WireLayout(tuple(
            coll.WireSegment(n * p // chunk_div,
                             self.gcfg if q else None, self.grad_wire_dtype)
            for n, q in zip(self.n_locals, self.gquant)
        ))


def _layer_keys(key: jax.Array, st: _LayerStatic) -> list:
    """Per-param gather keys — the same fold the per-tensor path applies, so
    coalesced and per-tensor quantization draw identical randomness."""
    return [jax.random.fold_in(key, _stable_hash(n)) for n in st.names]


def _layer_encode(shards, key: jax.Array, st: _LayerStatic) -> jax.Array:
    """Encode half of the coalesced gather: this device's shards quantized
    and serialized into one (layout.nbytes,) u8 buffer."""
    return coll.encode_wire([s.reshape(-1) for s in shards],
                            st.gather_layout(), _layer_keys(key, st))


def _layer_gather(buf: jax.Array, st: _LayerStatic) -> jax.Array:
    """The collective of the coalesced gather: (nbytes,) -> (P * nbytes,)."""
    pod = st.pod_axis if st.hierarchical else None
    return coll.gather_wire(buf, st.fsdp_axes, pod_axis=pod)


@scoped("qsdp_gather")
def _layer_encode_gather(shards, key: jax.Array, st: _LayerStatic) -> jax.Array:
    return _layer_gather(_layer_encode(shards, key, st), st)


@scoped("qsdp_gather")
def _layer_decode(wire: jax.Array, st: _LayerStatic):
    out_dt = getattr(jnp, st.gather_out_dtype) if st.gather_out_dtype else jnp.float32
    dts = [out_dt if q else jnp.float32 for q in st.quant]
    return tuple(coll.decode_gathered_wire(
        wire, st.gather_layout(), st.fsdp_size(), dts))


@scoped("qsdp_reduce_scatter")
def _layer_grad_rs(cts, key: jax.Array, st: _LayerStatic):
    p = st.fsdp_size()
    keys = [jax.random.fold_in(k, 0x5D) for k in _layer_keys(key, st)]
    if st.hierarchical and st.pod_axis is not None:
        p_inner = 1
        for a in st.inner_axes:
            p_inner *= axis_size(a)
        outs = coll.reduce_scatter_coalesced_hierarchical(
            cts, st.pod_axis, st.inner_axes,
            st.rs_layout(p_inner), st.rs_layout(p), keys)
    else:
        outs = coll.reduce_scatter_coalesced(cts, st.fsdp_axes,
                                             st.rs_layout(p), keys)
    res = []
    for g, sync in zip(outs, st.gsync):
        g = g.astype(jnp.float32) / p
        if sync:
            g = lax.psum(g, st.model_axis)
        res.append(g)
    return tuple(res)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def qsdp_gather_layer(shards: tuple, key: jax.Array, st: _LayerStatic) -> tuple:
    """Tuple of (n_local,) shards -> tuple of (P * n_local,) full flats,
    via one coalesced all-gather (backward: one coalesced reduce-scatter)."""
    return _layer_decode(_layer_encode_gather(shards, key, st), st)


def _qsdp_gather_layer_fwd(shards, key, st):
    return _layer_decode(_layer_encode_gather(shards, key, st), st), key


def _qsdp_gather_layer_bwd(st, key, cts):
    d = _layer_grad_rs([c.astype(jnp.float32) for c in cts], key, st)
    return d, jnp.zeros_like(key)


qsdp_gather_layer.defvjp(_qsdp_gather_layer_fwd, _qsdp_gather_layer_bwd)


def qsdp_gather_layer_start(shards: tuple, key: jax.Array, st: _LayerStatic) -> jax.Array:
    """Issue the coalesced all-gather; returns the (P * nbytes,) u8 wire
    buffer (prefetch pipeline: call one scan step ahead of the compute)."""
    return _layer_encode_gather(shards, key, st)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def qsdp_gather_layer_finish(shards: tuple, wire: jax.Array, key: jax.Array,
                             st: _LayerStatic) -> tuple:
    """Decode a wire buffer gathered by :func:`qsdp_gather_layer_start`.
    The primal ignores `shards` (their bytes are already in `wire`); the
    backward reduce-scatters the cotangents to them."""
    return _layer_decode(wire, st)


def _qsdp_gather_layer_finish_fwd(shards, wire, key, st):
    return _layer_decode(wire, st), key


def _qsdp_gather_layer_finish_bwd(st, key, cts):
    d = _layer_grad_rs([c.astype(jnp.float32) for c in cts], key, st)
    wire_len = st.fsdp_size() * st.gather_layout().nbytes
    return d, jnp.zeros((wire_len,), jnp.uint8), jnp.zeros_like(key)


qsdp_gather_layer_finish.defvjp(_qsdp_gather_layer_finish_fwd,
                                _qsdp_gather_layer_finish_bwd)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class EncodedLayer:
    """The encode half of one coalesced gather, kept: the u8 wire buffer of
    the parameters `names` (full names, sorted), encoded and not yet
    gathered.  Per device it is ``(1, 1, nbytes)``, or ``(stack, 1, 1,
    nbytes)`` for a scan-over-layers group, the rest layout's cells with the
    flat f32 shard replaced by its encoded bytes — 1/P of the layer on P
    devices.  :meth:`QSDPEngine.gather_encoded` runs the other half."""

    wire: jax.Array
    names: tuple[str, ...]

    def tree_flatten(self):
        return (self.wire,), self.names

    @classmethod
    def tree_unflatten(cls, names, children):
        return cls(children[0], names)


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------


class QSDPEngine:
    """Binds a MeshSpec + QSDPConfig + parameter specs into gather callables
    usable inside the shard_mapped step."""

    def __init__(self, ms: MeshSpec, cfg: QSDPConfig, specs: dict[str, ParamSpec]):
        self.ms = ms
        self.cfg = cfg
        self.specs = specs
        self.compute_dtype = getattr(jnp, cfg.compute_dtype)

    # -- static policy ------------------------------------------------------

    def _is_quantized(self, spec: ParamSpec) -> bool:
        return (
            spec.quantize
            and self.cfg.quantize_weights
            and spec.n_logical_local(self.ms.model_size) >= self.cfg.min_quant_size
        )

    def _is_grad_quantized(self, spec: ParamSpec) -> bool:
        return (
            spec.quantize
            and self.cfg.quantize_grads
            and spec.n_logical_local(self.ms.model_size) >= self.cfg.min_quant_size
        )

    def layer_wire_bytes(self, names: tuple[str, ...]) -> int:
        """Per-device bytes of the GATHERED coalesced wire buffer for one
        gather of `names` (= fsdp_size * encoded layout bytes) — the
        quantity the coalesce threshold compares against, and what the
        serialization term of the tune cost model scales with."""
        st = self._layer_static(tuple(names))
        return self.ms.fsdp_size * st.gather_layout().nbytes

    def layer_coalesced(self, names: tuple[str, ...]) -> bool:
        """Per-layer coalesce policy: ship these params as ONE wire buffer
        iff ``cfg.coalesce`` and the gathered buffer stays under
        ``cfg.coalesce_max_bytes`` (None = no threshold).  Purely static —
        decided from ParamSpecs at trace time, never from array values."""
        if not self.cfg.coalesce:
            return False
        if self.cfg.coalesce_max_bytes is None:
            return True
        return self.layer_wire_bytes(names) <= self.cfg.coalesce_max_bytes

    def _layer_static(self, names: tuple[str, ...]) -> _LayerStatic:
        specs = [self.specs[n] for n in names]
        return _LayerStatic(
            names=names,
            n_locals=tuple(s.n_local(self.ms) for s in specs),
            quant=tuple(self._is_quantized(s) for s in specs),
            gquant=tuple(self._is_grad_quantized(s) for s in specs),
            gsync=tuple(s.grad_sync_model for s in specs),
            fsdp_axes=self.ms.fsdp_axes,
            model_axis=self.ms.model_axis,
            wcfg=self.cfg.wcfg() if self.cfg.quantize_weights else None,
            gcfg=self.cfg.gcfg() if self.cfg.quantize_grads else None,
            weight_wire_dtype=self.cfg.weight_wire_dtype,
            grad_wire_dtype=self.cfg.grad_wire_dtype,
            hierarchical=self.cfg.hierarchical,
            gather_out_dtype=(self.cfg.compute_dtype
                              if getattr(self.cfg, "dequant_to_compute", False)
                              else None),
        )

    def _static_for(self, spec: ParamSpec) -> _GatherStatic:
        quant = self._is_quantized(spec)
        grad_quant = self._is_grad_quantized(spec)
        return _GatherStatic(
            fsdp_axes=self.ms.fsdp_axes,
            model_axis=self.ms.model_axis,
            grad_sync_model=spec.grad_sync_model,
            wcfg=self.cfg.wcfg() if quant else None,
            gcfg=self.cfg.gcfg() if grad_quant else None,
            weight_wire_dtype=self.cfg.weight_wire_dtype,
            grad_wire_dtype=self.cfg.grad_wire_dtype,
            hierarchical=self.cfg.hierarchical,
            gather_out_dtype=(self.cfg.compute_dtype
                              if getattr(self.cfg, "dequant_to_compute", False)
                              else None),
        )

    # -- per-device ops (inside shard_map) -----------------------------------

    def _reshape_full(self, name: str, full: jax.Array) -> jax.Array:
        spec = self.specs[name]
        n = spec.n_logical_local(self.ms.model_size)
        w = full[:n].reshape(spec.tp_local_shape(self.ms.model_size))
        return w.astype(self.compute_dtype)

    def _gather_per_tensor(self, name: str, flat: jax.Array,
                           key: jax.Array) -> jax.Array:
        """Forced per-tensor gather: 3 collectives (codes/scale/zero) for a
        quantized param, 1 for an fp payload — never re-coalesced."""
        spec = self.specs[name]
        key = jax.random.fold_in(key, _stable_hash(name))
        full = qsdp_gather(flat, key, self._static_for(spec))
        return self._reshape_full(name, full)

    def gather(self, name: str, local: jax.Array, key: jax.Array) -> jax.Array:
        """Materialize the TP-local tensor for parameter `name` from its
        per-device flat shard (shape (..., 1, 1, n_local) or (n_local,)).
        Under ``cfg.coalesce`` the tensor's codes + metadata ride one
        collective (single-segment wire buffer) instead of three."""
        flat = local.reshape(-1)
        if self.layer_coalesced((name,)):
            full = qsdp_gather_layer((flat,), key, self._layer_static((name,)))[0]
            return self._reshape_full(name, full)
        return self._gather_per_tensor(name, flat, key)

    def gather_layer(self, prefix: str, leaves: dict[str, jax.Array],
                     key: jax.Array) -> dict[str, jax.Array]:
        """Gather every parameter of one layer-dict — ONE collective for the
        whole layer under ``cfg.coalesce``, per-param otherwise.  The
        fallback is genuinely per-tensor (3 launches per quantized param):
        re-checking the byte threshold tensor-by-tensor would single-segment
        re-coalesce every small tensor, which the cost model prices as a
        loss (it saves 2 launches but adds the wire serialize/decode passes
        that caused the small-scale regression in the first place)."""
        if not leaves:
            return {}
        if not self.layer_coalesced(tuple(f"{prefix}{k}" for k in sorted(leaves))):
            return {k: self._gather_per_tensor(f"{prefix}{k}", v.reshape(-1), key)
                    for k, v in leaves.items()}
        names, st, shards = self._layer_args(prefix, leaves)
        fulls = qsdp_gather_layer(shards, key, st)
        return {k: self._reshape_full(f"{prefix}{k}", f)
                for k, f in zip(names, fulls)}

    def gather_layer_start(self, prefix: str, leaves: dict[str, jax.Array],
                           key: jax.Array) -> jax.Array:
        """Prefetch pipeline, step 1: issue the coalesced all-gather for a
        layer and return its u8 wire buffer (to be carried one scan step)."""
        _, st, shards = self._layer_args(prefix, leaves)
        return qsdp_gather_layer_start(shards, key, st)

    def gather_layer_finish(self, prefix: str, leaves: dict[str, jax.Array],
                            wire: jax.Array, key: jax.Array) -> dict[str, jax.Array]:
        """Prefetch pipeline, step 2: decode the carried wire buffer into the
        layer's TP-local tensors (backward: coalesced reduce-scatter)."""
        names, st, shards = self._layer_args(prefix, leaves)
        fulls = qsdp_gather_layer_finish(shards, wire, key, st)
        return {k: self._reshape_full(f"{prefix}{k}", f)
                for k, f in zip(names, fulls)}

    def _layer_args(self, prefix: str, leaves: dict[str, jax.Array]):
        names = tuple(sorted(leaves))
        st = self._layer_static(tuple(f"{prefix}{k}" for k in names))
        shards = tuple(leaves[k].reshape(-1) for k in names)
        return names, st, shards

    # -- encoded gather halves (serve: one fixed gather key) -------------------

    def encode_layer(self, names: tuple[str, ...], leaves: dict[str, jax.Array],
                     key: jax.Array) -> jax.Array:
        """The encode half of :meth:`gather_layer` (or of :meth:`gather` for
        one name) over the full param `names` (sorted): this device's
        (nbytes,) u8 buffer, under the same per-tensor keys, so
        :meth:`gather_encoded` later yields the bytes the fused gather would.
        Only for names that :meth:`layer_coalesced` ships as one buffer."""
        st = self._layer_static(names)
        with scope("qsdp_gather"):
            return _layer_encode(tuple(leaves[n].reshape(-1) for n in names),
                                 key, st)

    def gather_encoded(self, enc: EncodedLayer) -> dict[str, jax.Array]:
        """The other half: all-gather one layer's encoded buffer and decode
        it into each parameter's TP-local tensor, keyed by full name
        (inference only: no VJP)."""
        st = self._layer_static(enc.names)
        with scope("qsdp_gather"):
            wire = _layer_gather(enc.wire.reshape(-1), st)
        fulls = _layer_decode(wire, st)
        return {n: self._reshape_full(n, f) for n, f in zip(enc.names, fulls)}

    # -- code-form gather (serve/decode; no VJP — inference only) -------------

    def _rowquant_tiling_ok(self, spec: ParamSpec, cfg: QuantConfig) -> bool:
        """Do `cfg`'s buckets tile this weight's rows exactly?  2D (K, N)
        tp-local shape, a bit width whose packed codes unpack along bucket
        boundaries (bucket_size % codes_per_byte == 0 — always true for the
        packable widths 2/4/8, and sub-8-bit codes are unpacked to one byte
        per value after the gather), N a multiple of the bucket size, and an
        FSDP shard that is a whole number of buckets (no padding anywhere,
        so global bucket b covers flat elements [b*bsz, (b+1)*bsz) of the
        row-major weight).

        NB stacked (scan-over-layers) params are gathered one layer slice
        at a time, so shape/n here are already per-layer quantities."""
        shape = spec.tp_local_shape(self.ms.model_size)
        n = spec.n_logical_local(self.ms.model_size)
        p = self.ms.fsdp_size
        return (
            cfg.bucket_size % cfg.codes_per_byte == 0
            and not self.cfg.hierarchical
            and len(shape) == 2
            and shape[1] % cfg.bucket_size == 0
            and n % p == 0
            and (n // p) % cfg.bucket_size == 0
        )

    def _assemble_rowquant(self, spec: ParamSpec, cfg: QuantConfig,
                           q) -> RowQuantWeight:
        """All-gather a shard's (codes, scale, zero) over FSDP and reshape
        into the (K, N) / (K, n_seg) RowQuantWeight layout.  Sub-8-bit codes
        travel packed (the bits 2-8 wire format) and are unpacked to one
        byte per value after the gather — bucket boundaries survive packing
        (bucket_size % codes_per_byte == 0), so the unpacked bytes are the
        row-major codes the fused rowquant matmul consumes."""
        codes = lax.all_gather(q.codes, self.ms.fsdp_axes, tiled=True)
        scale = lax.all_gather(q.scale, self.ms.fsdp_axes, tiled=True)
        zero = lax.all_gather(q.zero, self.ms.fsdp_axes, tiled=True)
        if cfg.codes_per_byte > 1:
            codes = unpack_codes(codes, cfg.bits)
        k_dim, n_dim = spec.tp_local_shape(self.ms.model_size)
        n_seg = n_dim // cfg.bucket_size
        return RowQuantWeight(
            codes=codes.reshape(k_dim, n_dim),
            scale=scale.reshape(k_dim, n_seg),
            zero=zero.reshape(k_dim, n_seg),
        )

    def rowquant_eligible(self, name: str) -> bool:
        """A gathered weight can stay in code form through the matmul iff
        the engine quantizes it and the wire buckets tile its rows (see
        :meth:`_rowquant_tiling_ok`)."""
        spec = self.specs[name]
        return (self._is_quantized(spec)
                and self._rowquant_tiling_ok(spec, self.cfg.wcfg()))

    def gather_rowquant(self, name: str, local: jax.Array, key: jax.Array):
        """All-gather parameter `name` but return it as a
        :class:`RowQuantWeight` — the wire codes reshaped (K, N) with the
        per-bucket affine as (K, N/bucket) segments — instead of
        dequantizing to a dense matrix.  ``kernels.ops.rowquant_matmul``
        then consumes the codes directly, so the full-precision weight is
        never materialized in HBM (inference only: no custom VJP).

        Falls back to the dense :meth:`gather` when the layout conditions
        don't hold (see :meth:`rowquant_eligible`)."""
        if not self.rowquant_eligible(name):
            return self.gather(name, local, key)
        spec = self.specs[name]
        wcfg = self.cfg.wcfg()
        flat = local.reshape(-1)
        key = jax.random.fold_in(key, _stable_hash(name))
        return self._assemble_rowquant(spec, wcfg, quantize(flat, wcfg, key))

    def rowquant_wire_eligible(self, name: str, qp: QuantizedParam) -> bool:
        """Like :meth:`rowquant_eligible`, but for a parameter whose rest
        state already IS wire codes (quantized train state / checkpoint v2):
        the stored buckets must tile the weight's rows with no padding.
        Independent of the engine's comm policy — the codes exist whether or
        not this engine quantizes its own collectives."""
        return (qp.cfg.meta_dtype == "float32"
                and self._rowquant_tiling_ok(self.specs[name], qp.cfg))

    def gather_rowquant_wire(self, name: str, qp: QuantizedParam) -> RowQuantWeight:
        """All-gather a parameter stored as wire codes straight into a
        :class:`RowQuantWeight`: no quantize on the way out, no dequantize on
        the way in — the checkpoint/train-state bytes feed
        ``kernels.ops.rowquant_matmul`` directly (inference only).

        `qp` is the per-device view (wire (1, 1, nbytes), cell (n_local,));
        caller guarantees :meth:`rowquant_wire_eligible`."""
        q = wire_unpack(qp.wire.reshape(-1), qp.n, qp.cfg)
        return self._assemble_rowquant(self.specs[name], qp.cfg, q)

    @scoped("qsdp_gather")
    def gather_wire_dequant(self, name: str, qp: QuantizedParam) -> jax.Array:
        """Dense fallback for a wire-form parameter that the rowquant matmul
        can't tile (attention projections, 3D expert stacks, odd buckets):
        all-gather the packed wire segments over FSDP and dequantize each
        shard's segment through the bits 2-8 kernels into the TP-local
        tensor.  Each shard's [codes | scale | zero] segment is
        self-contained (its own bucket padding included), so no alignment
        between shards is required — this works for ANY per-leaf bucket
        size (inference only: no VJP)."""
        buf = lax.all_gather(qp.wire.reshape(-1), self.ms.fsdp_axes,
                             tiled=True)
        segs = buf.reshape(self.ms.fsdp_size, -1)

        def dec(b):
            return dequantize(wire_unpack(b, qp.n, qp.cfg)).reshape(-1)

        full = (dec(segs[0]) if segs.shape[0] == 1
                else jax.vmap(dec)(segs).reshape(-1))
        return self._reshape_full(name, full)

    # -- host-side helpers ----------------------------------------------------

    def init_params(self, key: jax.Array) -> dict[str, jax.Array]:
        out = {}
        for i, (name, spec) in enumerate(sorted(self.specs.items())):
            out[name] = init_param(jax.random.fold_in(key, i), spec, self.ms)
        return out

    def in_specs(self) -> dict[str, P]:
        return {name: spec.rest_pspec(self.ms) for name, spec in self.specs.items()}

    def param_bytes_per_device(self) -> int:
        total = 0
        for spec in self.specs.values():
            total += int(np.prod(spec.rest_shape(self.ms))) // (self.ms.fsdp_size * self.ms.model_size)
        return total * 4


def _stable_hash(s: str) -> int:
    h = 2166136261
    for ch in s.encode():
        h = (h ^ ch) * 16777619 & 0xFFFFFFFF
    return h


# ---------------------------------------------------------------------------
# Communication accounting (per step, analytic; feeds the Fig-4 model)
# ---------------------------------------------------------------------------


def step_comm_bytes(
    engine: QSDPEngine, gathers_per_param: int = 2, reduces_per_param: int = 1
) -> dict[str, int]:
    """Per-device wire bytes of one optimizer step under the engine's policy
    (2 weight all-gathers + 1 gradient reduce-scatter per param by default,
    i.e. the FSDP schedule)."""
    ms, cfg = engine.ms, engine.cfg
    p = ms.fsdp_size
    wbytes = rbytes = 0
    for spec in engine.specs.values():
        reps = spec.stack or 1
        n_local_shard = spec.n_local(ms)
        n_full = n_local_shard * p
        wq = cfg.wcfg() if engine._is_quantized(spec) else None
        gq = (
            cfg.gcfg()
            if (spec.quantize and cfg.quantize_grads
                and spec.n_logical_local(ms.model_size) >= cfg.min_quant_size)
            else None
        )
        wfp = 4 if cfg.weight_wire_dtype == "float32" else 2
        gfp = 4 if cfg.grad_wire_dtype == "float32" else 2
        wbytes += reps * gathers_per_param * coll.gather_wire_bytes(n_local_shard, p, wq, wfp)
        rbytes += reps * reduces_per_param * coll.reduce_scatter_wire_bytes(n_full, p, gq, gfp)
    return dict(weight_gather=wbytes, grad_reduce=rbytes, total=wbytes + rbytes)


def layer_gather_launches(engine: QSDPEngine, names: list[str]) -> int:
    """Analytic collective-launch count of ONE gather of the given params
    (the quantity the coalesced wire format collapses): 3 per quantized
    tensor (codes, scale, zero) + 1 per full-precision tensor when
    per-tensor, 1 total when coalesced.  Hierarchical (two-level) gathers
    double the quantized / coalesced launches (pod + in-pod).  Respects the
    per-layer ``coalesce_max_bytes`` policy (engine.layer_coalesced)."""
    levels = 2 if engine.cfg.hierarchical and engine.ms.multi_pod else 1
    if engine.layer_coalesced(tuple(names)):
        return levels
    return sum(3 * levels if engine._is_quantized(engine.specs[n]) else 1
               for n in names)
