"""Train-step builder: gradient accumulation + QSDP-wired backward +
sharded optimizer update, all inside one shard_map.

Schedule per optimizer step (paper Figure 5 + Appendix A):

  for each of n_micro microbatches:             (scan, rematerialized)
      for each layer:  quantized AllGather(w)   -> forward
      for each layer:  quantized AllGather(w)   -> backward
                       quantized ReduceScatter(g)
  grads averaged over microbatches              (local, sharded)
  AdamW update on the f32 master shards         (local, sharded)
  [optional] Q^w re-quantization of the master  (theory-faithful mode)

Under ``QSDPConfig.coalesce`` every per-layer AllGather / ReduceScatter
above is ONE u8 collective launch carrying the whole layer's coalesced wire
buffer (codes + metadata + filtered-fp payloads) instead of 3 x n_params
launches — same bytes, same decoded values, ~20x fewer launches (see
core/qsdp.py).  Under ``QSDPConfig.prefetch`` the scan-over-layers inside
``Model.loss_fn`` is additionally double-buffered: layer i+1's gather is
in flight while layer i computes, in the forward and the rematerialized
backward alike (``benchmarks/bench_step.py`` measures all three schedules).

Quantized-domain train state (``quantized_state=True``)
-------------------------------------------------------
The paper's Theorem 2 maintains ONLY quantized weights.  The historical
``quantize_master=True`` mode emulated that with f32 leaves round-tripped
through quantize->dequantize each step; ``quantized_state=True`` makes the
state itself quantized: every master-eligible parameter rests as a
:class:`~repro.core.quant.QuantizedParam` (packed u8 wire codes +
per-bucket affine, ~bits/32 of the f32 bytes).  Per step, each device
dequantizes its shard locally, runs the identical schedule above, and
re-quantizes the updated shard under the SAME per-step keys the QDQ master
uses (``fold_in(key, 0x3A57E9)`` then ``_h(name)``) — so the loss/param
trajectory is bit-exact with ``quantize_master=True`` started from the
same (quantization-grid) initial state; see ``quantize_train_state`` /
``dequantize_train_state``.

Gradient semantics: `Model.loss_fn` returns the per-device local-batch mean
with no collectives on the loss path; the engine's reduce-scatter backward
divides by the FSDP size, so accumulated grads are exact global-batch means.
Global-norm clipping needs one extra psum over all mesh axes (each element
of the sharded param grid lives on exactly one device).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

from ..core.quant import (
    QuantConfig,
    QuantizedParam,
    qparam_decode,
    qparam_encode,
    quantize_dequantize,
)
from ..models.transformer import Model
from ..obs import program
from ..optim import Optimizer, OptState


class TrainState(NamedTuple):
    params: dict[str, Any]  # f32 rest-layout leaves and/or QuantizedParam
    opt: OptState


def init_train_state(model: Model, optimizer: Optimizer, key: jax.Array,
                     mesh=None) -> TrainState:
    """Fresh params + optimizer state.  With `mesh`, one jitted program
    builds the state straight into its rest sharding (``state_pspecs``), so
    no device ever holds more than its share and the first step sees the
    same input shardings as every later one."""
    def init(k):
        params = model.init_params(k)
        return TrainState(params=params, opt=optimizer.init(params))

    if mesh is None:
        return init(key)
    specs = state_pspecs(
        model, quantized_moments=getattr(optimizer, "quantized_moments", False))
    shardings = jax.tree.map(lambda p: NamedSharding(mesh, p), specs,
                             is_leaf=lambda x: isinstance(x, P))
    return jax.jit(init, out_shardings=shardings)(key)


# -- master quantization policy (shared by quantize_master / quantized_state) --

_MASTER_SALT = 0x3A57E9


def master_quant_config(model: Model, master_bits: int = 8) -> QuantConfig:
    """The Q^w the master weights are re-quantized with (paper Theorem 2:
    random-shift rounding at the engine's bucket granularity)."""
    return QuantConfig(bits=master_bits, bucket_size=model.qcfg.bucket_size,
                       mode="shift")


def master_eligible(model: Model, name: str) -> bool:
    """Params the master quantization applies to — the same filter the wire
    quantization uses (norms / biases / tiny tensors stay full precision)."""
    spec = model.specs[name]
    return bool(
        spec.quantize
        and spec.n_logical_local(model.ms.model_size) >= model.qcfg.min_quant_size
    )


def quantize_train_state(state: TrainState, model: Model, key: jax.Array,
                         master_bits: int = 8) -> TrainState:
    """Convert an f32 TrainState into quantized-domain form: every
    master-eligible param leaf becomes a :class:`QuantizedParam` holding its
    packed wire codes, quantized under the same key schedule a train step
    with this `key` would use.  Host-side helper (global rest arrays);
    optimizer moments are left as the optimizer built them."""
    qc = master_quant_config(model, master_bits)
    mkey = jax.random.fold_in(key, _MASTER_SALT)
    params = {}
    for name, p in state.params.items():
        if master_eligible(model, name) and not isinstance(p, QuantizedParam):
            params[name] = qparam_encode(p, qc, jax.random.fold_in(mkey, _h(name)))
        else:
            params[name] = p
    return TrainState(params=params, opt=state.opt)


def dequantize_train_state(state: TrainState) -> TrainState:
    """Decode every QuantizedParam leaf (params AND optimizer moments) back
    to dense f32 rest layout.  Decoding is deterministic, so this yields
    exactly the values a `quantize_master=True` QDQ step would have stored."""
    def dec(leaf):
        return qparam_decode(leaf) if isinstance(leaf, QuantizedParam) else leaf

    params = {k: dec(v) for k, v in state.params.items()}
    mu = state.opt.mu if state.opt.mu == () else {k: dec(v) for k, v in state.opt.mu.items()}
    nu = state.opt.nu if state.opt.nu == () else {k: dec(v) for k, v in state.opt.nu.items()}
    return TrainState(params=params, opt=OptState(step=state.opt.step, mu=mu, nu=nu))


def state_pspecs(model: Model, optimizer_has_mu: bool = True, has_nu: bool = True,
                 quantized_state: bool = False, quantized_moments: bool = False):
    """PartitionSpec tree for a TrainState.  QuantizedParam leaves hold a
    rank-3 (MODEL, FSDP, nbytes) wire array whatever the stack, so their
    spec is always the flat wire spec (shard_map prefix-broadcasts the P
    over the QuantizedParam subtree)."""
    wire_p = P("model", model.ms.fsdp_axes, None)
    pspec = {}
    for name, spec in model.specs.items():
        if quantized_state and master_eligible(model, name):
            pspec[name] = wire_p
        else:
            pspec[name] = spec.rest_pspec(model.ms)
    base = model.param_pspecs()
    mom = {name: wire_p for name in base} if quantized_moments else base
    mu = mom if optimizer_has_mu else ()
    nu = mom if has_nu else ()
    return TrainState(
        params=pspec,
        opt=OptState(step=P(), mu=mu, nu=nu),
    )


def build_train_step(
    model: Model,
    optimizer: Optimizer,
    n_micro: int = 1,
    grad_clip: float = 1.0,
    quantize_master: bool = False,
    master_bits: int = 8,
    quantized_state: bool = False,
):
    """Returns the per-device step_fn to be wrapped in shard_map by the
    caller (launch.train / dryrun).  Buffer donation is owned by that
    caller's jit (see ``make_jitted_train_step``'s `donate`).

    quantize_master:  f32 state, round-tripped through Q^w each step (QDQ).
    quantized_state:  the state's master-eligible leaves ARE the wire codes
                      (QuantizedParam): decode shard-locally at step entry,
                      re-quantize at step exit under the same keys — bit-
                      exact with the QDQ path (see module docstring).
    """
    ms = model.ms
    all_axes = tuple(ms.axes)

    def step_fn(state: TrainState, batch: dict, key: jax.Array) -> tuple[TrainState, dict]:
        if quantized_state:
            params = {k: qparam_decode(v) if isinstance(v, QuantizedParam) else v
                      for k, v in state.params.items()}
        else:
            params = state.params

        # ---- microbatch split along the batch axis of every batch leaf ----
        # (axis 0 for everything except the M-RoPE "positions" stream, whose
        # leading axis is the 3 temporal/height/width channels)
        def split(name, x):
            ax = 1 if name == "positions" else 0
            b = x.shape[ax]
            assert b % n_micro == 0, (name, b, n_micro)
            x = jnp.moveaxis(x, ax, 0)
            x = x.reshape(n_micro, b // n_micro, *x.shape[1:])
            return jnp.moveaxis(x, 1, ax + 1)

        micro = {k: split(k, v) for k, v in batch.items()}

        def micro_step(carry, inp):
            acc, i = carry
            mb = inp
            mkey = jax.random.fold_in(key, i)
            loss, grads = jax.value_and_grad(model.loss_fn)(params, mb, mkey)
            acc = jax.tree.map(lambda a, g: a + g.astype(jnp.float32), acc, grads)
            return (acc, i + 1), loss

        zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (gsum, _), losses = lax.scan(micro_step, (zero, jnp.zeros((), jnp.int32)), micro)
        grads = jax.tree.map(lambda g: g / n_micro, gsum)
        loss = jnp.mean(losses)

        # ---- global-norm clip (elements are disjoint across the mesh) ----
        sq = sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads))
        gnorm = jnp.sqrt(lax.psum(sq, all_axes))
        if grad_clip:
            scale = jnp.minimum(1.0, grad_clip / jnp.maximum(gnorm, 1e-12))
        else:
            scale = jnp.ones(())

        new_params, new_opt = optimizer.update(params, grads, state.opt, grad_scale=scale)

        # ---- theory-faithful master quantization (Theorem 2) -------------
        if quantize_master or quantized_state:
            qc = master_quant_config(model, master_bits)
            mkey = jax.random.fold_in(key, _MASTER_SALT)

            def qmaster(name, p):
                if not master_eligible(model, name):
                    return p
                pkey = jax.random.fold_in(mkey, _h(name))
                if quantized_state:
                    return qparam_encode(p, qc, pkey)
                return quantize_dequantize(p, qc, pkey).astype(p.dtype)

            new_params = {k: qmaster(k, v) for k, v in new_params.items()}

        metrics = {
            "loss": lax.pmean(loss, all_axes),
            "grad_norm": gnorm,
            "step": new_opt.step,
        }
        return TrainState(params=new_params, opt=new_opt), metrics

    return step_fn


def _h(s: str) -> int:
    h = 2166136261
    for ch in s.encode():
        h = (h ^ ch) * 16777619 & 0xFFFFFFFF
    return h


def make_jitted_train_step(model: Model, optimizer: Optimizer, mesh, n_micro: int = 1,
                           batch_pspec: Optional[dict] = None, donate: bool = True,
                           quantized_state: bool = False, **kw):
    """Convenience: shard_map + jit the per-device step over `mesh`, as the
    program ``train_step``."""
    step = build_train_step(model, optimizer, n_micro=n_micro,
                            quantized_state=quantized_state, **kw)
    sspec = state_pspecs(
        model,
        quantized_state=quantized_state,
        quantized_moments=getattr(optimizer, "quantized_moments", False),
    )
    if batch_pspec is None:
        batch_pspec = {"tokens": P(model.ms.fsdp_axes), "labels": P(model.ms.fsdp_axes)}
    mapped = shard_map(
        step, mesh=mesh,
        in_specs=(sspec, batch_pspec, P()),
        out_specs=(sspec, {"loss": P(), "grad_norm": P(), "step": P()}),
        check_vma=False,
    )
    return jax.jit(program("train_step", mapped),
                   donate_argnums=(0,) if donate else ())
