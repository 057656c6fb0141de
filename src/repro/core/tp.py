"""Tensor-parallel collective ops with hand-specified transposes.

Under ``shard_map(..., check_vma=False)`` JAX uses the legacy pmap transpose
rules (``transpose(psum) = psum``), which double-counts gradients whenever a
psum output is consumed by replicated compute.  As in Megatron's f/g
functions, we fix the semantics explicitly:

    tp_copy   : identity forward  /  psum over "model" backward
                (entry into a column-parallel region from replicated
                activations — the backward sums each rank's contribution)
    tp_reduce : psum over "model" forward  /  identity backward
                (exit from a row-parallel region — the output is replicated,
                so each rank backpropagates the same cotangent locally)

Composition rule for all model code in this repo:

  * every path from model-replicated activations into rank-specific
    (TP-sharded) compute goes through ``tp_copy``;
  * every rank-partial result that must become replicated goes through
    ``tp_reduce`` (including the log-sum-exp and label terms of the
    vocab-parallel cross-entropy);
  * gradient semantics inside the shard_mapped step: the loss function
    returns the *local* (per-device) mean loss with NO collectives on the
    loss path; the QSDP gather backward performs the cross-device sum
    (reduce-scatter / fsdp_size).

``lax.all_to_all`` and activation ``all_gather`` keep their builtin
transposes (verified exact: a2a transposes to the inverse a2a; all_gather to
psum_scatter, correct when the gathered value is consumed rank-specifically).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from jax.lax import axis_size

MODEL_AXIS = "model"


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def tp_copy(x: jax.Array, axis: str = MODEL_AXIS) -> jax.Array:
    return x


def _tp_copy_fwd(x, axis):
    return x, None


def _tp_copy_bwd(axis, _, ct):
    return (lax.psum(ct, axis),)


tp_copy.defvjp(_tp_copy_fwd, _tp_copy_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def tp_reduce(x: jax.Array, axis: str = MODEL_AXIS) -> jax.Array:
    return lax.psum(x, axis)


def _tp_reduce_fwd(x, axis):
    return lax.psum(x, axis), None


def _tp_reduce_bwd(axis, _, ct):
    return (ct,)


tp_reduce.defvjp(_tp_reduce_fwd, _tp_reduce_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def tp_split_tokens(x: jax.Array, dim: int = 0, axis: str = MODEL_AXIS) -> jax.Array:
    """Replicated -> rank-sharded along `dim` (sequence/token parallelism).

    Forward: take this rank's 1/P chunk.  Backward: the full cotangent is
    assembled by all-gathering every rank's chunk-cotangent (each rank's
    compute path only touched its own chunk).
    """
    return _split(x, dim, axis)


def _split(x, dim, axis):
    p = axis_size(axis)
    r = lax.axis_index(axis)
    n = x.shape[dim] // p
    return lax.dynamic_slice_in_dim(x, r * n, n, axis=dim)


def _tp_split_fwd(x, dim, axis):
    return _split(x, dim, axis), None


def _tp_split_bwd(dim, axis, _, ct):
    y = lax.all_gather(ct, axis, tiled=False)
    y = jnp.moveaxis(y, 0, dim)
    s = list(ct.shape)
    s[dim] *= axis_size(axis)
    return (y.reshape(s),)


tp_split_tokens.defvjp(_tp_split_fwd, _tp_split_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def tp_merge_tokens(x: jax.Array, dim: int = 0, axis: str = MODEL_AXIS) -> jax.Array:
    """Rank-sharded along `dim` -> replicated (inverse of tp_split_tokens).

    Forward: all-gather the chunks.  Backward: every rank's consumer is a
    replica, so each rank keeps just its own chunk of the (identical)
    cotangent — NO cross-rank sum (contrast tp_all_gather, whose gathered
    value feeds rank-specific compute and therefore scatter-adds).
    """
    return _merge(x, dim, axis)


def _merge(x, dim, axis):
    y = lax.all_gather(x, axis, tiled=False)
    y = jnp.moveaxis(y, 0, dim)
    s = list(x.shape)
    s[dim] *= axis_size(axis)
    return y.reshape(s)


def _tp_merge_fwd(x, dim, axis):
    return _merge(x, dim, axis), None


def _tp_merge_bwd(dim, axis, _, ct):
    return (_split(ct, dim, axis),)


tp_merge_tokens.defvjp(_tp_merge_fwd, _tp_merge_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def tp_all_gather(x: jax.Array, dim: int, axis: str = MODEL_AXIS) -> jax.Array:
    """All-gather along tensor dim `dim` over the model axis, with the
    scatter-add transpose (correct when the gathered tensor is consumed
    rank-specifically, e.g. KV gathered while Q stays head-sharded)."""
    return _ag(x, dim, axis)


def _ag(x, dim, axis):
    y = lax.all_gather(x, axis, tiled=False)  # (P, ...) leading
    y = jnp.moveaxis(y, 0, dim)
    s = list(x.shape)
    s[dim] *= axis_size(axis)
    return y.reshape(s)


def _tp_ag_fwd(x, dim, axis):
    return _ag(x, dim, axis), None


def _tp_ag_bwd(dim, axis, _, ct):
    p = axis_size(axis)
    s = list(ct.shape)
    ct = ct.reshape(*s[:dim], p, s[dim] // p, *s[dim + 1 :])
    ct = jnp.moveaxis(ct, dim, 0)
    return (lax.psum_scatter(ct, axis, scatter_dimension=0, tiled=False),)


tp_all_gather.defvjp(_tp_ag_fwd, _tp_ag_bwd)
