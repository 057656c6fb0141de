"""Shared serve-engine setup.

launch/serve.py, examples/serve_batched.py and benchmarks/bench_serve.py
all build the same stack — mesh, MeshSpec, model config (registry name or
an explicit ModelConfig), QSDP engine, ring-sized DecodeSpec, ServeEngine,
and a (tokens + modality stubs) prompt batch.  This module is the ONE place
that does it, so every entry point serves the exact same engine.  (The
scripts/check_*.py sanity scripts deliberately hand-build engine-level
variations — batch-sharded pools, solo references — that are the thing
under test.)
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import configs
from ..core.qsdp import MeshSpec, QSDPConfig, step_comm_bytes
from ..launch.mesh import make_mesh
from ..models.config import ModelConfig
from ..models.decode import DecodeSpec
from ..models.transformer import Model
from .engine import ServeEngine


def decode_cache_len(cfg: ModelConfig, prompt_len: int, gen: int, tp: int) -> int:
    """KV ring size for serving `prompt_len + gen` tokens: 0 for pure-SSM
    stacks, else the total rounded up to a multiple of the model-axis size
    (the ring is sequence-sharded over it)."""
    if cfg.arch_type == "ssm":
        return 0
    ring = prompt_len + gen
    return ring + (-ring) % tp


def make_serve_spec(cfg: ModelConfig, ms: MeshSpec, batch: int,
                    prompt_len: int, gen: int, *, sampling: bool = False,
                    rowquant_mlp: bool = False,
                    batch_sharded: Optional[bool] = None,
                    kv_block_size: int = 0,
                    kv_pool_blocks: int = 0,
                    draft_bits: int = 0,
                    draft_depth: int = 0) -> DecodeSpec:
    """The DecodeSpec every serve entry point derives from (arch, shape).

    kv_block_size > 0 turns on the paged KV pool (block-table addressed;
    requires chunked prefill and an unsharded batch axis — block tables can
    point any lane at any pool row); kv_pool_blocks sizes the pool
    (0 = one full logical window per slot).  draft_bits + draft_depth > 1
    turn on self-speculative decoding (a draft_bits rowquant forward drafts
    up to draft_depth tokens per slot per step, batch-verified by the
    serving-precision model in one launch)."""
    if batch_sharded is None:
        batch_sharded = batch % ms.fsdp_size == 0 and not kv_block_size
    cache_len = decode_cache_len(cfg, prompt_len, gen, ms.model_size)
    if kv_block_size and cache_len:
        # the logical window must tile into whole blocks, and each block
        # must split evenly across the seq-sharded model axis
        kv_block_size += (-kv_block_size) % ms.model_size
        cache_len += (-cache_len) % kv_block_size
    return DecodeSpec(
        cache_len=cache_len,
        batch_global=batch,
        batch_sharded=batch_sharded,
        enc_len=max(prompt_len // cfg.enc_frames_ratio, ms.model_size)
        if cfg.arch_type == "audio" else 0,
        sampling=sampling,
        rowquant_mlp=rowquant_mlp,
        kv_block_size=kv_block_size if cache_len else 0,
        kv_pool_blocks=kv_pool_blocks,
        draft_bits=draft_bits,
        draft_depth=draft_depth,
    )


@dataclasses.dataclass
class ServeSetup:
    """Everything a serve driver needs, built identically everywhere."""
    cfg: ModelConfig
    model: Model
    params: dict
    mesh: object
    ms: MeshSpec
    spec: DecodeSpec
    engine: ServeEngine

    def decode_gather_bytes(self) -> int:
        """Analytic per-device weight-gather wire bytes of ONE decode step
        (FSDP serving re-gathers every param once per step)."""
        return step_comm_bytes(self.model.engine, gathers_per_param=1,
                               reduces_per_param=0)["weight_gather"]


def build_serve_setup(arch, *, data_par: int = 1, model_par: int = 1,
                      smoke: bool = True, qsdp: Optional[QSDPConfig] = None,
                      batch: int = 8, prompt_len: int = 32, gen: int = 16,
                      seed: int = 0, sampling: bool = False,
                      rowquant_mlp: bool = False,
                      batch_sharded: Optional[bool] = None,
                      kv_block_size: int = 0,
                      kv_pool_blocks: int = 0,
                      draft_bits: int = 0,
                      draft_depth: int = 0) -> ServeSetup:
    """Build (mesh, model, params, DecodeSpec, ServeEngine) for serving.
    `arch` is a registry name (resolved smoke/full) or a ModelConfig."""
    mesh = make_mesh((data_par, model_par), ("data", "model"))
    ms = MeshSpec(axes=("data", "model"), shape=(data_par, model_par))
    if isinstance(arch, ModelConfig):
        cfg = arch
    else:
        cfg = configs.get_smoke(arch) if smoke else configs.get_config(arch)
    qsdp = qsdp if qsdp is not None else QSDPConfig()
    model = Model(cfg, ms, qsdp)
    params = model.init_params(jax.random.PRNGKey(seed))
    spec = make_serve_spec(cfg, ms, batch, prompt_len, gen, sampling=sampling,
                           rowquant_mlp=rowquant_mlp,
                           batch_sharded=batch_sharded,
                           kv_block_size=kv_block_size,
                           kv_pool_blocks=kv_pool_blocks,
                           draft_bits=draft_bits,
                           draft_depth=draft_depth)
    engine = ServeEngine(model, mesh, spec)
    return ServeSetup(cfg=cfg, model=model, params=params, mesh=mesh, ms=ms,
                      spec=spec, engine=engine)


def make_prompt_batch(cfg: ModelConfig, spec: DecodeSpec, ms: MeshSpec,
                      tokens: jax.Array, *, seed: int = 1):
    """(tokens (B, S) [+ modality stubs], matching pspecs) for prefill."""
    tokens = jnp.asarray(tokens, jnp.int32)
    b, s = tokens.shape
    bax = ms.fsdp_axes if spec.batch_sharded else None
    batch = {"tokens": tokens}
    pspecs = {"tokens": P(bax)}
    if cfg.arch_type == "vlm":
        batch.update(vision_embeds=jnp.zeros((b, s, cfg.d_model), jnp.bfloat16),
                     vision_mask=jnp.zeros((b, s), bool),
                     positions=jnp.broadcast_to(jnp.arange(s), (3, b, s)))
        pspecs.update(vision_embeds=P(bax), vision_mask=P(bax),
                      positions=P(None, bax))
    if cfg.arch_type == "audio":
        batch["audio_embeds"] = 0.1 * jax.random.normal(
            jax.random.PRNGKey(seed), (b, spec.enc_len, cfg.d_model),
            jnp.bfloat16)
        pspecs["audio_embeds"] = P(bax)
    return batch, pspecs


def scheduler_batch_builder(cfg: ModelConfig, spec: DecodeSpec, ms: MeshSpec):
    """A ContinuousScheduler `batch_builder` for any architecture family:
    builds the batch-of-1 prefill batch (tokens + modality stubs)."""
    pf_spec = dataclasses.replace(spec, batch_global=1, batch_sharded=False)

    def build(tokens):
        return make_prompt_batch(cfg, pf_spec, ms, tokens)

    return build


def make_scheduler(setup: ServeSetup, *, gather_key=None,
                   prefill_chunk: int = 0, prefill_buckets: int = 4,
                   prefill_interleave: int = 1,
                   kv_quant_bits: int = 0, kv_quant_horizon: int = 0,
                   kv_prefix_share: bool = True):
    """The ContinuousScheduler every serve entry point builds from a
    ServeSetup: launcher, bench, and examples get the same batch_builder
    (modality stubs included) and the same chunked-admission knobs.  The
    kv_quant_* knobs configure the paged pool's quantized cold tier (paged
    setups only)."""
    from .scheduler import ContinuousScheduler
    return ContinuousScheduler(
        setup.model, setup.mesh, setup.spec, setup.params,
        gather_key=gather_key,
        batch_builder=scheduler_batch_builder(setup.cfg, setup.spec, setup.ms),
        prefill_chunk=prefill_chunk, prefill_buckets=prefill_buckets,
        prefill_interleave=prefill_interleave,
        kv_quant_bits=kv_quant_bits, kv_quant_horizon=kv_quant_horizon,
        kv_prefix_share=kv_prefix_share)
