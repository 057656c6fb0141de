"""Batched serving engine: jit-compiled prefill + decode steps over the mesh.

The engine owns the shard_map plumbing; `DecodeModel` owns the per-device
math.  Decoding re-gathers quantized weights layer-by-layer every step —
FSDP-style serving — so step latency is collective-bound and QSDP's wire
compression directly reduces it (see benchmarks/fig4_bandwidth_model.py).

A fixed gather key makes the encoded weights a constant:
:func:`encode_served_weights` runs the encode half of every such gather
ONCE (program ``serve_encode_weights``) and returns the served tree, whose
layers are EncodedLayer buffers (each device's u8 codes, not yet
gathered).  Given that tree, every step program only gathers and decodes
(the continuous scheduler serves this way); given raw params it still
encodes per step (``generate``, whose keys may change every step).

With ``DecodeSpec(rowquant_mlp=True)`` the dense-MLP weights additionally
*stay in wire-code form* after the gather: the fused
``kernels.ops.rowquant_matmul`` Pallas kernel consumes the gathered u8
codes + per-bucket affine directly, so the dequantized matrix is never
written to HBM (falls back to the dense path per weight when the wire
buckets don't tile its rows — see ``QSDPEngine.rowquant_eligible``).

Quantized-domain checkpoints (format v2, ``quantized_state=True`` training)
serve with ZERO conversion: :func:`prepare_wire_params` keeps the eligible
dense-MLP weights as their stored wire codes — sliced per layer so the
scan-over-layers can carry them — and the per-step gather ships those
exact bytes into a RowQuantWeight (``QSDPEngine.gather_rowquant_wire``);
everything else is decoded once, host-side, to its exact f32 values.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

from ..core.qsdp import EncodedLayer
from ..core.quant import (QuantConfig, QuantizedParam, qparam_decode,
                          qparam_encode, qparam_split_stack)
from ..models.decode import ROWQUANT_MLP, DecodeModel, DecodeSpec, make_decode_spec
from ..models.transformer import Model
from ..obs import program
from .kv_pool import PoolExhausted, decode_block, encode_block


def prepare_wire_params(model: Model, params: dict) -> dict:
    """Host-side: adapt a (possibly quantized-domain) train-state params
    dict for serving.

    QuantizedParam leaves that are rowquant-eligible dense-MLP weights of a
    dense/VLM stack keep their wire codes — stacked leaves are re-sliced
    per layer (``qparam_split_stack``) so ``lax.scan`` can carry them — and
    are consumed by ``QSDPEngine.gather_rowquant_wire`` with no
    re-quantization.  Every other QuantizedParam is decoded to its exact
    f32 rest-layout values (deterministic)."""
    out = {}
    eng = model.engine
    for name, v in params.items():
        if not isinstance(v, QuantizedParam):
            out[name] = v
            continue
        base = name.rsplit("/", 1)[-1]
        if (model.cfg.arch_type in ("dense", "vlm")
                and name.startswith("layers/")
                and base in ROWQUANT_MLP
                and eng.rowquant_wire_eligible(name, v)):
            out[name] = qparam_split_stack(v) if v.stacked else v
        else:
            out[name] = qparam_decode(v)
    return out


# layer weights the self-speculative draft re-quantizes to `draft_bits`
# (the large matmuls; norms / biases / router / embed / head stay shared
# with the serving params so the two models agree everywhere quantization
# wouldn't pay)
DRAFT_WEIGHTS = ROWQUANT_MLP + ("wq", "wk", "wv", "wo")


def _draft_bucket(n_local: int, bits: int) -> int:
    """Per-leaf draft bucket: the largest power-of-2 divisor of the shard
    size, capped at 256 — small enough that low-bit min-max buckets track
    the weight distribution (draft fidelity is what buys acceptance), and a
    divisor so `qparam_split_stack` stays bucket-aligned."""
    cpb = 8 // bits if 8 % bits == 0 else 1
    b = math.gcd(n_local, 256)
    return b if b % cpb == 0 else 0


def make_draft_params(model: Model, params: dict, draft_bits: int) -> dict:
    """Host-side: the self-speculative DRAFT parameter set — the serving
    params with every large `layers/*` matmul weight replaced by its
    `draft_bits`-bit wire codes (deterministic nearest rounding, so the
    draft — and therefore the acceptance rate — is a pure function of the
    served weights).  Leaves that already ARE wire codes (quantized
    checkpoints / train state) are reused as-is: the draft reads the codes
    already resident for QSDP, no second copy and no re-encode.  Everything
    else (embed, head, norms, biases, router) is the SAME array object as
    the serving params — zero extra bytes.

    The draft engine's per-step gather then ships the low-bit codes and
    consumes them through the bits 2-8 kernels: rowquant matmul where the
    buckets tile the rows, dense dequant otherwise (see
    ``DecodeModel._gather_layer_w``)."""
    if not 2 <= draft_bits <= 8:
        raise ValueError(f"draft_bits must be in [2, 8], got {draft_bits}")
    out = {}
    for name, v in params.items():
        base = name.rsplit("/", 1)[-1]
        if (not name.startswith("layers/") or base not in DRAFT_WEIGHTS
                or isinstance(v, QuantizedParam)):
            out[name] = v  # shared with (or already wire in) the serving set
            continue
        bucket = _draft_bucket(v.shape[-1], draft_bits)
        if not bucket or v.ndim not in (3, 4):
            out[name] = v
            continue
        cfg = QuantConfig(bits=draft_bits, bucket_size=bucket, mode="nearest")
        qp = qparam_encode(v, cfg)
        out[name] = qparam_split_stack(qp) if qp.stacked else qp
    return out


def wire_param_pspecs(model: Model, params: dict) -> dict:
    """Per-leaf PartitionSpecs for a params dict that may mix dense rest
    leaves, (possibly stack-split) QuantizedParam wire leaves and the
    EncodedLayer buffers of a served tree."""
    out = {}
    base = ("model", model.ms.fsdp_axes, None)
    for name, v in params.items():
        if isinstance(v, (QuantizedParam, EncodedLayer)):
            out[name] = P(None, *base) if v.wire.ndim == 4 else P(*base)
        else:
            out[name] = model.specs[name].rest_pspec(model.ms)
    return out


def encode_served_weights(model: Model, mesh, spec: DecodeSpec, params: dict,
                          key: jax.Array) -> dict:
    """The served tree for the fixed gather `key`: one program
    (``serve_encode_weights``) runs ``DecodeModel.encode_weights`` on every
    device, so each layer group and non-layer leaf the steps gather becomes
    an EncodedLayer holding that device's codes under the keys the steps
    fold.  Step programs built for this tree (``ServeEngine(...,
    params=tree)``) take it in place of `params` and only gather and decode
    it; their tokens are bit-identical to the per-step gather's."""
    dm = DecodeModel(model, spec)
    in_ps = wire_param_pspecs(model, params)
    groups = dm.encoded_groups(params)
    covered = {n for names in groups.values() for n in names}
    base = ("model", model.ms.fsdp_axes, None)
    out_ps = {n: p for n, p in in_ps.items() if n not in covered}
    for tkey, names in groups.items():
        out_ps[tkey] = P(None, *base) if params[names[0]].ndim == 4 else P(*base)
    fn = shard_map(dm.encode_weights, mesh=mesh, in_specs=(in_ps, P()),
                   out_specs=out_ps, check_vma=False)
    return jax.jit(program("serve_encode_weights", fn))(params, key)


def prefill_bucket_sizes(chunk: int, n_buckets: int, cache_len: int
                         ) -> tuple[int, ...]:
    """The bounded set of padded chunk lengths for chunked prefill:
    `n_buckets` evenly spaced sizes up to the chunk size (clamped to the KV
    ring so a chunk's ring targets stay collision-free), deduped ascending.
    Every chunk right-pads to the smallest bucket that fits, so the jit
    cache compiles at most `len(buckets)` prefill traces no matter how many
    distinct prompt lengths a trace contains.  A valid token's numerics are
    INDEPENDENT of the bucket it rides in (padded rows add query rows, they
    never enter another row's reductions), so bucketing cannot perturb
    tokens — only trace counts."""
    if chunk <= 0:
        return ()
    top = min(chunk, cache_len) if cache_len else chunk
    n = max(1, min(n_buckets, top))
    return tuple(sorted({max(1, round(top * i / n)) for i in range(1, n + 1)}
                        | {top}))


def prefill_bucket_for(length: int, buckets: tuple[int, ...]) -> int:
    """Smallest bucket that holds a chunk of `length` tokens."""
    for b in buckets:
        if length <= b:
            return b
    raise ValueError(f"chunk of {length} tokens exceeds every bucket "
                     f"{buckets}")


def make_sample_params(temperature: float = 0.0, top_k: int = 0,
                       seed: int = 0, b: int = 1) -> dict:
    """The `sample` tree consumed by DecodeModel.decode_fn/prefill_fn when
    ``DecodeSpec.sampling`` — ONE request's sampling state broadcast over b
    slots.  This is the only place that owns its shape contract."""
    return {
        "temp": jnp.full((b,), temperature, jnp.float32),
        "top_k": jnp.full((b,), top_k, jnp.int32),
        "key": jnp.broadcast_to(jax.random.PRNGKey(seed), (b, 2)),
    }


def greedy_sample_params(b: int) -> dict:
    """Per-slot sampling state that reduces every row to the greedy path
    bit-exactly (temp 0)."""
    return make_sample_params(b=b)


class ServeEngine:
    def __init__(self, model: Model, mesh, spec: DecodeSpec,
                 params: Optional[dict] = None):
        """`params` (optional) is only inspected for its leaf FORMS: pass it
        when serving wire-form (QuantizedParam) leaves so the shard_map
        pspecs match — see :func:`prepare_wire_params`."""
        self.model = model
        self.mesh = mesh
        self.spec = spec
        self.dm = DecodeModel(model, spec)
        ms = model.ms
        self.bax = ms.fsdp_axes if spec.batch_sharded else None
        self._pspecs = (wire_param_pspecs(model, params) if params is not None
                        else model.param_pspecs())
        _, self.cache_pspecs = self.dm.cache_struct()
        self._decode = None
        self._prefill = None
        # chunked prefill: one compiled step per BUCKET length — the
        # continuous scheduler right-pads prompt chunks into a bounded
        # bucket set, so this cache holds at most n_buckets entries.
        self._chunk_steps: dict[int, object] = {}
        # speculative verify: one compiled step per draft depth K actually
        # launched (bounded by spec.draft_depth distinct values)
        self._verify_steps: dict[int, object] = {}
        self._block_ops = None

    # -- jitted steps ---------------------------------------------------------

    def sample_pspecs(self) -> dict:
        """PartitionSpecs for the per-slot `sample` tree (batch-axis arrays)."""
        return {"temp": P(self.bax), "top_k": P(self.bax), "key": P(self.bax)}

    def decode_step(self):
        """jit'd decode: (params, cache, tokens (B,), pos (B,), key
        [, sample]) -> (next_tokens, cache).  pos is PER-SLOT — every batch
        slot advances at its own sequence position, which is what lets the
        continuous-batching scheduler interleave requests mid-decode.  The
        trailing `sample` arg exists iff ``spec.sampling``.

        Paged specs take a block-table arg after pos: (params, cache,
        tokens, pos, block_tables (B, blocks_per_slot) i32, key
        [, sample]) — the table is replicated (every rank resolves the
        same logical->physical block mapping; blocks are seq-sharded, so
        each rank's gather stays rank-local)."""
        if self._decode is None:
            in_specs = [self._pspecs, self.cache_pspecs, P(self.bax),
                        P(self.bax), P()]
            raw = self.dm.decode_fn
            if self.spec.paged:
                in_specs.insert(4, P(None, None))

                def raw(params, cache, tokens, pos, bt, key, *extra):
                    return self.dm.decode_fn(params, cache, tokens, pos,
                                             key, *extra, block_tables=bt)
            if self.spec.sampling:
                in_specs.append(self.sample_pspecs())
            fn = shard_map(
                raw, mesh=self.mesh,
                in_specs=tuple(in_specs),
                out_specs=(P(self.bax), self.cache_pspecs),
                check_vma=False,
            )
            self._decode = jax.jit(program("serve_decode", fn),
                                   donate_argnums=(1,))
        return self._decode

    def prefill_step(self, batch_pspecs: dict):
        if self.spec.paged:
            raise NotImplementedError(
                "whole-prompt prefill is ring-only; paged engines must use "
                "chunked prefill (prefill_chunk_step / generate(prefill_chunk=...))")
        if self._prefill is None:
            in_specs = [self._pspecs, batch_pspecs, P()]
            if self.spec.sampling:
                in_specs.append(self.sample_pspecs())
            fn = shard_map(
                self.dm.prefill_fn, mesh=self.mesh,
                in_specs=tuple(in_specs),
                out_specs=(P(self.bax), self.cache_pspecs),
                check_vma=False,
            )
            self._prefill = jax.jit(program("serve_prefill", fn))
        return self._prefill

    def prefill_chunk_step(self, bucket_len: int):
        """jit'd chunked prefill over the whole slot pool: (params, cache,
        tokens (B, Lb), offset (B,), n_valid (B,), key [, sample]) ->
        (next_tokens (B,), cache).  Compiled once per bucket length Lb;
        writes each prefilling slot's chunk KV into its ring lane in place
        (non-prefilling lanes pass n_valid 0 and are untouched), so it runs
        back-to-back with decode_step over the same donated cache."""
        if bucket_len not in self._chunk_steps:
            in_specs = [self._pspecs, self.cache_pspecs, P(self.bax),
                        P(self.bax), P(self.bax), P()]
            raw = self.dm.prefill_chunk_fn
            if self.spec.paged:
                # paged call shape: (params, cache, tokens, offset, n_valid,
                # block_tables, key [, sample])
                in_specs.insert(5, P(None, None))

                def raw(params, cache, tokens, offset, n_valid, bt, key,
                        *extra):
                    return self.dm.prefill_chunk_fn(
                        params, cache, tokens, offset, n_valid, key, *extra,
                        block_tables=bt)
            if self.spec.sampling:
                in_specs.append(self.sample_pspecs())
            fn = shard_map(
                raw, mesh=self.mesh,
                in_specs=tuple(in_specs),
                out_specs=(P(self.bax), self.cache_pspecs),
                check_vma=False,
            )
            self._chunk_steps[bucket_len] = jax.jit(
                program("serve_prefill_chunk", fn), donate_argnums=(1,))
        return self._chunk_steps[bucket_len]

    def verify_step(self, k: int):
        """jit'd speculative verify over the whole slot pool: (params,
        cache, tokens (B, K), pos (B,), n_spec (B,), key [, sample]) ->
        (out (B, K), cache) — ``DecodeModel.verify_fn`` scores all K
        drafted tokens per slot in ONE pooled launch and (re)writes their
        KV in serving precision.  Paged call shape inserts block_tables
        (B, blocks_per_slot) after n_spec.  Compiled once per draft depth
        K."""
        if k not in self._verify_steps:
            in_specs = [self._pspecs, self.cache_pspecs, P(self.bax),
                        P(self.bax), P(self.bax), P()]
            raw = self.dm.verify_fn
            if self.spec.paged:
                in_specs.insert(5, P(None, None))

                def raw(params, cache, tokens, pos, n_spec, bt, key, *extra):
                    return self.dm.verify_fn(params, cache, tokens, pos,
                                             n_spec, key, *extra,
                                             block_tables=bt)
            if self.spec.sampling:
                in_specs.append(self.sample_pspecs())
            fn = shard_map(
                raw, mesh=self.mesh,
                in_specs=tuple(in_specs),
                out_specs=(P(self.bax), self.cache_pspecs),
                check_vma=False,
            )
            self._verify_steps[k] = jax.jit(program("serve_verify", fn),
                                            donate_argnums=(1,))
        return self._verify_steps[k]

    # -- convenience ------------------------------------------------------------

    def init_cache(self):
        structs, specs = self.dm.cache_struct()
        return {
            k: jax.device_put(jnp.zeros(s.shape, s.dtype), NamedSharding(self.mesh, specs[k]))
            for k, s in structs.items()
        }

    # -- paged block ops (cold tier + copy-on-write) -----------------------------

    def kv_block_ops(self):
        """jit'd (extract, load, copy) over a paged cache's global k/v
        arrays, addressing ONE physical block by id.

        A block's bytes live strided across the model axis (each rank holds
        its block_loc-token slice of every block), so in the global arrays
        block `bid` = row ``bid // bpr``, seq positions
        ``rank * s_loc + (bid % bpr) * block_loc + [0, block_loc)`` per
        rank — token order inside the (L, block_size, n_kv * hd) view is the
        natural position order.  These run OUTSIDE shard_map between steps
        (cold-tier demote/rehydrate, COW forks); they are off the decode
        hot path."""
        if self._block_ops is None:
            sp, tp = self.spec, self.dm.tp
            bs = sp.kv_block_size
            bpr = sp.cache_len // bs
            bl = bs // tp
            s_loc = sp.cache_len // tp
            i = jnp.arange(bs)

            def seq_of(idx):
                return (i // bl) * s_loc + idx * bl + i % bl

            def extract(cache, bid):
                row, seq = bid // bpr, seq_of(bid % bpr)
                return cache["k"][:, row, seq], cache["v"][:, row, seq]

            def load(cache, bid, kblk, vblk):
                row, seq = bid // bpr, seq_of(bid % bpr)
                return dict(
                    cache,
                    k=cache["k"].at[:, row, seq].set(
                        kblk.astype(cache["k"].dtype)),
                    v=cache["v"].at[:, row, seq].set(
                        vblk.astype(cache["v"].dtype)))

            def copy(cache, src, dst):
                kb, vb = extract(cache, src)
                return load(cache, dst, kb, vb)

            self._block_ops = (jax.jit(extract),
                               jax.jit(load, donate_argnums=(0,)),
                               jax.jit(copy, donate_argnums=(0,)))
        return self._block_ops

    def demote_cold_blocks(self, cache, pool, now: int) -> int:
        """Quantized cold tier: re-encode cached (refcount-0 prefix) blocks
        idle past the pool's quant horizon into the `core.quant` wire format
        (host-resident packed codes + per-bucket meta) and free their hot
        blocks.  Returns the number of blocks demoted.  Values seen by
        attention are unchanged until a block is rehydrated — and demotion
        only ever touches blocks no live request references."""
        ids = pool.demotable(now)
        if not ids:
            return 0
        extract, _, _ = self.kv_block_ops()
        for bid in ids:
            kb, vb = extract(cache, jnp.int32(bid))
            cold = encode_block(jax.device_get(kb), jax.device_get(vb),
                                pool.quant_cfg)
            pool.demote(bid, cold, now)
        return len(ids)

    def rehydrate_block(self, cache, pool, key, now: int):
        """Bring a cold prefix block back hot: alloc a block, decode the
        wire codes (bit-exact `core.quant` QDQ values), scatter them in.
        Returns (bid, cache)."""
        bid, cold = pool.rehydrate(key, now)
        _, load, _ = self.kv_block_ops()
        kb, vb = decode_block(cold)
        return bid, load(cache, jnp.int32(bid), kb, vb)

    def generate(self, params, prompt_batch: dict, batch_pspecs: dict,
                 n_tokens: int, key: Optional[jax.Array] = None,
                 sample: Optional[dict] = None, fold_step_keys: bool = True,
                 prefill_chunk: int = 0, prefill_buckets: int = 4):
        """Prefill the prompt then decode n_tokens (greedy unless a `sample`
        tree is given on a ``spec.sampling`` engine).

        fold_step_keys=False reuses ONE gather key for prefill and every
        decode step, i.e. serves a FIXED quantized model: with the paper's
        stochastic-shift weight quantizer the dequantized weights depend on
        the step key, and a fixed key is what makes a request's tokens
        bit-identical between this solo path and the continuous-batching
        scheduler (which interleaves requests at different step indices, so
        no per-step key schedule could line up).

        prefill_chunk=C > 0 prefills through ``prefill_chunk_step`` in
        C-token chunks instead of one whole-prompt launch — the SAME
        computation the chunked continuous scheduler runs, which is what
        makes this the bit-exact solo reference for chunked serving.  (The
        two prefill styles are distinct float paths: chunked attention
        reads earlier chunks back from the bf16 KV ring, whole-prompt flash
        attention never rounds through the cache — each is deterministic
        and composition-independent, but their greedy tokens may differ.)"""
        key = key if key is not None else jax.random.PRNGKey(0)
        b, s = prompt_batch["tokens"].shape
        if sample is not None and not self.spec.sampling:
            raise ValueError(
                "generate() got a sample tree but this engine was built with "
                "DecodeSpec(sampling=False)")
        if self.spec.sampling and sample is None:
            sample = greedy_sample_params(b)
        extra = (sample,) if self.spec.sampling else ()
        bt = ()
        if self.spec.paged:
            if not prefill_chunk:
                raise ValueError(
                    "paged DecodeSpec serves through chunked prefill only; "
                    "pass prefill_chunk=...")
            # solo path: each lane owns its full logical window, laid out as
            # the identity block table — the pool must hold b * bps blocks.
            bps = self.spec.blocks_per_slot
            need, have = b * bps, self.spec.pool_blocks()
            if need > have:
                raise PoolExhausted(
                    f"KV pool exhausted: {b} lanes need {need} blocks but "
                    f"the pool holds {have}; raise --kv-pool-blocks (or "
                    "lower the batch)")
            bt = (jnp.arange(b * bps, dtype=jnp.int32).reshape(b, bps),)
        if prefill_chunk:
            if fold_step_keys:
                raise ValueError(
                    "chunked prefill serves a fixed quantized model; pass "
                    "fold_step_keys=False")
            if self.spec.cache_len and s > self.spec.cache_len:
                # the scheduler rejects these at submit(); enforce the same
                # bound here — a chunk at offset >= cache_len would
                # overwrite ring slots still holding LIVE earlier positions
                # before they are attended (non-causal reads)
                raise ValueError(
                    f"prompt ({s}) exceeds the KV ring "
                    f"({self.spec.cache_len}); chunked prefill cannot "
                    "stream a prompt through a smaller sliding window")
            buckets = prefill_bucket_sizes(prefill_chunk, prefill_buckets,
                                           self.spec.cache_len)
            tokens = prompt_batch["tokens"]
            cache = self.init_cache()
            for o in range(0, s, prefill_chunk):
                clen = min(prefill_chunk, s - o)
                bucket = prefill_bucket_for(clen, buckets)
                chunk = jnp.zeros((b, bucket), jnp.int32)
                chunk = chunk.at[:, :clen].set(tokens[:, o:o + clen])
                nxt, cache = self.prefill_chunk_step(bucket)(
                    params, cache, chunk, jnp.full((b,), o, jnp.int32),
                    jnp.full((b,), clen, jnp.int32), *bt, key, *extra)
        else:
            nxt, cache = self.prefill_step(batch_pspecs)(
                params, prompt_batch, key, *extra)
        out = [nxt]
        dec = self.decode_step()
        for i in range(n_tokens - 1):
            pos = jnp.full((b,), s + i, jnp.int32)
            k = jax.random.fold_in(key, i) if fold_step_keys else key
            nxt, cache = dec(params, cache, nxt, pos, *bt, k, *extra)
            out.append(nxt)
        return jnp.stack(out, axis=1)  # (B, n_tokens)
