"""Serving paths: prefill (build caches) and single-token decode for all six
architecture families, wired through the QSDP engine.

FSDP serving story (the paper's technique on the inference side): weights
stay fully sharded at rest and are re-gathered — *quantized* — layer by
layer inside every prefill/decode step.  Decode is therefore dominated by
weight all-gather bytes, exactly the regime where QSDP's wire compression
pays off most; the roofline benchmark quantifies this.  Under a fixed
gather key the encode half of those gathers is a constant:
:meth:`DecodeModel.encode_weights` runs it once, and a step given its
EncodedLayer tree runs only the gather and the decode.

With ``DecodeSpec(rowquant_mlp=True)`` the dense-MLP weights skip the
dequant step entirely: the gathered wire codes are reshaped (K, N) with
their per-bucket affine as (K, N/bucket) segments and fed straight into
the fused ``kernels.ops.rowquant_matmul`` kernel (see
``QSDPEngine.gather_rowquant``).

Cache layouts (global shapes; per-device views inside shard_map):

  attention KV  (L, B, S, n_kv * hd)  P(None, batch?, "model", None)
                ring-buffered along S (full cache == ring that never wraps;
                sliding-window long-context == ring of window size).  The
                heads and head_dim share the last axis: with head_dim
                minor (64 < the TPU's 128-lane tile) the program held the
                whole pool in a padded layout, copied in and out of every
                step at twice its size; a last axis of n_kv * hd keeps the
                stored layout the one the writes use.  Attention splits it
                back into heads after gathering a layer's view.
  mamba conv    (L, B, K-1, tp * Cc)  P(None, batch?, None, "model")
                with Cc = d_inner_local + 2N (each rank stores its own
                slice; the 2N B/C section is per-rank replicated state)
  mamba ssm     (L, B, H, P, N)       P(None, batch?, "model", None, None)
  hybrid        mamba states (all layers) + per-group shared-block KV
                (G, B, S, n_kv * hd)
  audio         decoder self KV ring + static encoder cross KV
                (L, B, S_enc, n_kv, hd) + enc_len scalar

`batch?` is the FSDP axes when the global batch divides them (decode_32k),
or replicated for tiny batches (long_500k's B=1).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..core.qsdp import EncodedLayer
from ..core.quant import QuantizedParam
from ..obs import scoped
from . import attention as attn_mod
from . import layers as L
from . import mamba as mamba_mod
from . import moe as moe_mod
from .transformer import Model

Params = dict[str, jax.Array]
Cache = dict[str, Any]

# dense-MLP weights that may stay in wire-code form through swiglu_mlp
# (rowquant decode and serve.engine.prepare_wire_params share this list)
ROWQUANT_MLP = ("w_gate", "w_up", "w_down")

# key, inside a layer group, of the group's EncodedLayer in a served tree
# (DecodeModel.encode_weights): "layers/" + ENCODED
ENCODED = "@encoded"

# families whose prompts may prefill chunk-at-a-time into the pool cache
# (pure attention stacks; modality/state caches still prefill whole-prompt)
CHUNKED_PREFILL_ARCHS = ("dense", "moe")


@dataclasses.dataclass(frozen=True)
class DecodeSpec:
    """Static decode-time configuration for one (arch, shape) pair."""

    cache_len: int  # ring size (== seq_len, or the sliding window)
    batch_global: int
    batch_sharded: bool  # shard batch over FSDP axes?
    enc_len: int = 0  # audio: encoder memory length (capped)
    # Decode the dense-MLP weights straight from their gathered wire codes
    # through kernels.ops.rowquant_matmul instead of materializing the dense
    # matrix (per-weight fallback when the wire layout doesn't tile rows —
    # see QSDPEngine.rowquant_eligible).
    rowquant_mlp: bool = False
    # Per-slot sampling: decode/prefill take a `sample` tree of per-slot
    # (temperature, top_k, PRNG key) arrays and sample the next token with
    # layers.sample_vocab_parallel instead of the pure greedy argmax.  Rows
    # with temp <= 0 or top_k == 1 still take the greedy path bit-exactly,
    # so a sampling engine at temp 0 matches a greedy engine token-for-token.
    sampling: bool = False
    # Paged KV block pool (serve/kv_pool.py).  kv_block_size > 0 switches
    # the attention cache from one private ring per slot to a shared pool
    # of fixed-size blocks addressed through per-slot block tables:
    # decode_fn / prefill_chunk_fn take a trailing `block_tables` (B, n_log)
    # int32 argument, and the cache kv leaves become (L, R, cache_len, ...)
    # with R pool rows instead of B lanes.  Requires cache_len %
    # kv_block_size == 0, kv_block_size % tp == 0, batch_sharded=False
    # (blocks may be shared across lanes, so the pool is batch-replicated),
    # and a CHUNKED_PREFILL_ARCHS architecture.
    kv_block_size: int = 0
    # Total physical blocks (0 = batch_global * cache_len // kv_block_size,
    # i.e. the same device bytes as the rings it replaces).  Rounded up to
    # whole pool rows of cache_len // kv_block_size blocks each.
    kv_pool_blocks: int = 0
    # Self-speculative decoding: a `draft_bits`-bit forward of the SAME
    # model (weights re-quantized from the resident wire codes) drafts up
    # to `draft_depth` tokens per slot per step, then the serving-precision
    # model scores all of them in ONE pooled `verify_fn` launch and commits
    # the longest prefix the verifier agrees with.  Greedy (and sampled)
    # streams are bit-identical to non-speculative decode by construction:
    # every committed token is produced by the verifier with math
    # elementwise identical to decode_fn.  draft_depth <= 1 disables
    # speculation (plain one-token decode).
    draft_bits: int = 0
    draft_depth: int = 0

    def batch_pspec(self, ms) -> tuple:
        return (ms.fsdp_axes,) if self.batch_sharded else (None,)

    @property
    def paged(self) -> bool:
        return self.kv_block_size > 0

    @property
    def speculative(self) -> bool:
        return self.draft_depth > 1 and self.draft_bits > 0

    @property
    def blocks_per_slot(self) -> int:
        """Logical blocks per slot ring (== physical blocks per pool row)."""
        return self.cache_len // self.kv_block_size

    def pool_rows(self) -> int:
        if not self.paged:
            return self.batch_global
        want = self.kv_pool_blocks or self.batch_global * self.blocks_per_slot
        return -(-want // self.blocks_per_slot)

    def pool_blocks(self) -> int:
        """Physical blocks actually materialized (whole rows)."""
        return self.pool_rows() * self.blocks_per_slot


def make_decode_spec(model: Model, shape, rowquant_mlp: bool = False) -> DecodeSpec:
    """Derive the decode configuration from a ShapeConfig."""
    cfg = model.cfg
    s = shape.seq_len
    if cfg.arch_type in ("ssm",):
        cache_len = 0  # state is O(1); no KV ring
    elif s > 65536 and cfg.long_context == "sliding_window":
        cache_len = cfg.long_context_window
    else:
        cache_len = s
    fsdp = model.ms.fsdp_size
    return DecodeSpec(
        cache_len=cache_len,
        batch_global=shape.global_batch,
        batch_sharded=shape.global_batch % fsdp == 0,
        enc_len=min(4096, s // cfg.enc_frames_ratio) if cfg.arch_type == "audio" else 0,
        rowquant_mlp=rowquant_mlp,
    )


class DecodeModel:
    """Per-device prefill / decode step functions for a bound Model."""

    def __init__(self, model: Model, spec: DecodeSpec):
        self.m = model
        self.spec = spec
        cfg = model.cfg
        ms = model.ms
        self.tp = ms.model_size
        if cfg.has_attention:
            assert spec.cache_len == 0 or spec.cache_len % self.tp == 0, (
                spec.cache_len, self.tp)
        if spec.paged:
            if cfg.arch_type not in CHUNKED_PREFILL_ARCHS:
                raise ValueError(
                    f"paged KV (kv_block_size={spec.kv_block_size}) supports "
                    f"{CHUNKED_PREFILL_ARCHS}, not {cfg.arch_type!r}")
            if spec.batch_sharded:
                raise ValueError(
                    "paged KV requires batch_sharded=False: block tables may "
                    "point any lane at any pool row, so the pool is "
                    "batch-replicated over the data axis")
            if spec.kv_block_size % self.tp:
                raise ValueError(
                    f"kv_block_size ({spec.kv_block_size}) must be a "
                    f"multiple of the model-axis size ({self.tp}) — every "
                    "block is sequence-sharded across all ranks")
            if spec.cache_len % spec.kv_block_size:
                raise ValueError(
                    f"cache_len ({spec.cache_len}) must be a multiple of "
                    f"kv_block_size ({spec.kv_block_size})")
        if spec.speculative and cfg.arch_type not in CHUNKED_PREFILL_ARCHS:
            raise ValueError(
                f"speculative decode (draft_depth={spec.draft_depth}) "
                f"supports {CHUNKED_PREFILL_ARCHS}, not {cfg.arch_type!r}")
        if spec.draft_bits and not 2 <= spec.draft_bits <= 8:
            raise ValueError(f"draft_bits must be in [2, 8], got "
                             f"{spec.draft_bits}")
        self.s_loc = spec.cache_len // self.tp if spec.cache_len else 0
        self.b_loc = (
            spec.batch_global // ms.fsdp_size if spec.batch_sharded else spec.batch_global
        )

    # ------------------------------------------------------------------
    # Cache shapes / pspecs (global views, for dryrun + init)
    # ------------------------------------------------------------------

    def cache_struct(self) -> tuple[Cache, Cache]:
        """Returns (ShapeDtypeStruct tree, PartitionSpec tree) — global."""
        m, cfg, sp = self.m, self.m.cfg, self.spec
        ms = m.ms
        bax = sp.batch_pspec(ms)[0]
        B = sp.batch_global
        structs: Cache = {}
        specs: Cache = {}

        def kv(prefix, layers, s):
            # paged: rows are pool storage, not lanes — never batch-sharded
            rows = sp.pool_rows() if sp.paged else B
            rax = None if sp.paged else bax
            shp = (layers, rows, s, m.acfg.n_kv * cfg.head_dim)
            structs[prefix + "k"] = jax.ShapeDtypeStruct(shp, jnp.bfloat16)
            structs[prefix + "v"] = jax.ShapeDtypeStruct(shp, jnp.bfloat16)
            specs[prefix + "k"] = P(None, rax, "model", None)
            specs[prefix + "v"] = P(None, rax, "model", None)

        if cfg.arch_type in ("dense", "vlm", "moe"):
            kv("", cfg.n_layers, sp.cache_len)
        elif cfg.arch_type == "ssm":
            self._mamba_struct(structs, specs, cfg.n_layers, B, bax)
        elif cfg.arch_type == "hybrid":
            self._mamba_struct(structs, specs, cfg.n_layers, B, bax)
            g = cfg.n_layers // cfg.hybrid_attn_every
            kv("shared_", g, sp.cache_len)
        elif cfg.arch_type == "audio":
            kv("", cfg.n_layers, sp.cache_len)
            shp = (cfg.n_layers, B, sp.enc_len, m.acfg.n_kv, cfg.head_dim)
            structs["ck"] = jax.ShapeDtypeStruct(shp, jnp.bfloat16)
            structs["cv"] = jax.ShapeDtypeStruct(shp, jnp.bfloat16)
            specs["ck"] = P(None, bax, "model", None, None)
            specs["cv"] = P(None, bax, "model", None, None)
        else:
            raise ValueError(cfg.arch_type)
        return structs, specs

    def _mamba_struct(self, structs, specs, layers, B, bax):
        mc = self.m.mcfg
        cc = mc.d_inner_local + 2 * mc.d_state
        structs["conv"] = jax.ShapeDtypeStruct(
            (layers, B, mc.conv_k - 1, self.tp * cc), jnp.float32)
        specs["conv"] = P(None, bax, None, "model")
        structs["ssm"] = jax.ShapeDtypeStruct(
            (layers, B, mc.n_heads, mc.head_dim, mc.d_state), jnp.float32)
        specs["ssm"] = P(None, bax, "model", None, None)

    def init_cache_local(self) -> Cache:
        """Per-device zero cache (inside shard_map) — used by tests."""
        structs, _ = self.cache_struct()
        ms = self.m.ms
        out = {}
        for k, st in structs.items():
            shp = list(st.shape)
            # paged kv rows are pool storage (already final in the struct);
            # every other cache leaf's dim 1 is the (possibly sharded) batch
            if not (self.spec.paged and k not in ("conv", "ssm")):
                shp[1] = self.b_loc
            if k in ("conv",):
                shp[3] //= self.tp
            elif k in ("ssm",):
                shp[2] //= self.tp
            else:  # kv
                shp[2] //= self.tp
            out[k] = jnp.zeros(shp, st.dtype)
        return out

    # ------------------------------------------------------------------
    # Decode (one token)
    # ------------------------------------------------------------------

    def decode_fn(self, params: Params, cache: Cache, tokens: jax.Array,
                  pos: jax.Array, key: jax.Array,
                  sample: Optional[dict] = None,
                  block_tables: Optional[jax.Array] = None
                  ) -> tuple[jax.Array, Cache]:
        """tokens (B_loc,) int32 current input; pos () or (B_loc,) int32 its
        position — a vector gives every batch slot its own sequence position
        (continuous batching).  pos[b] < 0 marks a DEAD lane: its KV write
        is masked out (ring bytes frozen), every cached slot fails the
        validity test (zero attention output), and schedulers pair it with
        temp<=0 so the row burns no Gumbel draws.  Returns
        (next_tokens (B_loc,), new_cache).

        sample (present iff ``spec.sampling``): per-slot sampling state —
        {"temp": (B_loc,) f32, "top_k": (B_loc,) i32, "key": (B_loc, 2) u32}.
        The per-token sampling key is fold_in(slot key, pos + 1) — a pure
        function of the REQUEST's own key and position, so sampled output
        is reproducible across runs and across batch compositions."""
        cfg = self.m.cfg
        pos = jnp.asarray(pos, jnp.int32)
        if pos.ndim == 0:
            pos = jnp.broadcast_to(pos, tokens.shape)
        if self.spec.paged and block_tables is None:
            raise ValueError("paged DecodeSpec: decode_fn needs block_tables")
        emb = self._gather_leaf(params, "embed", key)
        x = L.embed_vocab_parallel(tokens[:, None], emb)[:, 0]  # (B, d)

        cos, sin = self._decode_rope(pos)

        if cfg.arch_type in ("dense", "vlm"):
            x, cache = self._decode_attn_stack(params, "layers", x, cache, pos, cos, sin, key,
                                               mlp="dense", block_tables=block_tables)
        elif cfg.arch_type == "moe":
            x, cache = self._decode_attn_stack(params, "layers", x, cache, pos, cos, sin, key,
                                               mlp="moe", block_tables=block_tables)
        elif cfg.arch_type == "ssm":
            x, cache = self._decode_mamba_stack(params, x, cache, key)
        elif cfg.arch_type == "hybrid":
            x, cache = self._decode_hybrid(params, x, cache, pos, cos, sin, key)
        elif cfg.arch_type == "audio":
            x, cache = self._decode_audio(params, x, cache, pos, cos, sin, key)
        else:
            raise ValueError(cfg.arch_type)

        fn = self._gather_leaf(params, "final_norm", key)
        x = L.rms_norm(x, fn, cfg.norm_eps)
        head = emb if cfg.tie_embeddings else self._gather_leaf(params, "lm_head", key)
        logits = L.vocab_parallel_logits(x, head)
        nxt = self._sample(logits, head.shape[0], sample, pos + 1,
                           valid=attn_mod.slot_valid_mask(pos))
        return nxt.astype(jnp.int32), cache

    def _sample(self, logits, v_local, sample, n_consumed, valid=None):
        """Next-token selection: greedy argmax, or per-slot sampling keyed by
        fold_in(request key, tokens consumed so far) when `sample` is given.
        n_consumed (B,) is the model-visible prefix length, i.e. the global
        position of the token being produced — identical for a request
        whether it runs solo or interleaved, which is what pins sampled
        streams across batch compositions.

        `valid` (B,) bool — dead lanes (attention.slot_valid_mask: the ONE
        sentinel test) are clamped to temp 0 / top-k 1 in the DEVICE step
        itself, so they take the draw-free greedy reduction no matter what
        the host mirrors hold (schedulers also clear them host-side; this
        makes the Gumbel skip a property of the sentinel, not of scheduler
        discipline)."""
        if sample is None:
            return L.greedy_sample_vocab_parallel(logits, v_local)
        temp, top_k = sample["temp"], sample["top_k"]
        if valid is not None:
            temp = jnp.where(valid, temp, 0.0)
            top_k = jnp.where(valid, jnp.asarray(top_k), 1)
        skeys = jax.vmap(jax.random.fold_in)(sample["key"], n_consumed)
        return L.sample_vocab_parallel(logits, v_local, temp, top_k, skeys)

    def _decode_rope(self, pos):
        """pos () or (B,) -> cos/sin broadcastable for decode_new_kv
        ((hd//2,) shared, or (B, hd//2) per-slot)."""
        cfg = self.m.cfg
        if not cfg.has_attention:
            return None, None
        if cfg.rope_mode == "mrope":
            pos3 = jnp.broadcast_to(pos, (3,) + jnp.shape(pos))
            return L.mrope_cos_sin(pos3, cfg.head_dim, cfg.rope_theta, cfg.mrope_sections)
        return L.rope_cos_sin(pos, cfg.head_dim, cfg.rope_theta)

    @scoped("kv_write")
    def _write_token_kv(self, kc_all, vc_all, layer, k1, v1, pos,
                        block_tables=None):
        """Write this token's KV (B, n_kv, hd) into the scan-carried stacked
        cache (L, B, S_loc, n_kv * hd) at (layer, b, ring slot of pos[b]) —
        a token-sized gather + scatter per layer (~KB) instead of re-emitting
        the whole cache as scan ys (§Perf P2-1).  pos is (B,): each batch
        slot writes its OWN ring slot, so interleaved requests at different
        positions never touch each other's cache lines.

        pos[b] < 0 is the DEAD-LANE sentinel (retired / never-filled /
        mid-chunked-prefill slots; ``attention.slot_valid_mask`` is the one
        place that owns the test): the lane's write is masked out entirely,
        so a dead lane's ring bytes are frozen — required by the chunked
        prefill path, which fills a lane's ring incrementally and cannot
        rely on a full-ring splice to wipe garbage writes.

        With ``block_tables`` the cache is the (L, R, S_row, ...) paged
        pool: the ring offset maps through the lane's table to a (pool row,
        row seq index) target instead (``attention.paged_slot``), and
        masked-out lanes redirect to the out-of-range row R and are DROPPED
        — same determinism argument as the chunk path below."""
        b = k1.shape[0]
        k1, v1 = k1.reshape(b, -1), v1.reshape(b, -1)
        if block_tables is not None:
            bl_loc = self.spec.kv_block_size // self.tp
            row, seq, is_mine = attn_mod.paged_slot(
                pos, self.spec.cache_len, self.spec.kv_block_size, bl_loc,
                block_tables)
            mask = is_mine & attn_mod.slot_valid_mask(pos)
            row = jnp.where(mask, row, kc_all.shape[1])  # OOB row => dropped
            kc_all = kc_all.at[layer, row, seq].set(
                k1.astype(kc_all.dtype), mode="drop")
            vc_all = vc_all.at[layer, row, seq].set(
                v1.astype(vc_all.dtype), mode="drop")
            return kc_all, vc_all
        s_loc = kc_all.shape[2]
        idx, is_mine = attn_mod.ring_slot(pos, self.spec.cache_len, s_loc)
        bi = jnp.arange(b)
        mine = (is_mine & attn_mod.slot_valid_mask(pos))[:, None]
        new_k = jnp.where(mine, k1.astype(kc_all.dtype), kc_all[layer, bi, idx])
        new_v = jnp.where(mine, v1.astype(vc_all.dtype), vc_all[layer, bi, idx])
        kc_all = kc_all.at[layer, bi, idx].set(new_k)
        vc_all = vc_all.at[layer, bi, idx].set(new_v)
        return kc_all, vc_all

    def _decode_attn_layer(self, x, w, kc_all, vc_all, layer, pos, cos, sin, mlp,
                           block_tables=None):
        m, cfg = self.m, self.m.cfg
        h = L.rms_norm(x, w["attn_norm"], cfg.norm_eps)
        q_all, k1, v1 = attn_mod.decode_new_kv(h, w, m.acfg, cos, sin)
        kc_all, vc_all = self._write_token_kv(kc_all, vc_all, layer, k1, v1, pos,
                                              block_tables=block_tables)
        kc = lax.dynamic_index_in_dim(kc_all, layer, 0, keepdims=False)
        vc = lax.dynamic_index_in_dim(vc_all, layer, 0, keepdims=False)
        o = attn_mod.decode_attend(q_all, kc, vc, m.acfg, pos, self.spec.cache_len,
                                   block_tables=block_tables,
                                   block_size=self.spec.kv_block_size)
        a = attn_mod.decode_out_proj(o, w, m.acfg, x.dtype)
        x = x + a
        h = L.rms_norm(x, w["mlp_norm"], cfg.norm_eps)
        if mlp == "dense":
            x = x + L.swiglu_mlp(h, w["w_gate"], w["w_up"], w["w_down"])
        else:  # moe — drop-free dispatch: dead/other lanes must never evict
            # a live lane's expert slot (slot isolation; bit-neutral while
            # B * top_k fits the capacity floor, where nothing ever drops)
            y, _ = moe_mod.moe_layer(h, {k: w[k] for k in ("router", "w_gate", "w_up", "w_down")},
                                     m.ecfg, no_drop=True)
            x = x + y
        return x, kc_all, vc_all

    _ROWQUANT_MLP = ROWQUANT_MLP

    def _gather_layer_w(self, prefix, names, lw, lkey, mlp=None):
        """Gather one layer's weights — one coalesced collective for the
        dense/dequantized ones (see QSDPEngine.gather_layer); when rowquant
        decode is enabled the dense-MLP matmul weights come back as
        RowQuantWeights (wire codes + per-bucket affine) gathered separately
        and stay in code form through swiglu_mlp.

        Leaves that arrive as QuantizedParam (quantized train state /
        checkpoint-v2 serving, or a low-bit self-speculative draft built by
        ``serve.engine.make_draft_params``) are all-gathered straight from
        their stored codes — zero re-quantization: dense-MLP matmul weights
        whose buckets tile their rows stay in code form
        (QSDPEngine.gather_rowquant_wire -> rowquant_matmul), everything
        else dequantizes densely through the bits 2-8 kernels
        (QSDPEngine.gather_wire_dequant).

        A layer that arrives as an EncodedLayer (under ``ENCODED``, from
        :meth:`encode_weights`) was encoded once under this same `lkey`:
        only its gather and decode run here.  A QuantizedParam leaf beside
        it overrides its copy of that name (the speculative draft's low-bit
        codes over the served layer)."""
        m = self.m
        enc = [n for n in names if isinstance(lw[n], EncodedLayer)]
        wire = [n for n in names if isinstance(lw[n], QuantizedParam)]
        rq = [n for n in names
              if n not in wire and n not in enc
              and self.spec.rowquant_mlp and mlp == "dense" and n in self._ROWQUANT_MLP]
        out = m.engine.gather_layer(
            f"{prefix}/", {n: lw[n] for n in names
                           if n not in rq and n not in wire and n not in enc},
            lkey)
        pl = len(prefix) + 1
        for n in enc:
            out.update({k[pl:]: v for k, v in m.engine.gather_encoded(lw[n]).items()})
        for n in wire:
            if (n in self._ROWQUANT_MLP
                    and m.engine.rowquant_wire_eligible(f"{prefix}/{n}", lw[n])):
                out[n] = m.engine.gather_rowquant_wire(f"{prefix}/{n}", lw[n])
            else:
                out[n] = m.engine.gather_wire_dequant(f"{prefix}/{n}", lw[n])
        for n in rq:
            out[n] = m.engine.gather_rowquant(f"{prefix}/{n}", lw[n], lkey)
        return out

    def _gather_leaf(self, params, name, key):
        """Gather a non-layer leaf (embed, final_norm, lm_head) under the
        step key: only the gather and decode when it arrives encoded."""
        v = params[name]
        if isinstance(v, EncodedLayer):
            return self.m.engine.gather_encoded(v)[name]
        return self.m.engine.gather(name, v, key)

    # ------------------------------------------------------------------
    # Served weights encoded once, for a fixed gather key
    # ------------------------------------------------------------------

    def encoded_groups(self, params: Params) -> dict[str, tuple[str, ...]]:
        """What :meth:`encode_weights` encodes: {served-tree key: full
        names}.  A layer group is encoded when every step program gathers
        its layer i under ``fold_in(key, i)`` (the attention stacks, the
        pure-SSM stack, the audio decoder) and rowquant decode does not
        split it; its raw leaves (QuantizedParam leaves are already codes)
        go into one buffer per layer under ``"<group>/" + ENCODED``.  The
        non-layer leaves the steps gather under the key itself keep their
        names.  Only what the engine ships as one coalesced buffer
        (``QSDPEngine.layer_coalesced``) is encoded; the rest stays raw and
        keeps its per-step gather."""
        eng, arch = self.m.engine, self.m.cfg.arch_type
        stacks = {"hybrid": (), "audio": ("dec",)}.get(arch, ("layers",))
        groups = {}
        for prefix in () if self.spec.rowquant_mlp else stacks:
            names = tuple(sorted(n for n, v in params.items()
                                 if n.startswith(prefix + "/")
                                 and not isinstance(v, QuantizedParam)))
            if names and eng.layer_coalesced(names):
                groups[f"{prefix}/{ENCODED}"] = names
        for n in ("embed", "final_norm", "lm_head"):
            if (n in params and not isinstance(params[n], QuantizedParam)
                    and eng.layer_coalesced((n,))):
                groups[n] = (n,)
        return groups

    def encode_weights(self, params: Params, key: jax.Array) -> Params:
        """Per-device: the served tree for the fixed gather `key` — each of
        :meth:`encoded_groups` replaced by its EncodedLayer, encoded under
        exactly the keys the step programs fold (``fold_in(key, i)`` for
        layer i, `key` for a non-layer leaf), every other leaf as it was.
        The steps then run only the gather and decode of those bytes, so
        their tokens are bit-identical to the per-step gather's."""
        eng = self.m.engine
        groups = self.encoded_groups(params)
        covered = {n for names in groups.values() for n in names}
        out = {n: v for n, v in params.items() if n not in covered}
        for tkey, names in groups.items():
            leaves = {n: params[n] for n in names}
            lead = leaves[names[0]].shape[:-1]
            if tkey in names:
                wire = eng.encode_layer(names, leaves, key)
            else:
                wire = lax.map(
                    lambda inp: eng.encode_layer(
                        names, inp[1], jax.random.fold_in(key, inp[0])),
                    (jnp.arange(lead[0]), leaves))
            out[tkey] = EncodedLayer(wire.reshape(*lead, -1), names)
        return out

    def _decode_attn_stack(self, params, prefix, x, cache, pos, cos, sin, key, mlp,
                           block_tables=None):
        m = self.m
        grp = m._group(params, prefix)
        names = list(grp.keys())

        def body(carry, inp):
            x, kc_all, vc_all = carry
            idx, lw = inp
            lkey = jax.random.fold_in(key, idx)
            w = self._gather_layer_w(prefix, names, lw, lkey, mlp=mlp)
            x, kc_all, vc_all = self._decode_attn_layer(
                x, w, kc_all, vc_all, idx, pos, cos, sin, mlp,
                block_tables=block_tables)
            return (x, kc_all, vc_all), None

        nl = jax.tree.leaves(grp)[0].shape[0]
        (x, k_new, v_new), _ = lax.scan(
            body, (x, cache["k"], cache["v"]), (jnp.arange(nl), grp))
        cache = dict(cache, k=k_new, v=v_new)
        return x, cache

    # ------------------------------------------------------------------
    # Speculative verify (score k draft tokens in one launch)
    # ------------------------------------------------------------------

    def verify_fn(self, params: Params, cache: Cache, tokens: jax.Array,
                  pos: jax.Array, n_spec: jax.Array, key: jax.Array,
                  sample: Optional[dict] = None,
                  block_tables: Optional[jax.Array] = None
                  ) -> tuple[jax.Array, Cache]:
        """Serving-precision batch-verify of up to K drafted tokens per slot.

        tokens (B_loc, K): token j of slot b is the token fed at position
        pos[b] + j — row [t0, g1, .., g_{K-1}] where t0 is the slot's
        current feed token and g_j its draft chain.  pos (B_loc,) is each
        slot's feed position (< 0 = dead lane); n_spec (B_loc,) how many of
        the K tokens the slot actually runs this step (token j >= n_spec[b]
        is masked to the dead sentinel: no KV write, garbage output).

        Returns (out (B_loc, K), cache): out[b, j] is the model's next
        token after the prefix ..tokens[b, :j+1] — out[b, 0] is exactly
        what decode_fn would emit this step, and out[b, j] is valid
        whenever tokens[b, 1:j+1] were all accepted (each equals the
        verifier's previous output).  Every token's KV is (re)written at
        its own position in serving precision — draft-precision KV left by
        the draft rounds is overwritten — so after committing the accepted
        prefix the cache is bit-identical to sequential decode's.

        BIT-IDENTITY: the per-token math is `_decode_attn_layer` /
        `_sample` on the same (B, .) shapes as decode_fn — layers scan
        outside, the K token positions scan inside (write-before-attend
        per token, so token j attends the serving-precision KV of tokens
        < j), and the final norm/logits/sample stage also runs per token —
        so a committed token is bit-for-bit the token the equivalent
        sequence of decode_fn calls would produce (same gather key, same
        per-layer fold_in, same matmul shapes, same sampling fold)."""
        m, cfg = self.m, self.m.cfg
        if cfg.arch_type not in CHUNKED_PREFILL_ARCHS:
            raise NotImplementedError(
                f"speculative verify supports {CHUNKED_PREFILL_ARCHS}, "
                f"not {cfg.arch_type!r}")
        if self.spec.paged and block_tables is None:
            raise ValueError("paged DecodeSpec: verify_fn needs block_tables")
        b, kmax = tokens.shape
        pos = jnp.asarray(pos, jnp.int32)
        n_spec = jnp.asarray(n_spec, jnp.int32)
        mlp = "moe" if cfg.is_moe else "dense"

        emb = self._gather_leaf(params, "embed", key)
        # (K, B, d): embed is an elementwise take + psum, so embedding all
        # K tokens at once is bit-identical to decode_fn's per-token embed
        xs = jnp.moveaxis(L.embed_vocab_parallel(tokens, emb), 1, 0)
        # per-token positions with the dead sentinel beyond each slot's
        # depth: (K, B); attention.slot_valid_mask owns the < 0 test
        js = jnp.arange(kmax, dtype=jnp.int32)
        pjs = jnp.where((js[:, None] < n_spec[None, :])
                        & attn_mod.slot_valid_mask(pos)[None, :],
                        pos[None, :] + js[:, None], -1)

        grp = m._group(params, "layers")
        names = list(grp.keys())

        def layer_body(carry, inp):
            xs, kc_all, vc_all = carry
            idx, lw = inp
            lkey = jax.random.fold_in(key, idx)
            w = self._gather_layer_w("layers", names, lw, lkey, mlp=mlp)

            def token_body(tc, inp2):
                kc_all, vc_all = tc
                x, pj = inp2
                cos, sin = self._decode_rope(pj)
                x, kc_all, vc_all = self._decode_attn_layer(
                    x, w, kc_all, vc_all, idx, pj, cos, sin, mlp,
                    block_tables=block_tables)
                return (kc_all, vc_all), x

            (kc_all, vc_all), xs = lax.scan(token_body, (kc_all, vc_all),
                                            (xs, pjs))
            return (xs, kc_all, vc_all), None

        nl = jax.tree.leaves(grp)[0].shape[0]
        (xs, k_new, v_new), _ = lax.scan(
            layer_body, (xs, cache["k"], cache["v"]), (jnp.arange(nl), grp))
        cache = dict(cache, k=k_new, v=v_new)

        fn = self._gather_leaf(params, "final_norm", key)
        head = emb if cfg.tie_embeddings else self._gather_leaf(
            params, "lm_head", key)

        def out_body(_, inp2):
            x, pj = inp2
            h = L.rms_norm(x, fn, cfg.norm_eps)
            logits = L.vocab_parallel_logits(h, head)
            nxt = self._sample(logits, head.shape[0], sample, pj + 1,
                               valid=attn_mod.slot_valid_mask(pj))
            return None, nxt

        _, outs = lax.scan(out_body, None, (xs, pjs))  # (K, B)
        return jnp.moveaxis(outs, 0, 1).astype(jnp.int32), cache

    # ------------------------------------------------------------------
    # Chunked prefill (one prompt chunk per slot, fused into the pool)
    # ------------------------------------------------------------------

    @scoped("kv_write")
    def _write_chunk_kv(self, kc_all, vc_all, layer, k1, v1, pos, n_valid,
                        block_tables=None):
        """Write one chunk's KV into the stacked pool cache at each slot's
        own ring offsets.  k1/v1 (B, Lq, n_kv, hd), stored as
        (B, Lq, n_kv * hd); pos (B, Lq) global
        positions; n_valid (B,) valid tokens per slot (0 = lane not
        prefilling).

        ``ring_slot`` indices are LOCAL (slot - owner * s_loc), so two
        tokens of one padded chunk can alias the same local index whenever
        the chunk spans more global slots than one rank holds (Lq > s_loc
        — e.g. padding tokens folding onto a valid token's slot).  Every
        non-owned or invalid token is therefore redirected to the
        out-of-range index s_loc and DROPPED, leaving exactly one scatter
        target per written slot: deterministic, and nothing is written for
        padded tokens or non-prefilling lanes, so live decode slots' (and
        dead lanes') ring bytes are untouched."""
        b, lq = pos.shape
        k1, v1 = k1.reshape(b, lq, -1), v1.reshape(b, lq, -1)
        tok_valid = jnp.arange(lq)[None, :] < n_valid[:, None]
        if block_tables is not None:
            # paged: targets map through the lane's table to (pool row, row
            # seq index); the drop redirect goes to the out-of-range row R.
            # Same single-writer argument: a chunk's positions are distinct
            # mod the window, and the scheduler never hands two lanes the
            # same writable physical block.
            bl_loc = self.spec.kv_block_size // self.tp
            row, seq, is_mine = attn_mod.paged_slot(
                pos, self.spec.cache_len, self.spec.kv_block_size, bl_loc,
                block_tables)
            row = jnp.where(is_mine & tok_valid, row, kc_all.shape[1])
            kc_all = kc_all.at[layer, row, seq].set(k1.astype(kc_all.dtype),
                                                    mode="drop")
            vc_all = vc_all.at[layer, row, seq].set(v1.astype(vc_all.dtype),
                                                    mode="drop")
            return kc_all, vc_all
        s_loc = kc_all.shape[2]
        idx, is_mine = attn_mod.ring_slot(pos, self.spec.cache_len, s_loc)
        bi = jnp.broadcast_to(jnp.arange(b)[:, None], (b, lq))
        idx = jnp.where(is_mine & tok_valid, idx, s_loc)  # s_loc => dropped
        kc_all = kc_all.at[layer, bi, idx].set(k1.astype(kc_all.dtype),
                                               mode="drop")
        vc_all = vc_all.at[layer, bi, idx].set(v1.astype(vc_all.dtype),
                                               mode="drop")
        return kc_all, vc_all

    def _chunk_attn_layer(self, x, w, kc_all, vc_all, layer, pos, n_valid,
                          cos, sin, mlp, block_tables=None):
        """One attention layer over a (B, Lq, d) chunk: write the chunk's KV
        into the ring first, then attend the full ring (the chunk sees its
        own earlier tokens AND every previously-prefilled chunk through the
        cache, exactly like decode sees the prefix)."""
        m, cfg = self.m, self.m.cfg
        b, lq, _ = x.shape
        h = L.rms_norm(x, w["attn_norm"], cfg.norm_eps)
        q_all, k1, v1 = attn_mod.chunk_new_kv(h, w, m.acfg, cos, sin)
        kc_all, vc_all = self._write_chunk_kv(kc_all, vc_all, layer, k1, v1,
                                              pos, n_valid,
                                              block_tables=block_tables)
        kc = lax.dynamic_index_in_dim(kc_all, layer, 0, keepdims=False)
        vc = lax.dynamic_index_in_dim(vc_all, layer, 0, keepdims=False)
        o = attn_mod.chunk_attend(q_all, kc, vc, m.acfg, pos, self.spec.cache_len,
                                  block_tables=block_tables,
                                  block_size=self.spec.kv_block_size)
        hp = o.shape[2]
        a = attn_mod.decode_out_proj(o.reshape(b * lq, hp, cfg.head_dim), w,
                                     m.acfg, x.dtype)
        x = x + a.reshape(b, lq, -1)
        h = L.rms_norm(x, w["mlp_norm"], cfg.norm_eps)
        if mlp == "dense":
            x = x + L.swiglu_mlp(h, w["w_gate"], w["w_up"], w["w_down"])
        else:  # moe — no_drop: expert capacity must never let padding or
            # co-resident lanes evict a valid token's expert slot (slot
            # isolation), so the chunk path dispatches drop-free
            y, _ = moe_mod.moe_layer(
                h.reshape(b * lq, -1),
                {k: w[k] for k in ("router", "w_gate", "w_up", "w_down")},
                m.ecfg, no_drop=True)
            x = x + y.reshape(b, lq, -1)
        return x, kc_all, vc_all

    def _chunk_attn_stack(self, params, prefix, x, cache, pos, n_valid, cos,
                          sin, key, mlp, block_tables=None):
        m = self.m
        grp = m._group(params, prefix)
        names = list(grp.keys())

        def body(carry, inp):
            x, kc_all, vc_all = carry
            idx, lw = inp
            lkey = jax.random.fold_in(key, idx)
            # mlp=None: same gather routing as whole-prompt prefill, so the
            # dequantized weights are bit-identical between the two paths.
            w = self._gather_layer_w(prefix, names, lw, lkey, mlp=None)
            x, kc_all, vc_all = self._chunk_attn_layer(
                x, w, kc_all, vc_all, idx, pos, n_valid, cos, sin, mlp,
                block_tables=block_tables)
            return (x, kc_all, vc_all), None

        nl = jax.tree.leaves(grp)[0].shape[0]
        (x, k_new, v_new), _ = lax.scan(
            body, (x, cache["k"], cache["v"]), (jnp.arange(nl), grp))
        return x, dict(cache, k=k_new, v=v_new)

    def prefill_chunk_fn(self, params: Params, cache: Cache,
                         tokens: jax.Array, offset: jax.Array,
                         n_valid: jax.Array, key: jax.Array,
                         sample: Optional[dict] = None,
                         block_tables: Optional[jax.Array] = None
                         ) -> tuple[jax.Array, Cache]:
        """Offset-aware chunked prefill fused over the WHOLE slot pool.

        tokens (B_loc, Lb): one right-padded prompt chunk per slot, Lb the
        bucket length (the scheduler pads chunks into a bounded bucket set
        so the jit cache holds at most n_buckets traces).  offset (B_loc,)
        is each slot's chunk start position, n_valid (B_loc,) its real
        chunk length (0 = lane not prefilling this step: nothing is read
        from or written to that lane).  Writes each chunk's KV into the
        slot's ring at its offsets and returns (next_tokens (B_loc,),
        cache) — next_tokens is meaningful only for lanes whose chunk ends
        the prompt (sampled from the last valid position with
        n_consumed = offset + n_valid, identical to whole-prompt prefill's
        keying), garbage elsewhere.

        Same gather key / per-layer fold_in as prefill_fn and decode_fn, so
        the dequantized weights are bit-identical to the whole-prompt path.
        Supported for CHUNKED_PREFILL_ARCHS (pure attention stacks)."""
        cfg = self.m.cfg
        if cfg.arch_type not in CHUNKED_PREFILL_ARCHS:
            raise NotImplementedError(
                f"chunked prefill supports {CHUNKED_PREFILL_ARCHS}, "
                f"not {cfg.arch_type!r}")
        if self.spec.paged and block_tables is None:
            raise ValueError("paged DecodeSpec requires block_tables")
        b, lq = tokens.shape
        offset = jnp.asarray(offset, jnp.int32)
        n_valid = jnp.asarray(n_valid, jnp.int32)
        emb = self._gather_leaf(params, "embed", key)
        x = L.embed_vocab_parallel(tokens, emb)  # (B, Lq, d)
        pos = offset[:, None] + jnp.arange(lq, dtype=jnp.int32)[None, :]
        cos, sin = L.rope_cos_sin(pos, cfg.head_dim, cfg.rope_theta)
        x, cache = self._chunk_attn_stack(
            params, "layers", x, cache, pos, n_valid, cos, sin, key,
            mlp="moe" if cfg.is_moe else "dense", block_tables=block_tables)
        fn = self._gather_leaf(params, "final_norm", key)
        last = jnp.clip(n_valid - 1, 0, lq - 1)
        h = L.rms_norm(x[jnp.arange(b), last], fn, cfg.norm_eps)
        head = emb if cfg.tie_embeddings else self._gather_leaf(
            params, "lm_head", key)
        logits = L.vocab_parallel_logits(h, head)
        nxt = self._sample(logits, head.shape[0], sample, offset + n_valid,
                           valid=n_valid > 0)
        return nxt.astype(jnp.int32), cache

    def _decode_mamba_layer(self, x, w, conv, ssm):
        m, cfg = self.m, self.m.cfg
        h = L.rms_norm(x, w["pre_norm"], cfg.norm_eps)
        mw = {k: v for k, v in w.items() if k != "pre_norm"}
        y, conv, ssm = mamba_mod.mamba2_decode(h, mw, m.mcfg, conv, ssm)
        return x + y, conv, ssm

    def _decode_mamba_stack(self, params, x, cache, key, prefix="layers",
                            grp=None, conv=None, ssm=None, key_base=0,
                            layer_offset=0):
        """Scan mamba layers with the stacked (conv, ssm) state as CARRY,
        updating each layer's slice in place (same rationale as the
        attention cache — §Perf P2-1)."""
        m = self.m
        grp = grp if grp is not None else m._group(params, prefix)
        names = list(grp.keys())
        external = conv is not None
        conv = conv if external else cache["conv"]
        ssm = ssm if external else cache["ssm"]

        def body(carry, inp):
            x, conv_all, ssm_all = carry
            idx, lw = inp
            lkey = jax.random.fold_in(key, key_base + idx)
            w = self._gather_layer_w(prefix, names, lw, lkey)
            li = layer_offset + idx
            cv = lax.dynamic_index_in_dim(conv_all, li, 0, keepdims=False)
            st = lax.dynamic_index_in_dim(ssm_all, li, 0, keepdims=False)
            x, cv, st = self._decode_mamba_layer(x, w, cv, st)
            conv_all = lax.dynamic_update_slice_in_dim(
                conv_all, cv[None].astype(conv_all.dtype), li, 0)
            ssm_all = lax.dynamic_update_slice_in_dim(
                ssm_all, st[None].astype(ssm_all.dtype), li, 0)
            return (x, conv_all, ssm_all), None

        nl = jax.tree.leaves(grp)[0].shape[0]
        (x, conv_new, ssm_new), _ = lax.scan(
            body, (x, conv, ssm), (jnp.arange(nl), grp))
        if not external:
            return x, dict(cache, conv=conv_new, ssm=ssm_new)
        return x, conv_new, ssm_new

    def _decode_hybrid(self, params, x, cache, pos, cos, sin, key):
        m, cfg = self.m, self.m.cfg
        every = cfg.hybrid_attn_every
        n_groups, rem = divmod(cfg.n_layers, every)
        grp = m._group(params, "layers")
        main = {k: v[: n_groups * every].reshape(n_groups, every, *v.shape[1:])
                for k, v in grp.items()}
        tail = {k: v[n_groups * every:] for k, v in grp.items()}

        shared_names = [n for n in
                        ["attn_norm", "wq", "wk", "wv", "wo", "bq", "bk", "bv",
                         "mlp_norm", "w_gate", "w_up", "w_down"]
                        if f"shared/{n}" in params]
        mamba_names = list(grp.keys())

        def group_body(carry, inp):
            x, conv_all, ssm_all, kc_all, vc_all = carry
            gidx, gw = inp
            gkey = jax.random.fold_in(key, 1000 + gidx)

            def layer_body(inner, inp2):
                x, conv_all, ssm_all = inner
                li_in_g, lw = inp2
                lkey = jax.random.fold_in(gkey, li_in_g)
                w = m.engine.gather_layer(
                    "layers/", {n: lw[n] for n in mamba_names}, lkey)
                li = gidx * every + li_in_g
                cv = lax.dynamic_index_in_dim(conv_all, li, 0, keepdims=False)
                st = lax.dynamic_index_in_dim(ssm_all, li, 0, keepdims=False)
                x, cv, st = self._decode_mamba_layer(x, w, cv, st)
                conv_all = lax.dynamic_update_slice_in_dim(
                    conv_all, cv[None].astype(conv_all.dtype), li, 0)
                ssm_all = lax.dynamic_update_slice_in_dim(
                    ssm_all, st[None].astype(ssm_all.dtype), li, 0)
                return (x, conv_all, ssm_all), None

            (x, conv_all, ssm_all), _ = lax.scan(
                layer_body, (x, conv_all, ssm_all), (jnp.arange(every), gw))
            skey = jax.random.fold_in(key, 5000 + gidx)
            w = self._gather_layer_w(
                "shared", shared_names,
                {n: params[f"shared/{n}"] for n in shared_names}, skey,
                mlp="dense")
            x, kc_all, vc_all = self._decode_attn_layer(
                x, w, kc_all, vc_all, gidx, pos, cos, sin, "dense")
            return (x, conv_all, ssm_all, kc_all, vc_all), None

        (x, conv_new, ssm_new, k_new, v_new), _ = lax.scan(
            group_body,
            (x, cache["conv"], cache["ssm"], cache["shared_k"], cache["shared_v"]),
            (jnp.arange(n_groups), main))
        if rem:
            x, conv_new, ssm_new = self._decode_mamba_stack(
                params, x, None, jax.random.fold_in(key, 2000), grp=tail,
                conv=conv_new, ssm=ssm_new, layer_offset=n_groups * every)
        return x, dict(cache, conv=conv_new, ssm=ssm_new,
                       shared_k=k_new, shared_v=v_new)

    def _decode_audio(self, params, x, cache, pos, cos, sin, key):
        m, cfg = self.m, self.m.cfg
        grp = m._group(params, "dec")
        names = list(grp.keys())
        enc_len = jnp.asarray(self.spec.enc_len, jnp.int32)

        def body(carry, inp):
            x, kc_all, vc_all = carry
            idx, lw, ck, cv = inp
            lkey = jax.random.fold_in(key, idx)
            w = self._gather_layer_w("dec", names, lw, lkey, mlp="dense")
            h = L.rms_norm(x, w["attn_norm"], cfg.norm_eps)
            q_all, k1, v1 = attn_mod.decode_new_kv(h, w, m.acfg, cos, sin)
            kc_all, vc_all = self._write_token_kv(kc_all, vc_all, idx, k1, v1, pos)
            kc = lax.dynamic_index_in_dim(kc_all, idx, 0, keepdims=False)
            vc = lax.dynamic_index_in_dim(vc_all, idx, 0, keepdims=False)
            o = attn_mod.decode_attend(q_all, kc, vc, m.acfg, pos, self.spec.cache_len)
            x = x + attn_mod.decode_out_proj(o, w, m.acfg, x.dtype)
            h = L.rms_norm(x, w["xattn_norm"], cfg.norm_eps)
            xw = {"wq": w["xwq"], "wk": w["xwk"], "wv": w["xwv"], "wo": w["xwo"]}
            x = x + attn_mod.decode_cross_attention(h, xw, m.acfg, ck, cv, enc_len)
            h = L.rms_norm(x, w["mlp_norm"], cfg.norm_eps)
            x = x + L.swiglu_mlp(h, w["w_gate"], w["w_up"], w["w_down"])
            return (x, kc_all, vc_all), None

        nl = jax.tree.leaves(grp)[0].shape[0]
        (x, k_new, v_new), _ = lax.scan(
            body, (x, cache["k"], cache["v"]),
            (jnp.arange(nl), grp, cache["ck"], cache["cv"]))
        return x, dict(cache, k=k_new, v=v_new)

    # ------------------------------------------------------------------
    # Prefill (build caches from a full prompt)
    # ------------------------------------------------------------------

    def prefill_fn(self, params: Params, batch: dict, key: jax.Array,
                   sample: Optional[dict] = None) -> tuple[jax.Array, Cache]:
        """batch: same leaves as training minus labels.  Returns
        (next_tokens (B_loc,) from the last position, cache).

        sample: optional per-slot sampling state (see decode_fn); the first
        generated token is keyed by fold_in(slot key, prompt length)."""
        m, cfg = self.m, self.m.cfg
        if self.spec.paged:
            raise NotImplementedError(
                "whole-prompt prefill is ring-only; paged specs must use "
                "chunked prefill (prefill_chunk_fn)")
        tokens = batch["tokens"]
        b, s = tokens.shape
        if self.m.cfg.has_attention:
            assert self.spec.cache_len >= s, "prefill prompt exceeds the cache ring"
        emb = self._gather_leaf(params, "embed", key)
        x = L.embed_vocab_parallel(tokens, emb)
        if cfg.arch_type == "vlm":
            x = jnp.where(batch["vision_mask"][..., None],
                          batch["vision_embeds"].astype(x.dtype), x)
        positions = jnp.arange(s)
        cos, sin = m._rope(batch, s)

        cache: Cache = {}
        if cfg.arch_type in ("dense", "vlm", "moe"):
            x, cache = self._prefill_attn_stack(params, "layers", x, key, cos, sin, positions,
                                                mlp="moe" if cfg.is_moe else "dense")
        elif cfg.arch_type == "ssm":
            x, conv, ssm = self._prefill_mamba_stack(params, x, key)
            cache = {"conv": conv, "ssm": ssm}
        elif cfg.arch_type == "hybrid":
            x, cache = self._prefill_hybrid(params, x, key, cos, sin, positions)
        elif cfg.arch_type == "audio":
            x, cache = self._prefill_audio(params, batch, x, key, cos, sin, positions)
        else:
            raise ValueError(cfg.arch_type)

        fn = self._gather_leaf(params, "final_norm", key)
        h = L.rms_norm(x[:, -1], fn, cfg.norm_eps)
        head = emb if cfg.tie_embeddings else self._gather_leaf(params, "lm_head", key)
        logits = L.vocab_parallel_logits(h, head)
        nxt = self._sample(logits, head.shape[0], sample,
                           jnp.full((b,), s, jnp.int32))
        return nxt.astype(jnp.int32), cache

    def _slice_seq(self, kv: jax.Array) -> jax.Array:
        """(B, S, n_kv, hd) full-seq KV -> this rank's S_loc ring chunk in
        the cache's (B, S_loc, n_kv * hd) form (zero-padded when the prompt
        is shorter than the ring)."""
        rank = lax.axis_index("model")
        b, s, nk, hd = kv.shape
        pad = self.spec.cache_len - s
        if pad:
            kv = jnp.pad(kv, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return lax.dynamic_slice(kv, (0, rank * self.s_loc, 0, 0),
                                 (b, self.s_loc, nk, hd)).reshape(b, self.s_loc, -1)

    def _prefill_attn_layer(self, x, w, cos, sin, positions, mlp):
        m, cfg = self.m, self.m.cfg
        h = L.rms_norm(x, w["attn_norm"], cfg.norm_eps)
        a, (kf, vf) = attn_mod.self_attention(h, w, m.acfg, cos, sin, positions,
                                              cache_slice=True)
        x = x + a
        h = L.rms_norm(x, w["mlp_norm"], cfg.norm_eps)
        if mlp == "dense":
            x = x + L.swiglu_mlp(h, w["w_gate"], w["w_up"], w["w_down"])
        else:
            bb, ss, d = h.shape
            y, _ = moe_mod.moe_layer(h.reshape(bb * ss, d),
                                     {k: w[k] for k in ("router", "w_gate", "w_up", "w_down")},
                                     m.ecfg)
            x = x + y.reshape(bb, ss, d)
        kc = self._slice_seq(kf).astype(jnp.bfloat16)
        vc = self._slice_seq(vf).astype(jnp.bfloat16)
        return x, kc, vc

    def _prefill_attn_stack(self, params, prefix, x, key, cos, sin, positions, mlp):
        m = self.m
        grp = m._group(params, prefix)
        names = list(grp.keys())

        def body(x, inp):
            idx, lw = inp
            lkey = jax.random.fold_in(key, idx)
            # mlp=None: rowquant stays a decode-only optimization in prefill,
            # but wire-form (QuantizedParam) leaves still route to their
            # code-form gather.
            w = self._gather_layer_w(prefix, names, lw, lkey, mlp=None)
            x, kc, vc = self._prefill_attn_layer(x, w, cos, sin, positions, mlp)
            return x, (kc, vc)

        nl = jax.tree.leaves(grp)[0].shape[0]
        x, (k, v) = lax.scan(jax.checkpoint(body), x, (jnp.arange(nl), grp))
        return x, {"k": k, "v": v}

    def _prefill_mamba_stack(self, params, x, key, prefix="layers", grp=None, key_base=0):
        m, cfg = self.m, self.m.cfg
        grp = grp if grp is not None else m._group(params, prefix)
        names = list(grp.keys())

        def body(x, inp):
            idx, lw = inp
            lkey = jax.random.fold_in(key, key_base + idx)
            w = self._gather_layer_w(prefix, names, lw, lkey)
            h = L.rms_norm(x, w["pre_norm"], cfg.norm_eps)
            mw = {k: v for k, v in w.items() if k != "pre_norm"}
            y, (cx, cbc, hf) = mamba_mod.mamba2_block(h, mw, m.mcfg, return_state=True)
            conv = jnp.concatenate([cx, cbc.astype(cx.dtype)], axis=-1).astype(jnp.float32)
            return x + y, (conv, hf.astype(jnp.float32))

        nl = jax.tree.leaves(grp)[0].shape[0]
        x, (conv, ssm) = lax.scan(jax.checkpoint(body), x, (jnp.arange(nl), grp))
        return x, conv, ssm

    def _prefill_hybrid(self, params, x, key, cos, sin, positions):
        m, cfg = self.m, self.m.cfg
        every = cfg.hybrid_attn_every
        n_groups, rem = divmod(cfg.n_layers, every)
        grp = m._group(params, "layers")
        main = {k: v[: n_groups * every].reshape(n_groups, every, *v.shape[1:])
                for k, v in grp.items()}
        tail = {k: v[n_groups * every:] for k, v in grp.items()}
        shared_names = [n for n in
                        ["attn_norm", "wq", "wk", "wv", "wo", "bq", "bk", "bv",
                         "mlp_norm", "w_gate", "w_up", "w_down"]
                        if f"shared/{n}" in params]

        def group_body(x, inp):
            gidx, gw = inp
            gkey = jax.random.fold_in(key, 1000 + gidx)
            x, conv, ssm = self._prefill_mamba_stack(params, x, gkey, grp=gw)
            skey = jax.random.fold_in(key, 5000 + gidx)
            w = m.engine.gather_layer(
                "shared/", {n: params[f"shared/{n}"] for n in shared_names}, skey)
            x, kc, vc = self._prefill_attn_layer(x, w, cos, sin, positions, "dense")
            return x, (conv, ssm, kc, vc)

        x, (cm, sm, k, v) = lax.scan(jax.checkpoint(group_body), x, (jnp.arange(n_groups), main))
        conv = cm.reshape(n_groups * every, *cm.shape[2:])
        ssm = sm.reshape(n_groups * every, *sm.shape[2:])
        if rem:
            x, ct, st = self._prefill_mamba_stack(
                params, x, jax.random.fold_in(key, 2000), grp=tail)
            conv = jnp.concatenate([conv, ct], axis=0)
            ssm = jnp.concatenate([ssm, st], axis=0)
        return x, {"conv": conv, "ssm": ssm, "shared_k": k, "shared_v": v}

    def _prefill_audio(self, params, batch, x, key, cos, sin, positions):
        m, cfg = self.m, self.m.cfg
        audio = batch["audio_embeds"].astype(m.compute_dtype)
        b, s_enc, _ = audio.shape
        cos_e, sin_e = L.rope_cos_sin(jnp.arange(s_enc), cfg.head_dim, cfg.rope_theta)
        # offset-3000 encoder key scope — must match Model._loss_encdec
        # (qlint QK201: enc/dec layers share short names; a shared parent
        # key would correlate their quantization noise)
        mem = m._scan_layers(params, "enc", audio,
                             jax.random.fold_in(key, 3000), cos_e, sin_e,
                             jnp.arange(s_enc), m._enc_layer)
        efn = m.engine.gather("enc_final_norm", params["enc_final_norm"], key)
        mem = L.rms_norm(mem, efn, cfg.norm_eps)

        grp = m._group(params, "dec")
        names = list(grp.keys())
        dec = m._dec_layer_factory(mem)

        def body(x, inp):
            idx, lw = inp
            lkey = jax.random.fold_in(key, idx)
            w = self._gather_layer_w("dec", names, lw, lkey)
            # self-attn with cache slice
            h = L.rms_norm(x, w["attn_norm"], cfg.norm_eps)
            a, (kf, vf) = attn_mod.self_attention(h, w, m.acfg, cos, sin, positions,
                                                  cache_slice=True)
            x = x + a
            h = L.rms_norm(x, w["xattn_norm"], cfg.norm_eps)
            xw = {"wq": w["xwq"], "wk": w["xwk"], "wv": w["xwv"], "wo": w["xwo"]}
            x = x + attn_mod.cross_attention(h, mem, xw, m.acfg)
            h = L.rms_norm(x, w["mlp_norm"], cfg.norm_eps)
            x = x + L.swiglu_mlp(h, w["w_gate"], w["w_up"], w["w_down"])
            # cross-KV cache: computed from memory with this layer's weights
            mi = mem
            ck = (mi @ w["xwk"]).reshape(b, s_enc, m.acfg.kv_local, cfg.head_dim)
            cvv = (mi @ w["xwv"]).reshape(b, s_enc, m.acfg.kv_local, cfg.head_dim)
            if m.acfg.kv_mode == "tp":
                ck = lax.all_gather(ck, "model", axis=2, tiled=True)
                cvv = lax.all_gather(cvv, "model", axis=2, tiled=True)
            rank = lax.axis_index("model")
            e_loc = self.spec.enc_len // self.tp
            ck = lax.dynamic_slice(ck, (0, rank * e_loc, 0, 0),
                                   (b, e_loc, m.acfg.n_kv, cfg.head_dim))
            cvv = lax.dynamic_slice(cvv, (0, rank * e_loc, 0, 0),
                                    (b, e_loc, m.acfg.n_kv, cfg.head_dim))
            kc = self._slice_seq(kf).astype(jnp.bfloat16)
            vc = self._slice_seq(vf).astype(jnp.bfloat16)
            return x, (kc, vc, ck.astype(jnp.bfloat16), cvv.astype(jnp.bfloat16))

        nl = jax.tree.leaves(grp)[0].shape[0]
        x, (k, v, ck, cv) = lax.scan(jax.checkpoint(body), x, (jnp.arange(nl), grp))
        return x, {"k": k, "v": v, "ck": ck, "cv": cv}
