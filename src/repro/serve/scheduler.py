"""Continuous-batching request scheduler over the jit-compiled ServeEngine.

The scheduler owns a fixed pool of ``B = spec.batch_global`` decode slots.
Queued requests are admitted into freed slots MID-DECODE, by one of two
admission paths:

* **Blocking (default, ``prefill_chunk=0``)** — admission runs a batch-of-1
  prefill that writes the prompt's KV (the slot's entire ring, so nothing
  stale survives from the previous occupant) and the resulting single-slot
  cache is spliced into the pool cache with a token-addressed
  ``dynamic_update_slice`` along the batch axis.  Every queued prompt
  stalls the pooled decode for its full length, and each distinct prompt
  length costs one jit retrace.

* **Chunked (``prefill_chunk=C``)** — prompts prefill C tokens at a time
  through ``ServeEngine.prefill_chunk_step``: each scheduler step advances
  every *prefilling* slot by at most one chunk (one pooled launch, chunks
  from concurrently-admitting slots ride it together), written straight
  into the slot's KV ring at its chunk offset, alongside the normal pooled
  decode — live slots never wait more than one chunk's latency for a new
  arrival, however long its prompt.  Chunks are right-padded into a small
  set of length buckets (``serve.common.prefill_bucket_sizes``) so the jit
  cache is bounded at n_buckets traces instead of one per distinct prompt
  length.  ``prefill_interleave`` is the fairness knob: chunk launches per
  scheduler step (1 = maximally decode-fair, higher drains the queue
  faster at the cost of longer steps).  Supported for the pure-attention
  families (``models.decode.CHUNKED_PREFILL_ARCHS``).

Dead lanes (never filled, retired, or mid-chunked-prefill) carry the
sentinel ``pos = -1``: the decode step masks their KV-ring write entirely
(bytes frozen), their attention sees zero valid slots, and their sampling
row is clamped to temp 0 / top-k 1 so it takes the draw-free greedy
reduction.  Nothing a dead lane computes can reach a live lane, and the
conformance suite asserts its cache bytes never change.

Invariants this module is built around (enforced by
tests/test_serve_scheduler.py, tests/test_chunked_prefill.py and
scripts/check_serve_sched.py):

* **Slot isolation** — with greedy decoding, a request's output tokens are
  bit-identical whether it runs alone in a batch-of-1 engine
  (``ServeEngine.generate(..., fold_step_keys=False)``, with the MATCHING
  ``prefill_chunk`` so the solo run performs the same chunk decomposition)
  or interleaved with arbitrary other requests here.  Nothing a slot
  computes reads another slot's cache, position, or sampling state, and a
  chunk's numerics are independent of the bucket it is padded into.
  (Chunked and whole-prompt prefill are distinct float paths — chunked
  attention reads earlier chunks back from the bf16 KV ring, flash prefill
  never rounds through the cache — so each admission path is compared
  against its own solo form.)
* **Fixed served model** — the paper's stochastic-shift weight quantizer
  makes the dequantized weights a function of the gather key, so the
  scheduler uses ONE ``gather_key`` for every prefill chunk and decode
  step.  Chunked prefill folds the same per-layer keys as whole-prompt
  prefill, so both paths dequantize bit-identical weights.  With the key
  fixed the encoded weights are a constant, so the scheduler encodes them
  ONCE, when it is built (``serve.engine.encode_served_weights``, counted
  by ``stats()["weight_encodes"]``), and serves that encoded tree in place
  of the f32 params: each step only gathers and decodes it, and its tokens
  are bit-identical to the per-step gather of
  ``ServeEngine.generate(..., fold_step_keys=False)``.
* **Reproducible sampling** — per-request sampling streams are keyed by
  ``fold_in(PRNGKey(request.seed), position)``, a pure function of the
  request itself, so temperature/top-k outputs are identical across runs
  and across batch compositions.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from functools import partial
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..models.decode import CHUNKED_PREFILL_ARCHS, DecodeSpec
from ..models.transformer import Model
from ..obs import span
from .engine import (ServeEngine, encode_served_weights, make_draft_params,
                     make_sample_params, prefill_bucket_for,
                     prefill_bucket_sizes)
from .kv_pool import BlockPool, PoolExhausted, prefix_keys


@dataclasses.dataclass(frozen=True)
class Request:
    """One generation request.

    temperature <= 0 (or top_k == 1) decodes greedily — bit-exact with the
    pure-greedy engine path.  top_k <= 0 means no top-k restriction.
    """
    rid: str
    prompt: Sequence[int]
    max_new_tokens: int
    temperature: float = 0.0
    top_k: int = 0
    seed: int = 0
    eos_id: Optional[int] = None

    @property
    def needs_sampling(self) -> bool:
        return self.temperature > 0.0 and self.top_k != 1


@dataclasses.dataclass(frozen=True)
class TokenEvent:
    """One streamed token: request `rid` produced its `index`-th token."""
    rid: str
    token: int
    index: int
    done: bool


@dataclasses.dataclass
class CompletedRequest:
    """A finished request: its tokens, and the decode-step count and host
    clock (``time.perf_counter``) at each point of its life.  A request's
    time to first token splits into its queue wait (``admit_time -
    submit_time``, waiting for a slot) and its prefill wait
    (``first_token_time - admit_time``, in a slot until token 0)."""
    rid: str
    tokens: np.ndarray  # (n_generated,) int32, includes the EOS if hit
    submit_step: int  # scheduler decode-step count at submit()
    admit_step: int  # ... when the request entered a slot (chunked: when
    # assignment started; blocking: when the prompt's prefill was launched)
    finish_step: int  # ... when the last token was produced
    submit_time: float
    finish_time: float
    first_token_step: int = 0  # ... when token 0 (the prefill token) landed
    first_token_time: float = 0.0  # wall clock of token 0 (TTFT source)
    admit_time: float = 0.0  # wall clock of admit_step


@dataclasses.dataclass
class _Slot:
    req: Request
    n_out: int  # tokens generated so far (incl. the prefill token)
    pf_off: int = 0  # prompt tokens already prefilled (chunked admission)
    prefilling: bool = False  # True until the last chunk lands
    # paged-pool bookkeeping (spec.paged only)
    pkeys: Optional[list] = None  # chained prefix keys of the full prompt blocks
    n_registered: int = 0  # prompt blocks already published to the prefix table
    reserve: int = 0  # worst-case future block allocs still owed to this lane


@partial(jax.jit, donate_argnums=(0,))
def _splice_slot(pool: dict, one: dict, slot: jax.Array) -> dict:
    """Write a batch-of-1 prefill cache into pool slot `slot` (batch axis 1
    on every cache leaf).  A dynamic_update_slice touches ONLY that slot's
    lane, so live slots keep decoding over unchanged bytes."""
    return {
        k: lax.dynamic_update_slice_in_dim(v, one[k].astype(v.dtype), slot, axis=1)
        for k, v in pool.items()
    }


class ContinuousScheduler:
    """Fixed-slot continuous batching over one model / parameter set.

    Parameters
    ----------
    model, mesh, spec, params:
        as for :class:`ServeEngine`; ``spec.batch_global`` is the slot-pool
        size B.  Set ``spec.sampling=True`` to serve temperature/top-k
        requests (greedy requests still take the bit-exact greedy path).
        ``self.params`` is the served tree the step programs take: `params`
        encoded once under `gather_key` (see module docstring).
    gather_key:
        the ONE weight-gather key used for every prefill and decode step
        (see module docstring).  Defaults to PRNGKey(0).
    batch_builder:
        ``tokens (1, s) -> (batch dict, batch pspecs)`` for architectures
        whose prefill needs modality stubs (vlm/audio); defaults to a
        tokens-only batch.  Blocking admission only.
    prefill_chunk:
        0 (default) = blocking batch-of-1 admission; C > 0 = chunked
        admission, at most C prompt tokens prefilled per scheduler step per
        slot (see module docstring).
    prefill_buckets:
        bucket count for chunk right-padding (bounds the chunked jit cache).
    prefill_interleave:
        chunk launches per scheduler step (fairness knob; default 1).
    """

    def __init__(self, model: Model, mesh, spec: DecodeSpec, params: dict,
                 gather_key: Optional[jax.Array] = None,
                 batch_builder: Optional[Callable] = None,
                 prefill_chunk: int = 0, prefill_buckets: int = 4,
                 prefill_interleave: int = 1,
                 kv_quant_bits: int = 0, kv_quant_horizon: int = 0,
                 kv_prefix_share: bool = True):
        self.model = model
        self.mesh = mesh
        self.spec = spec
        self.B = spec.batch_global
        self.gather_key = (gather_key if gather_key is not None
                           else jax.random.PRNGKey(0))
        self.batch_builder = batch_builder or self._default_batch
        # the fixed key makes the encoded weights a constant: encode them
        # once, and serve the encoded tree in place of the f32 params
        self.params = encode_served_weights(model, mesh, spec, params,
                                            self.gather_key)
        self.weight_encodes = 1
        self.engine = ServeEngine(model, mesh, spec, params=self.params)
        self.prefill_chunk = int(prefill_chunk)
        if self.prefill_chunk < 0:
            raise ValueError(
                f"prefill_chunk must be >= 0 (0 = blocking admission), "
                f"got {prefill_chunk}")
        if spec.paged and not self.prefill_chunk:
            raise ValueError(
                "paged DecodeSpec(kv_block_size > 0) requires chunked "
                "admission; pass prefill_chunk > 0")
        self.prefill_interleave = max(int(prefill_interleave), 1)
        if self.prefill_chunk:
            if model.cfg.arch_type not in CHUNKED_PREFILL_ARCHS:
                raise ValueError(
                    f"prefill_chunk requires an arch in "
                    f"{CHUNKED_PREFILL_ARCHS}; {model.cfg.arch_type!r} "
                    "prefills whole-prompt (prefill_chunk=0)")
            self.buckets = prefill_bucket_sizes(
                self.prefill_chunk, prefill_buckets, spec.cache_len)
        else:
            self.buckets = ()
        # batch-of-1 prefill engine (blocking admission): prompts prefill at
        # their exact length (one retrace per distinct length), into the
        # same ring layout
        self._pf_spec = dataclasses.replace(spec, batch_global=1,
                                            batch_sharded=False)
        self.prefill_engine = ServeEngine(model, mesh, self._pf_spec,
                                          params=self.params)

        # self-speculative decoding (spec.draft_depth > 1): a low-bit draft
        # engine shares THIS scheduler's cache and reads the wire codes
        # already resident for QSDP (make_draft_params re-encodes only raw
        # leaves, once, host-side); the serving-precision engine verifies
        # every drafted token before it is committed, so streams stay
        # bit-identical to non-speculative decode.  The draft tree is the
        # served tree with the draft's own low-bit leaves over it, so the
        # two share every encoded byte the draft does not replace.
        self.draft_engine: Optional[ServeEngine] = None
        self.draft_params: Optional[dict] = None
        if spec.speculative:
            draft = make_draft_params(model, params, spec.draft_bits)
            self.draft_params = self.params | {
                n: v for n, v in draft.items() if v is not params[n]}
            self.draft_engine = ServeEngine(model, mesh, spec,
                                            params=self.draft_params)

        # paged pool (spec.paged): block tables map each lane's logical
        # block index -> physical pool block; every valid table entry holds
        # exactly one pool reference (alloc = 1, prefix lookup = +1)
        self.pool: Optional[BlockPool] = None
        self.block_tables: Optional[np.ndarray] = None
        self._reserved = 0  # sum of live lanes' worst-case future allocs
        self.prefix_share = bool(kv_prefix_share)  # A/B knob (bench)
        if spec.paged:
            structs, _ = self.engine.dm.cache_struct()
            ks = structs["k"]
            hot = (int(np.prod((ks.shape[0], spec.kv_block_size)
                               + tuple(ks.shape[3:])))
                   * jnp.dtype(ks.dtype).itemsize * 2)
            self.pool = BlockPool(
                spec.pool_blocks(), spec.kv_block_size,
                quant_bits=kv_quant_bits, quant_horizon=kv_quant_horizon,
                hot_block_bytes=hot)
            self.block_tables = np.full(
                (self.B, spec.blocks_per_slot), -1, np.int32)

        self.cache = self.engine.init_cache()
        self.queue: deque[Request] = deque()
        self.slots: list[Optional[_Slot]] = [None] * self.B
        # per-slot device-step state (host mirrors; assembled each step);
        # every lane starts at the dead sentinel
        self.tok = np.zeros(self.B, np.int32)
        self.pos = np.full(self.B, -1, np.int32)
        self.temp = np.zeros(self.B, np.float32)
        self.top_k = np.ones(self.B, np.int32)
        self.keys = np.zeros((self.B, 2), np.uint32)
        self._submit_meta: dict[str, tuple[int, float]] = {}
        self._admit_meta: dict[str, tuple[int, float]] = {}
        self._first_token: dict[str, tuple[int, float]] = {}
        self._out: dict[str, list[int]] = {}
        self.finished: dict[str, CompletedRequest] = {}
        # stats
        self.step_count = 0
        self.prefill_count = 0
        self.prefill_chunk_count = 0
        self._pf_shapes: set[int] = set()  # distinct compiled prefill shapes
        self._max_pf_tokens = 0  # longest single prefill launch (seq tokens)
        self.occupancy_sum = 0
        self.tokens_generated = 0
        # the two waits that make up a time to first token, summed over
        # requests (seconds on the host clock): submit -> slot, stamped at
        # admission; slot -> token 0, stamped when token 0 is emitted
        self.queue_wait_s = 0.0
        self.prefill_wait_s = 0.0
        self.first_tokens = 0
        # speculative-decoding stats: a "lane step" is one lane's
        # participation in one pooled launch; accepted_per_launch is
        # committed tokens per verify lane step (non-speculative decode is
        # exactly 1 by construction, anything above 1 is bought latency)
        self.decode_launches = 0
        self.draft_launches = 0
        self.draft_lane_steps = 0
        self.verify_launches = 0
        self.spec_tokens = 0
        self.spec_lane_steps = 0

    # -- request intake ------------------------------------------------------

    def submit(self, req: Request) -> None:
        if req.rid in self._out or req.rid in self.finished:
            raise ValueError(f"duplicate request id {req.rid!r}")
        if req.needs_sampling and not self.spec.sampling:
            raise ValueError(
                f"request {req.rid!r} needs sampling but the engine was built "
                "with DecodeSpec(sampling=False)")
        if len(req.prompt) < 1:
            raise ValueError(f"request {req.rid!r}: prompt must be non-empty")
        if self.spec.cache_len and len(req.prompt) > self.spec.cache_len:
            raise ValueError(
                f"request {req.rid!r}: prompt ({len(req.prompt)}) exceeds the "
                f"logical KV window ({self.spec.cache_len})")
        if req.max_new_tokens < 1:
            raise ValueError(f"request {req.rid!r}: max_new_tokens must be >= 1")
        if self.pool is not None and self._lane_need(req) > self.pool.n_blocks:
            # paged admission queues on transient pool pressure, but a
            # request whose worst case exceeds the WHOLE pool can never run
            raise ValueError(
                f"request {req.rid!r}: needs up to {self._lane_need(req)} KV "
                f"blocks but the pool holds {self.pool.n_blocks}; raise "
                "--kv-pool-blocks")
        self._submit_meta[req.rid] = (self.step_count, time.perf_counter())
        self._out[req.rid] = []
        self.queue.append(req)

    # -- internals -----------------------------------------------------------

    @staticmethod
    def _default_batch(tokens: np.ndarray):
        return {"tokens": jnp.asarray(tokens)}, {"tokens": P(None)}

    def _free_slots(self) -> list[int]:
        return [i for i, s in enumerate(self.slots) if s is None]

    def n_active(self) -> int:
        return sum(s is not None for s in self.slots)

    def _clear_lane(self, slot_i: int) -> None:
        """Dead-lane sentinel: pos -1 masks the lane's KV write and zeroes
        its attention; temp 0 / top-k 1 take the draw-free greedy path."""
        self.tok[slot_i] = 0
        self.pos[slot_i] = -1
        self.temp[slot_i] = 0.0
        self.top_k[slot_i] = 1
        self.keys[slot_i] = 0

    def _arm_lane(self, slot_i: int, req: Request, first_pos: int) -> None:
        """Slot enters the decoding phase at position `first_pos`."""
        self.pos[slot_i] = first_pos
        self.temp[slot_i] = req.temperature
        self.top_k[slot_i] = req.top_k
        self.keys[slot_i] = np.asarray(jax.random.PRNGKey(req.seed), np.uint32)

    # -- paged-pool bookkeeping (spec.paged) ---------------------------------

    def _lane_need(self, req: Request) -> int:
        """Worst-case number of pool blocks a lane running `req` ever
        allocates: its whole footprint when it fits the window, the full
        per-slot table otherwise (a wrapping lane COW-forks every shared
        block, so sharing buys it nothing in the worst case)."""
        bs, bps = self.spec.kv_block_size, self.spec.blocks_per_slot
        total = len(req.prompt) + req.max_new_tokens
        return bps if total > self.spec.cache_len else -(-total // bs)

    def _lane_alloc(self, st: _Slot) -> int:
        bid = self.pool.alloc(self.step_count)
        if st.reserve > 0:
            st.reserve -= 1
            self._reserved -= 1
        return bid

    def _prefix_attach(self, slot_i: int, st: _Slot) -> None:
        """Walk the prompt's prefix-key chain and share every consecutive
        hit (hot or cold-rehydrated) read-only; the lane skips prefilling
        the shared tokens.  Sharing is truncated so the skipped span is a
        whole number of prefill chunks AND at least one prompt token
        remains — the lane's remaining chunks then land at the same offsets
        as its solo chunk decomposition, which is what keeps shared-prefix
        streams bit-identical to solo runs."""
        req, C, bs = st.req, self.prefill_chunk, self.spec.kv_block_size
        taken: list[int] = []  # bids, one NEW reference each
        for key in st.pkeys:
            bid = self.pool.lookup(key, self.step_count)
            if bid is None and self.pool.lookup_cold(key) is not None:
                try:
                    bid, self.cache = self.engine.rehydrate_block(
                        self.cache, self.pool, key, self.step_count)
                except PoolExhausted:
                    bid = None
            if bid is None:
                break
            taken.append(bid)
        n = len(taken)
        while n and not ((n * bs) % C == 0 and n * bs < len(req.prompt)):
            n -= 1
        for bid in taken[n:]:
            self.pool.decref(bid, self.step_count)
        for j, bid in enumerate(taken[:n]):
            self.block_tables[slot_i, j] = bid
        st.pf_off = n * bs
        st.n_registered = n

    def _release_lane_blocks(self, slot_i: int) -> None:
        """Retirement: drop the lane's reference on every table entry —
        registered prompt blocks fall into deferred reclaim (LRU cache),
        generated-token blocks free immediately — and return its unused
        reservation."""
        st = self.slots[slot_i]
        if st is not None and st.reserve:
            self._reserved -= st.reserve
            st.reserve = 0
        for b in self.block_tables[slot_i]:
            if b >= 0:
                self.pool.decref(int(b), self.step_count)
        self.block_tables[slot_i] = -1

    def _prepare_decode_block(self, slot_i: int) -> None:
        """Before a lane's next decode write at pos p: make sure the target
        logical block has a writable private physical block.  Fresh logical
        blocks allocate; on ring wrap into a SHARED block (ref > 1) the lane
        COW-forks and device-copies the bytes first (other readers keep the
        original); wrapping a block it registered itself withdraws it from
        the prefix table (its content is about to change)."""
        st = self.slots[slot_i]
        p = int(self.pos[slot_i])
        w, bs = self.spec.cache_len, self.spec.kv_block_size
        j = (p % w) // bs
        b = int(self.block_tables[slot_i, j])
        if b < 0:
            self.block_tables[slot_i, j] = self._lane_alloc(st)
        elif p >= w and p % bs == 0:
            if self.pool.ref(b) > 1:
                new = self.pool.cow_fork(b, self.step_count)
                if st.reserve > 0:
                    st.reserve -= 1
                    self._reserved -= 1
                _, _, copyb = self.engine.kv_block_ops()
                self.cache = copyb(self.cache, jnp.int32(b), jnp.int32(new))
                self.block_tables[slot_i, j] = new
            elif self.pool.is_registered(b):
                self.pool.unregister(b)

    def _prepare_decode_blocks(self, slot_i: int, k: int) -> None:
        """Speculative variant of :meth:`_prepare_decode_block`: the lane may
        write up to `k` positions (p .. p+k-1) this step, so every logical
        block that span touches needs a physical block up front.  The
        scheduler only drafts k > 1 under the no-wrap gate (p + k <= window),
        where all written positions are past the prompt: the lane is the sole
        owner of every target block, so the COW / unregister branches can
        never fire — fresh allocation is the only case."""
        if k <= 1:
            self._prepare_decode_block(slot_i)
            return
        st = self.slots[slot_i]
        p = int(self.pos[slot_i])
        bs = self.spec.kv_block_size
        for j in range(p // bs, (p + k - 1) // bs + 1):
            if self.block_tables[slot_i, j] < 0:
                self.block_tables[slot_i, j] = self._lane_alloc(st)

    def _bt_device(self) -> jax.Array:
        # -1 (unallocated) entries are safe to ship raw: gathers clip them
        # and the position-validity math masks those logical slots, writes
        # only ever target allocated blocks
        return jnp.asarray(self.block_tables)

    def _admit(self, req: Request) -> None:
        """Stamp `req`'s entry into a slot, and add its queue wait."""
        now = time.perf_counter()
        self._admit_meta[req.rid] = (self.step_count, now)
        self.queue_wait_s += now - self._submit_meta[req.rid][1]

    def _emit(self, events: list, slot_i: int, token: int) -> None:
        """Record one generated token for the slot's request; retire the
        slot when the request is done."""
        st = self.slots[slot_i]
        req = st.req
        self._out[req.rid].append(token)
        st.n_out += 1
        self.tokens_generated += 1
        if st.n_out == 1:
            now = time.perf_counter()
            self._first_token[req.rid] = (self.step_count, now)
            self.prefill_wait_s += now - self._admit_meta[req.rid][1]
            self.first_tokens += 1
        done = (st.n_out >= req.max_new_tokens
                or (req.eos_id is not None and token == req.eos_id))
        events.append(TokenEvent(req.rid, token, st.n_out - 1, done))
        if done:
            submit_step, submit_time = self._submit_meta.pop(req.rid)
            ft_step, ft_time = self._first_token.pop(req.rid)
            admit_step, admit_time = self._admit_meta.pop(req.rid)
            self.finished[req.rid] = CompletedRequest(
                rid=req.rid,
                tokens=np.asarray(self._out.pop(req.rid), np.int32),
                submit_step=submit_step,
                admit_step=admit_step,
                finish_step=self.step_count,
                submit_time=submit_time,
                finish_time=time.perf_counter(),
                first_token_step=ft_step,
                first_token_time=ft_time,
                admit_time=admit_time,
            )
            if self.pool is not None:
                self._release_lane_blocks(slot_i)
            self.slots[slot_i] = None
            self._clear_lane(slot_i)
        else:
            self.tok[slot_i] = token

    # -- blocking admission (prefill_chunk == 0) -----------------------------

    def _admit_blocking(self, events: list) -> None:
        """Prefill queued requests into free slots (batch-of-1 prefill, then
        splice the slot cache lane in place).  Each pass dispatches every
        free slot's prefill asynchronously and host-syncs the produced
        tokens ONCE; the outer loop re-scans for slots freed by their own
        prefill token (max_new_tokens == 1 / instant EOS) so a retirement
        never leaves a lane idle while the queue is non-empty."""
        while self.queue:
            free = self._free_slots()
            if not free:
                return
            admitted: list[tuple[int, jax.Array]] = []
            for slot_i in free:
                if not self.queue:
                    break
                req = self.queue.popleft()
                s = len(req.prompt)
                tokens = np.asarray(req.prompt, np.int32)[None, :]
                batch, pspecs = self.batch_builder(tokens)
                extra = ()
                if self.spec.sampling:
                    extra = (make_sample_params(req.temperature, req.top_k,
                                                req.seed),)
                nxt1, cache1 = self.prefill_engine.prefill_step(pspecs)(
                    self.params, batch, self.gather_key, *extra)
                self.prefill_count += 1
                self._pf_shapes.add(s)
                self._max_pf_tokens = max(self._max_pf_tokens, s)
                self.cache = _splice_slot(self.cache, cache1,
                                          jnp.asarray(slot_i, jnp.int32))
                self.slots[slot_i] = _Slot(req=req, n_out=0)
                self._admit(req)
                # slot decode state: the prefill token is fed at position s
                self._arm_lane(slot_i, req, s)
                admitted.append((slot_i, nxt1))
            if not admitted:
                return
            # ONE host sync for the whole pass (the prefills above were all
            # dispatched without a device round-trip between them)
            with span("sched.prefill_sync"):
                toks = jax.device_get([t for _, t in admitted])
            with span("sched.emit"):
                for (slot_i, _), t in zip(admitted, toks):
                    self._emit(events, slot_i, int(np.asarray(t)[0]))

    # -- chunked admission (prefill_chunk > 0) -------------------------------

    def _assign_slots(self) -> None:
        """Move queued requests into free slots as `prefilling` occupants;
        no model work happens here — chunks run in :meth:`_chunk_pass`.

        Paged: admission is additionally gated on pool headroom — a request
        only enters a slot when the pool's reclaimable blocks cover its
        worst-case footprint on top of what already-admitted lanes may
        still claim (so no lane can deadlock mid-flight on an empty pool);
        otherwise it QUEUES, however long its prompt.  Admission then walks
        the prompt's prefix chain and shares every cached block read-only,
        skipping that span's prefill entirely."""
        for slot_i in self._free_slots():
            if not self.queue:
                return
            req = self.queue[0]
            if self.pool is not None:
                need = self._lane_need(req)
                if self.pool.free_blocks - self._reserved < need:
                    return  # pool pressure: keep queued (FIFO, no skip-ahead)
            self.queue.popleft()
            st = _Slot(req=req, n_out=0, prefilling=True)
            self.slots[slot_i] = st
            self._admit(req)
            if self.pool is not None:
                st.pkeys = prefix_keys(req.prompt, self.spec.kv_block_size)
                st.reserve = need
                self._reserved += need
                if self.prefix_share:
                    self._prefix_attach(slot_i, st)
                wraps = (len(req.prompt) + req.max_new_tokens
                         > self.spec.cache_len)
                if st.pf_off and not wraps:
                    # shared blocks the lane will never allocate (a wrapping
                    # lane keeps the full reservation: it may COW-fork them)
                    n_shared = st.pf_off // self.spec.kv_block_size
                    st.reserve -= n_shared
                    self._reserved -= n_shared
            # the lane keeps the dead sentinel until its last chunk lands

    def _chunk_pass(self, events: list) -> None:
        """Advance every prefilling slot by one chunk per launch, at most
        ``prefill_interleave`` launches this step.  All concurrently
        prefilling slots' chunks ride ONE pooled launch, right-padded to
        the smallest shared bucket; lanes whose chunk completes the prompt
        stream their prefill token (one batched host sync) and start
        decoding this very step."""
        for _ in range(self.prefill_interleave):
            lanes = [i for i, s in enumerate(self.slots)
                     if s is not None and s.prefilling]
            if not lanes:
                return
            with span("sched.prefill_launch"):
                nxt, finishing = self._launch_chunk(lanes)
            if finishing:
                with span("sched.prefill_sync"):
                    toks = np.asarray(jax.device_get(nxt))  # one sync per launch
                with span("sched.emit"):
                    for i in finishing:
                        st = self.slots[i]
                        st.prefilling = False
                        self.prefill_count += 1
                        self._arm_lane(i, st.req, len(st.req.prompt))
                        self._emit(events, i, int(toks[i]))
                # a prefill token may retire its request instantly; refill
                # the freed lanes so they start prefilling next launch
                with span("sched.admit"):
                    self._assign_slots()

    def _launch_chunk(self, lanes: list[int]):
        """Host prep and dispatch of one pooled chunk launch over the
        prefilling `lanes`.  Returns the launch's (device) next tokens and
        the lanes whose chunk completes their prompt."""
        clen = {i: min(self.prefill_chunk,
                       len(self.slots[i].req.prompt) - self.slots[i].pf_off)
                for i in lanes}
        bucket = prefill_bucket_for(max(clen.values()), self.buckets)
        tokens = np.zeros((self.B, bucket), np.int32)
        offset = np.zeros(self.B, np.int32)
        n_valid = np.zeros(self.B, np.int32)
        temp = np.zeros(self.B, np.float32)
        top_k = np.ones(self.B, np.int32)
        keys = np.zeros((self.B, 2), np.uint32)
        for i in lanes:
            st = self.slots[i]
            tokens[i, :clen[i]] = st.req.prompt[st.pf_off:st.pf_off + clen[i]]
            offset[i] = st.pf_off
            n_valid[i] = clen[i]
            temp[i] = st.req.temperature
            top_k[i] = st.req.top_k
            keys[i] = np.asarray(jax.random.PRNGKey(st.req.seed), np.uint32)
        bt = ()
        if self.pool is not None:
            bs = self.spec.kv_block_size
            for i in lanes:
                st = self.slots[i]
                for j in range(st.pf_off // bs,
                               -(-(st.pf_off + clen[i]) // bs)):
                    if self.block_tables[i, j] < 0:
                        self.block_tables[i, j] = self._lane_alloc(st)
            bt = (self._bt_device(),)
        extra = ()
        if self.spec.sampling:
            extra = ({"temp": jnp.asarray(temp),
                      "top_k": jnp.asarray(top_k),
                      "key": jnp.asarray(keys)},)
        nxt, self.cache = self.engine.prefill_chunk_step(bucket)(
            self.params, self.cache, jnp.asarray(tokens),
            jnp.asarray(offset), jnp.asarray(n_valid), *bt,
            self.gather_key, *extra)
        self.prefill_chunk_count += 1
        self._pf_shapes.add(bucket)
        self._max_pf_tokens = max(self._max_pf_tokens, bucket)
        finishing = []
        for i in lanes:
            st = self.slots[i]
            st.pf_off += clen[i]
            if self.pool is not None and self.prefix_share:
                # publish every prompt block this chunk completed (all
                # chunk offsets are multiples of the chunk size from 0,
                # so the block bytes are the canonical decomposition's)
                full = min(st.pf_off // self.spec.kv_block_size,
                           len(st.pkeys))
                for j in range(st.n_registered, full):
                    self.pool.register(st.pkeys[j],
                                       int(self.block_tables[i, j]))
                st.n_registered = max(st.n_registered, full)
            if st.pf_off >= len(st.req.prompt):
                finishing.append(i)
        return nxt, finishing

    # -- the scheduler loop --------------------------------------------------

    def step(self) -> list[TokenEvent]:
        """Admit pending requests, then run ONE pooled decode step.  Under
        chunked admission the admit phase runs at most `prefill_interleave`
        chunk launches; under blocking admission it prefills whole prompts
        into every free slot.  Returns the tokens streamed this step
        (admission may also stream admitted requests' first tokens)."""
        events: list[TokenEvent] = []
        if self.prefill_chunk:
            with span("sched.admit"):
                self._assign_slots()
            self._chunk_pass(events)
        else:
            with span("sched.admit"):
                self._admit_blocking(events)
        active = [i for i, s in enumerate(self.slots)
                  if s is not None and not s.prefilling]
        if not active:
            return events
        # per-slot draft depth this step: capped by the remaining token
        # budget and the no-wrap gate — a lane whose window would wrap
        # inside the draft span (pos + k > cache_len) decodes plainly
        # (k = 1) through the COW-aware single-block path, so speculative
        # writes are always sole-owner, never rollback/COW
        n_spec = np.ones(self.B, np.int32)
        if self.spec.speculative:
            for i in active:
                st = self.slots[i]
                k_i = min(self.spec.draft_depth,
                          st.req.max_new_tokens - st.n_out,
                          self.spec.cache_len - int(self.pos[i]))
                n_spec[i] = max(1, k_i)
        kmax = max(int(n_spec[i]) for i in active)
        with span("sched.decode_launch"):
            bt = ()
            if self.pool is not None:
                for i in active:
                    self._prepare_decode_blocks(i, int(n_spec[i]))
                if self.pool.quant_horizon > 0 and self.pool.quant_cfg:
                    # quantized cold tier: idle cached prefix blocks re-encode
                    # into the core.quant wire format, freeing their hot block
                    self.engine.demote_cold_blocks(self.cache, self.pool,
                                                   self.step_count)
                bt = (self._bt_device(),)
            extra = ()
            if self.spec.sampling:
                extra = ({"temp": jnp.asarray(self.temp),
                          "top_k": jnp.asarray(self.top_k),
                          "key": jnp.asarray(self.keys)},)
            if kmax > 1:
                return self._step_speculative(events, active, n_spec, kmax,
                                              bt, extra)
            nxt, self.cache = self.engine.decode_step()(
                self.params, self.cache, jnp.asarray(self.tok),
                jnp.asarray(self.pos), *bt, self.gather_key, *extra)
        with span("sched.decode_sync"):
            nxt = np.asarray(jax.device_get(nxt))
        self.decode_launches += 1
        self.step_count += 1
        self.occupancy_sum += len(active)
        with span("sched.emit"):
            for slot_i in active:
                self.pos[slot_i] += 1
                self._emit(events, slot_i, int(nxt[slot_i]))
        return events

    def _step_speculative(self, events: list, active: list[int],
                          n_spec: np.ndarray, kmax: int,
                          bt: tuple, extra: tuple) -> list[TokenEvent]:
        """Draft up to kmax-1 tokens per lane on the low-bit engine, then
        score the whole window in ONE pooled serving-precision launch.

        Round r of the draft feeds the previous round's token at position
        pos + r (lanes whose depth is exhausted ride along dead, pos -1);
        the drafts write draft-precision KV into the shared cache, every
        slot of which the verifier then overwrites with serving-precision
        KV before any future query can attend to it.  Verification scores
        [tok, d1, .., d_{k-1}] with the exact per-token decode math (same
        weights, same fold_in-keyed sampling streams), commits the longest
        prefix of drafts the serving model agrees with plus the one token
        it produces itself — so every committed token, greedy or sampled,
        is bit-identical to non-speculative decode by construction."""
        rows = [jnp.asarray(self.tok)]
        cur = rows[0]
        dstep = self.draft_engine.decode_step()
        for r in range(kmax - 1):
            live = (self.pos >= 0) & (n_spec - 1 > r)
            pos_r = np.where(live, self.pos + r, -1).astype(np.int32)
            cur, self.cache = dstep(
                self.draft_params, self.cache, cur, jnp.asarray(pos_r),
                *bt, self.gather_key, *extra)
            rows.append(cur)
            self.draft_launches += 1
            self.draft_lane_steps += int(live.sum())
        tok_mat = jnp.stack(rows, axis=1)  # (B, kmax) drafted window
        outs, self.cache = self.engine.verify_step(kmax)(
            self.params, self.cache, tok_mat, jnp.asarray(self.pos),
            jnp.asarray(n_spec), *bt, self.gather_key, *extra)
        self.verify_launches += 1
        self.spec_lane_steps += len(active)
        with span("sched.decode_sync"):
            tok_host, out_host = jax.device_get((tok_mat, outs))
        tok_host = np.asarray(tok_host)
        out_host = np.asarray(out_host)
        self.step_count += 1
        self.occupancy_sum += len(active)
        with span("sched.emit"):
            for i in active:
                k_i = int(n_spec[i])
                a = 0  # accepted drafts: longest prefix the verifier agrees on
                while (a < k_i - 1
                       and int(out_host[i, a]) == int(tok_host[i, a + 1])):
                    a += 1
                for j in range(a + 1):
                    self.pos[i] += 1
                    self.spec_tokens += 1
                    self._emit(events, i, int(out_host[i, j]))
                    if self.slots[i] is None:
                        break  # EOS / budget retirement mid-window
        return events

    def run(self, max_steps: Optional[int] = None,
            on_token: Optional[Callable[[TokenEvent], None]] = None
            ) -> dict[str, CompletedRequest]:
        """Drain the queue: step until every submitted request finished (or
        max_steps decode steps ran).  Returns {rid: CompletedRequest}."""
        steps = 0
        while self.queue or self.n_active():
            if max_steps is not None and steps >= max_steps:
                break
            for ev in self.step():
                if on_token is not None:
                    on_token(ev)
            steps += 1
        return self.finished

    def stats(self) -> dict:
        d = self.pool.capacity_stats() if self.pool is not None else {}
        return d | {
            "decode_steps": self.step_count,
            "prefills": self.prefill_count,
            "prefill_chunks": self.prefill_chunk_count,
            # distinct prefill shapes this scheduler compiled: bucket
            # lengths when chunked (bounded by len(self.buckets)), distinct
            # prompt lengths when blocking (unbounded — the bug chunking
            # fixes); benchmarks/bench_serve.py asserts on it
            "prefill_traces": len(self._pf_shapes),
            # longest prompt-token stretch a single prefill launch processed
            # while live slots waited: the whole prompt under blocking
            # admission, at most one (padded) chunk under chunked admission
            "max_prefill_launch_tokens": self._max_pf_tokens,
            "tokens_generated": self.tokens_generated,
            # times the served weights were encoded: once, when the
            # scheduler was built, however many steps run
            "weight_encodes": self.weight_encodes,
            # time to first token, split and summed over requests (host
            # seconds): queue_wait_s from submit to a slot (added at
            # admission), prefill_wait_s from the slot to token 0 (added
            # with token 0); first_tokens counts the token 0s, so the mean
            # prefill wait is prefill_wait_s / first_tokens
            "queue_wait_s": self.queue_wait_s,
            "prefill_wait_s": self.prefill_wait_s,
            "first_tokens": self.first_tokens,
            "slots": self.B,
            "mean_occupancy": (self.occupancy_sum / self.step_count
                               if self.step_count else 0.0),
            # serving-precision launch accounting, normalized per lane so
            # the numbers are batch-composition independent:
            # launches_per_token = serving-precision lane-steps per decoded
            # token — exactly 1.0 for non-speculative decode (every active
            # lane in every pooled launch emits one token), < 1.0 iff
            # speculation commits more than one token per verify
            "decode_launches": self.decode_launches,
            "draft_launches": self.draft_launches,
            "draft_lane_steps": self.draft_lane_steps,
            "verify_launches": self.verify_launches,
            "spec_tokens": self.spec_tokens,
            "spec_lane_steps": self.spec_lane_steps,
            "lane_steps": self.occupancy_sum,
            "accepted_per_launch": (self.spec_tokens / self.spec_lane_steps
                                    if self.spec_lane_steps else 0.0),
            "launches_per_token": (
                self.occupancy_sum
                / max(1, self.tokens_generated - self.prefill_count)),
            # draft cost per committed token (low-bit lane-steps; the
            # speculative win is real when accepted_per_launch beats
            # 1 + draft_overhead * cost_ratio of the draft forward)
            "draft_overhead": (
                self.draft_lane_steps
                / max(1, self.tokens_generated - self.prefill_count)),
        }
