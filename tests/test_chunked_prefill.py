"""Property/conformance suite for chunked, length-bucketed prefill
(serve/scheduler.py ``prefill_chunk > 0`` + models/decode.py
``prefill_chunk_fn``).

The load-bearing invariant carries over from the blocking admission path:
with greedy decoding, a request's output tokens are BIT-IDENTICAL whether
it runs alone in a batch-of-1 engine
(``ServeEngine.generate(..., fold_step_keys=False, prefill_chunk=C)`` —
the solo reference runs the SAME chunk decomposition) or interleaved
under the chunked scheduler — across chunk sizes {1, 7, 64}, prompt
lengths straddling bucket boundaries, mid-prefill retirements of *other*
slots, and KV-ring wrap.  A request's stream depends only on (prompt,
weights, chunk size): never on bucket padding (asserted directly), nor on
co-resident traffic, admission timing, or pool dirtiness.  (Chunked and
whole-prompt prefill are distinct float paths — chunked attention reads
earlier chunks back from the bf16 KV ring, flash prefill never rounds
through the cache — so each admission path is compared against ITS solo
form, exactly as any chunked-prefill serving system must.)  Plus the
bounded-retrace guarantee (at most n_buckets compiled prefill shapes for
arbitrarily many distinct prompt lengths) and the dead-lane contract (a
retired or never-filled lane's cache bytes are frozen — the pos = -1
sentinel masks its ring write).  The scheduler's chunk and decode steps
take its weights encoded once for its gather key, while the solo reference
re-encodes them every step from the f32 params, so each solo comparison
also pins the resident path to the per-step gather.

Engines and schedulers are cached at module scope (compiles dominate);
reusing one scheduler across tests is deliberate — chunked admission never
wipes a lane's ring, so a dirty pool is exactly the state the validity
masking must survive.
"""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st
from jax.sharding import PartitionSpec as P

from repro.core.qsdp import MeshSpec, QSDPConfig
from repro.launch.mesh import make_mesh
from repro.models.config import ModelConfig
from repro.models.decode import DecodeSpec
from repro.models.transformer import Model
from repro.serve import (ContinuousScheduler, Request, ServeEngine,
                         make_sample_params, prefill_bucket_for,
                         prefill_bucket_sizes)

MS = MeshSpec(axes=("data", "model"), shape=(1, 1))
MESH = make_mesh((1, 1), ("data", "model"))
GATHER_KEY = jax.random.PRNGKey(7)
RING = 32
VOCAB = 256
CHUNKS = (1, 7, 64)
_RID = itertools.count()


def _cfg(family: str) -> ModelConfig:
    base = dict(name=f"chunk-{family}", arch_type=family, n_layers=2,
                d_model=64, vocab_size=VOCAB, n_heads=4, n_kv_heads=2,
                head_dim=16, d_ff=128)
    if family == "moe":
        base.update(n_experts=4, moe_top_k=2)
    return ModelConfig(**base)


_models: dict = {}
_scheds: dict = {}
_solo: dict = {}
_solo_out: dict = {}


def model_and_params(family):
    if family not in _models:
        m = Model(_cfg(family), MS, QSDPConfig(min_quant_size=256))
        _models[family] = (m, m.init_params(jax.random.PRNGKey(0)))
    return _models[family]


def scheduler(family, slots, chunk, buckets=4, interleave=1
              ) -> ContinuousScheduler:
    key = (family, slots, chunk, buckets, interleave)
    if key not in _scheds:
        m, params = model_and_params(family)
        spec = DecodeSpec(cache_len=RING, batch_global=slots,
                          batch_sharded=False, sampling=True)
        _scheds[key] = ContinuousScheduler(
            m, MESH, spec, params, gather_key=GATHER_KEY,
            prefill_chunk=chunk, prefill_buckets=buckets,
            prefill_interleave=interleave)
    return _scheds[key]


def solo_tokens(family, prompt, gen, chunk, temperature=0.0, top_k=0, seed=0):
    """Reference: the request alone in a batch-of-1 engine running the SAME
    chunk decomposition (chunk=0 = whole-prompt prefill), fixed gather key
    (memoized across scenarios)."""
    key = (family, tuple(prompt), gen, chunk, temperature, top_k, seed)
    if key in _solo_out:
        return _solo_out[key]
    if family not in _solo:
        m, _ = model_and_params(family)
        spec = DecodeSpec(cache_len=RING, batch_global=1,
                          batch_sharded=False, sampling=True)
        _solo[family] = ServeEngine(m, MESH, spec)
    _, params = model_and_params(family)
    sample = make_sample_params(temperature, top_k, seed)
    out = _solo[family].generate(
        params, {"tokens": jnp.asarray(np.asarray(prompt, np.int32)[None])},
        {"tokens": P(None)}, n_tokens=gen, key=GATHER_KEY, sample=sample,
        fold_step_keys=False, prefill_chunk=chunk)
    _solo_out[key] = np.asarray(jax.device_get(out))[0]
    return _solo_out[key]


def run_scheduler(sched, reqs):
    for r in reqs:
        sched.submit(r)
    done = sched.run()
    return [done[r.rid].tokens for r in reqs]


def make_requests(rng, n, max_gen=5, min_plen=1, max_plen=10):
    """Prompt lengths drawn uniformly over [min_plen, max_plen] — for every
    chunk size under test that range straddles bucket boundaries (and for
    chunk 7 it crosses the multi-chunk threshold)."""
    return [Request(rid=f"c{next(_RID)}",
                    prompt=rng.integers(0, VOCAB,
                                        size=int(rng.integers(
                                            min_plen, max_plen + 1))).tolist(),
                    max_new_tokens=int(rng.integers(1, max_gen + 1)))
            for _ in range(n)]


# ---------------------------------------------------------------------------
# Bucket policy (pure host-side properties)
# ---------------------------------------------------------------------------


@settings(max_examples=50, deadline=None)
@given(chunk=st.integers(1, 256), n=st.integers(1, 8),
       ring=st.integers(1, 512))
def test_bucket_policy_properties(chunk, n, ring):
    """Buckets are ascending, at most n (+dedup slack never exceeds n),
    capped at min(chunk, ring); every chunk length <= the cap lands in a
    bucket >= it."""
    buckets = prefill_bucket_sizes(chunk, n, ring)
    top = min(chunk, ring)
    assert buckets == tuple(sorted(set(buckets)))
    assert len(buckets) <= n
    assert buckets[-1] == top
    for length in range(1, top + 1):
        b = prefill_bucket_for(length, buckets)
        assert length <= b <= top


def test_bucket_policy_rejects_oversized_chunk():
    with pytest.raises(ValueError, match="exceeds"):
        prefill_bucket_for(9, (4, 8))


# ---------------------------------------------------------------------------
# Tentpole invariant: chunked-interleaved greedy == solo same-chunk batch-of-1
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("family,chunk",
                         [("dense", 1), ("dense", 7), ("dense", 64),
                          ("moe", 7)])
@settings(max_examples=3, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_chunked_greedy_matches_solo(family, chunk, seed):
    """Random prompt lengths (straddling every bucket boundary) and
    generation lengths, admitted mid-decode through the chunked scheduler:
    every greedy request's tokens match its solo batch-of-1 run (same chunk
    decomposition) token-for-token, for chunk sizes 1 (token-at-a-time), 7
    (multi-chunk with ragged tails), and 64 (single chunk > every prompt).
    This compares the resident path (weights encoded once by the scheduler)
    with the per-step gather of ``generate(fold_step_keys=False)``."""
    rng = np.random.default_rng(seed)
    sched = scheduler(family, 2, chunk)
    reqs = make_requests(rng, int(rng.integers(3, 6)))
    outs = run_scheduler(sched, reqs)
    assert sched.stats()["weight_encodes"] == 1
    for r, got in zip(reqs, outs):
        ref = solo_tokens(family, r.prompt, r.max_new_tokens, chunk)
        np.testing.assert_array_equal(got, ref,
                                      err_msg=f"{family} chunk={chunk} {r.rid}")


def test_chunked_sampled_requests_reproducible():
    """Sampled requests admitted through chunked prefill match their solo
    sampled run (the final chunk keys its draw by fold_in(seed, prompt_len),
    identical to whole-prompt prefill) and replay identically."""
    sched = scheduler("dense", 2, 4)
    rng = np.random.default_rng(17)
    reqs = [Request(rid=f"c{next(_RID)}",
                    prompt=rng.integers(0, VOCAB, size=pl).tolist(),
                    max_new_tokens=g, temperature=t, top_k=k, seed=s)
            for pl, g, t, k, s in [(9, 4, 1.1, 4, 3), (5, 3, 0.0, 0, 0),
                                   (7, 4, 0.8, 0, 9)]]
    outs = run_scheduler(sched, reqs)
    for r, got in zip(reqs, outs):
        np.testing.assert_array_equal(
            got, solo_tokens("dense", r.prompt, r.max_new_tokens, 4,
                             r.temperature, r.top_k, r.seed))
    renamed = [Request(rid=f"c{next(_RID)}", prompt=r.prompt,
                       max_new_tokens=r.max_new_tokens,
                       temperature=r.temperature, top_k=r.top_k, seed=r.seed)
               for r in reqs]
    for a, b in zip(outs, run_scheduler(sched, renamed)):
        np.testing.assert_array_equal(a, b)


def test_mid_prefill_retirement_of_other_slots():
    """A slot retired by its own prefill token (max_new_tokens == 1) while a
    neighbour is mid-prefill: the neighbour's remaining chunks, and the
    request refilled into the freed lane, are unaffected."""
    rng = np.random.default_rng(23)
    sched = scheduler("dense", 2, 2)
    reqs = [Request(rid=f"c{next(_RID)}",
                    prompt=rng.integers(0, VOCAB, size=4).tolist(),
                    max_new_tokens=1),  # retires off its prefill token
            Request(rid=f"c{next(_RID)}",
                    prompt=rng.integers(0, VOCAB, size=9).tolist(),
                    max_new_tokens=4),  # 5 chunks: mid-prefill at retirement
            Request(rid=f"c{next(_RID)}",
                    prompt=rng.integers(0, VOCAB, size=6).tolist(),
                    max_new_tokens=3)]  # refills the freed lane
    outs = run_scheduler(sched, reqs)
    for r, got in zip(reqs, outs):
        np.testing.assert_array_equal(
            got, solo_tokens("dense", r.prompt, r.max_new_tokens, 2),
            err_msg=r.rid)


def test_ring_wrap_composes_with_chunked_prefill():
    """Sliding-window model: chunked prefill into a ring the generation then
    wraps, through slots that are freed and reused — must match the solo
    run (which wraps the same ring)."""
    cfg = ModelConfig(name="chunk-wrap", arch_type="dense", n_layers=2,
                      d_model=64, vocab_size=VOCAB, n_heads=4, n_kv_heads=2,
                      head_dim=16, d_ff=128, sliding_window=0,
                      long_context="sliding_window", long_context_window=16)
    m = Model(cfg, MS, QSDPConfig(min_quant_size=256))
    params = m.init_params(jax.random.PRNGKey(0))
    spec = DecodeSpec(cache_len=16, batch_global=2, batch_sharded=False,
                      sampling=True)
    sched = ContinuousScheduler(m, MESH, spec, params, gather_key=GATHER_KEY,
                                prefill_chunk=3)
    solo = ServeEngine(
        m, MESH, DecodeSpec(cache_len=16, batch_global=1, batch_sharded=False,
                            sampling=True))
    rng = np.random.default_rng(3)
    # gen 14 from prompt 8: positions reach 21 > ring 16 — wraps; 3 requests
    # on 2 slots forces reuse after a wrapped generation
    reqs = [Request(rid=f"c{next(_RID)}",
                    prompt=rng.integers(0, VOCAB, size=8).tolist(),
                    max_new_tokens=g) for g in (14, 6, 14)]
    outs = run_scheduler(sched, reqs)
    for r, got in zip(reqs, outs):
        ref = solo.generate(
            params, {"tokens": jnp.asarray(np.asarray(r.prompt, np.int32)[None])},
            {"tokens": P(None)}, n_tokens=r.max_new_tokens, key=GATHER_KEY,
            fold_step_keys=False, prefill_chunk=3)
        np.testing.assert_array_equal(got, np.asarray(jax.device_get(ref))[0])


def test_tokens_independent_of_bucket_padding():
    """A valid chunk token's numerics never depend on the bucket it is
    padded into: the same request through bucket sets {C} (every chunk
    padded to C) and the default graded set yields bit-identical tokens —
    padding adds query rows, it cannot enter another row's reductions."""
    m, params = model_and_params("dense")
    spec = DecodeSpec(cache_len=RING, batch_global=1, batch_sharded=False,
                      sampling=True)
    eng = ServeEngine(m, MESH, spec)
    rng = np.random.default_rng(43)
    prompt = rng.integers(0, VOCAB, size=9).tolist()
    tb = {"tokens": jnp.asarray(np.asarray(prompt, np.int32)[None])}
    outs = [np.asarray(jax.device_get(eng.generate(
        params, tb, {"tokens": P(None)}, n_tokens=4, key=GATHER_KEY,
        fold_step_keys=False, prefill_chunk=4, prefill_buckets=nb)))[0]
        for nb in (1, 4)]
    np.testing.assert_array_equal(outs[0], outs[1])


# ---------------------------------------------------------------------------
# Bounded retraces
# ---------------------------------------------------------------------------


def test_trace_count_bounded_by_buckets():
    """>= 8 distinct prompt lengths compile at most n_buckets chunked
    prefill traces (the blocking path compiles one per distinct length —
    the retrace bug chunking fixes)."""
    m, params = model_and_params("dense")
    rng = np.random.default_rng(29)
    plens = list(range(1, 10))  # 9 distinct lengths
    reqs = [Request(rid=f"c{next(_RID)}",
                    prompt=rng.integers(0, VOCAB, size=pl).tolist(),
                    max_new_tokens=2) for pl in plens]
    sched = scheduler("dense", 2, 8, buckets=4)
    base = sched.stats()
    run_scheduler(sched, reqs)
    st_ = sched.stats()
    assert st_["prefill_traces"] <= 4, st_
    # the REAL jit cache (one compiled fn per bucket) obeys the same bound
    assert len(sched.engine._chunk_steps) <= 4
    assert st_["prefills"] - base["prefills"] == len(plens)
    assert st_["prefill_chunks"] > base["prefill_chunks"]

    blocking = scheduler("dense", 2, 0)
    run_scheduler(blocking, [
        Request(rid=f"c{next(_RID)}", prompt=r.prompt,
                max_new_tokens=r.max_new_tokens) for r in reqs])
    assert blocking.stats()["prefill_traces"] == len(plens)


def test_chunked_validation_and_interleave():
    """prefill_chunk rejects non-attention stacks; prefill_interleave > 1
    drains multi-chunk prompts in fewer scheduler steps, same tokens."""
    mcfg = ModelConfig(name="chunk-ssm", arch_type="ssm", n_layers=2,
                       d_model=64, vocab_size=VOCAB, ssm_state=16,
                       ssm_head_dim=16, ssm_chunk=8)
    m = Model(mcfg, MS, QSDPConfig(min_quant_size=256))
    spec = DecodeSpec(cache_len=0, batch_global=2, batch_sharded=False)
    with pytest.raises(ValueError, match="prefill_chunk"):
        ContinuousScheduler(m, MESH, spec, m.init_params(jax.random.PRNGKey(0)),
                            prefill_chunk=4)

    rng = np.random.default_rng(31)
    prompt = rng.integers(0, VOCAB, size=10).tolist()
    fair = scheduler("dense", 2, 2)
    eager = scheduler("dense", 2, 2, interleave=4)
    a = run_scheduler(fair, [Request(rid=f"c{next(_RID)}", prompt=prompt,
                                     max_new_tokens=4)])[0]
    b = run_scheduler(eager, [Request(rid=f"c{next(_RID)}", prompt=prompt,
                                      max_new_tokens=4)])[0]
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(a, solo_tokens("dense", prompt, 4, 2))


def test_moe_no_drop_isolates_tokens():
    """moe_layer(no_drop=True): a token's output is independent of every
    other token in the batch — capacity can never evict it.  The standard
    capacity path demonstrably leaks (earlier tokens' routing decides which
    later assignments are dropped), which is why the chunked-prefill and
    pooled-decode serve paths dispatch drop-free."""
    from jax import shard_map
    from repro.models.moe import MoEConfig, moe_layer

    cfg = MoEConfig(n_experts=4, top_k=2, d_model=16, d_ff=32, tp=1,
                    capacity_factor=0.25)  # overflows at t=32 (c floors at 8)
    rng = np.random.default_rng(7)
    w = {"router": jnp.asarray(rng.normal(size=(16, 4)), jnp.float32),
         "w_gate": jnp.asarray(0.1 * rng.normal(size=(4, 16, 32)), jnp.float32),
         "w_up": jnp.asarray(0.1 * rng.normal(size=(4, 16, 32)), jnp.float32),
         "w_down": jnp.asarray(0.1 * rng.normal(size=(4, 32, 16)), jnp.float32)}
    x1 = rng.normal(size=(32, 16)).astype(np.float32)
    x2 = x1.copy()
    x2[:16] = rng.normal(size=(16, 16))  # perturb the OTHER (earlier) tokens

    def run(no_drop, x):
        fn = shard_map(lambda xx: moe_layer(xx, w, cfg, no_drop=no_drop)[0],
                       mesh=MESH, in_specs=(P(),), out_specs=P(),
                       check_vma=False)
        return np.asarray(jax.device_get(jax.jit(fn)(jnp.asarray(x))))

    np.testing.assert_array_equal(run(True, x1)[16:], run(True, x2)[16:])
    assert not np.array_equal(run(False, x1)[16:], run(False, x2)[16:]), \
        "expected capacity drops to leak across tokens at this overflow"


# ---------------------------------------------------------------------------
# Dead-lane contract
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("chunk", [0, 4])
def test_dead_lane_bytes_frozen(chunk):
    """A retired lane's cache bytes never change while other lanes decode:
    the pos = -1 sentinel masks the dead lane's ring write under BOTH
    admission paths — the direct form of 'a dead lane's bytes never
    influence a live lane' (plus the live lane still matches solo)."""
    sched = scheduler("dense", 2, chunk)
    rng = np.random.default_rng(37)
    # dirty both lanes, then retire everything
    run_scheduler(sched, make_requests(rng, 3, max_gen=3))
    assert sched.n_active() == 0
    snap = {k: np.asarray(jax.device_get(v))[:, 1].copy()
            for k, v in sched.cache.items()}
    # one request -> lane 0; lane 1 stays dead (dirty) for the whole run
    req = Request(rid=f"c{next(_RID)}",
                  prompt=rng.integers(0, VOCAB, size=6).tolist(),
                  max_new_tokens=4)
    out = run_scheduler(sched, [req])[0]
    np.testing.assert_array_equal(out,
                                  solo_tokens("dense", req.prompt,
                                              req.max_new_tokens, chunk))
    for k, v in sched.cache.items():
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(v))[:, 1], snap[k],
            err_msg=f"dead lane {k} bytes changed (chunk={chunk})")


def test_immediate_retire_refills_same_admission_pass():
    """Blocking admission: a slot retired by its own prefill token
    (max_new_tokens == 1) is re-scanned and refilled within the SAME
    admission pass — three 1-token requests through one slot finish with
    ZERO pooled decode steps."""
    sched = scheduler("dense", 1, 0)
    base = sched.stats()
    rng = np.random.default_rng(41)
    reqs = [Request(rid=f"c{next(_RID)}",
                    prompt=rng.integers(0, VOCAB, size=5).tolist(),
                    max_new_tokens=1) for _ in range(3)]
    outs = run_scheduler(sched, reqs)
    st_ = sched.stats()
    assert st_["decode_steps"] - base["decode_steps"] == 0, st_
    assert st_["prefills"] - base["prefills"] == 3
    for r, got in zip(reqs, outs):
        np.testing.assert_array_equal(got, solo_tokens("dense", r.prompt, 1, 0))


# ---------------------------------------------------------------------------
# Paged KV block pool (kv_block_size > 0): the same isolation invariant must
# hold with the per-slot rings replaced by block-table indirection into a
# shared pool — across placements, prefix-cache hits, pool fragmentation
# after churn, wrap-driven copy-on-write, and co-resident traffic.  On one
# device the paged gather/scatter visits the same logical addresses as the
# ring, so the RING solo engine doubles as the reference: these tests also
# pin paged == ring at tp=1 (the (2,4) form lives in check_serve_sched.py).
# ---------------------------------------------------------------------------

PAGED_BS = 8  # block size == chunk size keeps shared prefixes chunk-aligned


def paged_scheduler(slots=3, pool_blocks=0, share=True) -> ContinuousScheduler:
    key = ("paged", slots, pool_blocks, share)
    if key not in _scheds:
        m, params = model_and_params("dense")
        spec = DecodeSpec(cache_len=RING, batch_global=slots,
                          batch_sharded=False, sampling=True,
                          kv_block_size=PAGED_BS, kv_pool_blocks=pool_blocks)
        _scheds[key] = ContinuousScheduler(
            m, MESH, spec, params, gather_key=GATHER_KEY,
            prefill_chunk=PAGED_BS, prefill_buckets=3, kv_prefix_share=share)
    return _scheds[key]


@settings(max_examples=3, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_paged_interleaved_matches_solo(seed):
    """Random requests through the paged scheduler: every greedy stream
    matches the solo batch-of-1 run (same chunk decomposition) bit-for-bit,
    wherever the allocator happened to place each block.  This compares the
    resident path (weights encoded once by the scheduler) with the per-step
    gather of ``generate(fold_step_keys=False)``."""
    rng = np.random.default_rng(seed)
    sched = paged_scheduler()
    reqs = make_requests(rng, int(rng.integers(3, 6)))
    outs = run_scheduler(sched, reqs)
    assert sched.stats()["weight_encodes"] == 1
    for r, got in zip(reqs, outs):
        np.testing.assert_array_equal(
            got, solo_tokens("dense", r.prompt, r.max_new_tokens, PAGED_BS),
            err_msg=f"paged {r.rid}")
    sched.pool.check_invariants()


def test_paged_sampled_requests_match_solo():
    """Sampled requests under the paged pool reproduce their solo sampled
    runs — block indirection must not perturb the per-request keying.  This
    compares the resident path with the per-step gather of
    ``generate(fold_step_keys=False)``."""
    sched = paged_scheduler()
    rng = np.random.default_rng(19)
    reqs = [Request(rid=f"c{next(_RID)}",
                    prompt=rng.integers(0, VOCAB, size=pl).tolist(),
                    max_new_tokens=g, temperature=t, top_k=k, seed=s)
            for pl, g, t, k, s in [(9, 4, 1.1, 4, 3), (5, 3, 0.0, 0, 0),
                                   (7, 4, 0.8, 0, 9)]]
    outs = run_scheduler(sched, reqs)
    for r, got in zip(reqs, outs):
        np.testing.assert_array_equal(
            got, solo_tokens("dense", r.prompt, r.max_new_tokens, PAGED_BS,
                             r.temperature, r.top_k, r.seed))


def test_paged_prefix_sharing_bit_exact():
    """Requests sharing a 2-block system prompt: sharing engages
    (prefix_hits > 0, shared blocks skip their prefill chunks) and every
    stream still matches BOTH its solo run and the same trace through a
    sharing-disabled scheduler — the prefix cache is invisible in tokens."""
    rng = np.random.default_rng(23)
    system = rng.integers(0, VOCAB, size=2 * PAGED_BS).tolist()
    mk = lambda: Request(  # noqa: E731
        rid=f"c{next(_RID)}",
        prompt=system + rng.integers(
            0, VOCAB, size=int(rng.integers(1, 5))).tolist(),
        max_new_tokens=int(rng.integers(2, 5)))
    reqs = [mk() for _ in range(5)]
    sched = paged_scheduler()
    base_hits = sched.pool.stats["prefix_hits"]
    base_chunks = sched.stats()["prefill_chunks"]
    outs = run_scheduler(sched, reqs)
    hits = sched.pool.stats["prefix_hits"] - base_hits
    assert hits > 0, sched.pool.stats
    # shared blocks skip whole chunks: 5 requests x 3 chunks would be 15
    launches = sched.stats()["prefill_chunks"] - base_chunks
    assert launches < 3 * len(reqs), launches
    for r, got in zip(reqs, outs):
        np.testing.assert_array_equal(
            got, solo_tokens("dense", r.prompt, r.max_new_tokens, PAGED_BS),
            err_msg=r.rid)
    noshare = paged_scheduler(share=False)
    renamed = [Request(rid=f"c{next(_RID)}", prompt=r.prompt,
                       max_new_tokens=r.max_new_tokens) for r in reqs]
    for a, b in zip(outs, run_scheduler(noshare, renamed)):
        np.testing.assert_array_equal(a, b)
    sched.pool.check_invariants()


def test_paged_fragmentation_churn():
    """Waves of mixed-length requests fragment the free list (retirements
    interleave with admissions, cached prefix blocks evict on demand);
    tokens stay placement-independent and the pool neither leaks nor
    double-frees."""
    sched = paged_scheduler()
    rng = np.random.default_rng(29)
    for wave in range(3):
        reqs = make_requests(rng, 5, max_gen=4)
        outs = run_scheduler(sched, reqs)
        for r, got in zip(reqs, outs):
            np.testing.assert_array_equal(
                got, solo_tokens("dense", r.prompt, r.max_new_tokens,
                                 PAGED_BS), err_msg=f"wave {wave} {r.rid}")
        sched.pool.check_invariants()
    assert sched.pool.blocks_in_use == 0  # every retirement released blocks


def test_paged_wrap_cow_preserves_shared_blocks():
    """Sliding-window wrap into a SHARED prefix block: the wrapping writer
    must copy-on-write (readers keep the original bytes) or unregister (sole
    owner), and every wrapped stream still matches its solo run."""
    cfg = ModelConfig(name="paged-wrap", arch_type="dense", n_layers=2,
                      d_model=64, vocab_size=VOCAB, n_heads=4, n_kv_heads=2,
                      head_dim=16, d_ff=128, sliding_window=0,
                      long_context="sliding_window", long_context_window=16)
    m = Model(cfg, MS, QSDPConfig(min_quant_size=256))
    params = m.init_params(jax.random.PRNGKey(0))
    # 6 blocks (not the default 4): with only 4, r1's admission pins the
    # shared block out of the cached tier and reserves its full wrap
    # footprint, so r2 would queue and only ever see an UNREGISTERED block
    # (r1 wraps as sole owner).  6 lets both admit concurrently, which is
    # the scenario under test: the first wrapping writer must COW-fork
    # because the other lane still holds a reference.
    spec = DecodeSpec(cache_len=16, batch_global=2, batch_sharded=False,
                      sampling=True, kv_block_size=PAGED_BS, kv_pool_blocks=6)
    sched = ContinuousScheduler(m, MESH, spec, params, gather_key=GATHER_KEY,
                                prefill_chunk=PAGED_BS, prefill_buckets=2)
    solo = ServeEngine(
        m, MESH, DecodeSpec(cache_len=16, batch_global=1, batch_sharded=False,
                            sampling=True))
    rng = np.random.default_rng(13)
    system = rng.integers(0, VOCAB, size=PAGED_BS).tolist()
    mk = lambda g: Request(  # noqa: E731
        rid=f"c{next(_RID)}",
        prompt=system + rng.integers(0, VOCAB, size=2).tolist(),
        max_new_tokens=g)
    r0 = mk(2)  # registers the system block, retires (block cached)
    outs = run_scheduler(sched, [r0])
    r1, r2 = mk(10), mk(10)  # 10 + 10 = 20 > window 16: both wrap back
    outs += run_scheduler(sched, [r1, r2])  # into the SHARED logical block 0
    assert sched.pool.stats["prefix_hits"] >= 2, sched.pool.stats
    assert sched.pool.stats["cow_forks"] >= 1, sched.pool.stats
    for r, got in zip([r0, r1, r2], outs):
        ref = solo.generate(
            params, {"tokens": jnp.asarray(np.asarray(r.prompt, np.int32)[None])},
            {"tokens": P(None)}, n_tokens=r.max_new_tokens, key=GATHER_KEY,
            fold_step_keys=False, prefill_chunk=PAGED_BS)
        np.testing.assert_array_equal(got, np.asarray(jax.device_get(ref))[0],
                                      err_msg=r.rid)
    sched.pool.check_invariants()


def test_paged_pool_exhaustion_queues():
    """Satellite: admission is bounded by FREE BLOCKS, not free slots — two
    4-block requests over a 4-block pool run one at a time (the second
    queues despite an idle slot) and both finish with solo-exact tokens."""
    sched = paged_scheduler(slots=2, pool_blocks=4)  # one row: 4 blocks
    rng = np.random.default_rng(31)
    reqs = [Request(rid=f"c{next(_RID)}",
                    prompt=rng.integers(0, VOCAB, size=20).tolist(),
                    max_new_tokens=6)  # ceil(26 / 8) = 4 blocks
            for _ in range(2)]
    for r in reqs:
        sched.submit(r)
    while sched.queue or sched.n_active():
        assert sched.n_active() <= 1, "pool-exhausted admission did not queue"
        sched.step()
    for r in reqs:
        np.testing.assert_array_equal(
            sched.finished[r.rid].tokens,
            solo_tokens("dense", r.prompt, r.max_new_tokens, PAGED_BS),
            err_msg=r.rid)
    sched.pool.check_invariants()
