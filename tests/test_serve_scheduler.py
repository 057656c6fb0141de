"""Serving conformance & property suite for the continuous-batching
scheduler (serve/scheduler.py).

The load-bearing invariant: with greedy decoding, a request's output tokens
are BIT-IDENTICAL whether it runs alone in a batch-of-1 engine
(``ServeEngine.generate(..., fold_step_keys=False)``) or interleaved with
arbitrary other requests under the scheduler — random arrival orders,
prompt/generation lengths, and slot counts, on the dense and moe families.
The scheduler serves its weights encoded once for its gather key, and the
solo engine re-encodes them every step from the f32 params, so each solo
comparison also pins the resident path to the per-step gather.
Plus: cache hygiene on slot reuse (no stale KV; ring wrap composes with
reuse), and per-request sampling that is reproducible across runs and
batch compositions and reduces to the greedy path bit-exactly at
temperature 0 / top-k 1.

Engines and schedulers are cached at module scope (compiles dominate);
reusing one scheduler across tests is deliberate — every admission must
fully overwrite the slot it lands in, so a dirty pool is exactly the state
the hygiene invariant covers.
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
                         make_sample_params)

MS = MeshSpec(axes=("data", "model"), shape=(1, 1))
MESH = make_mesh((1, 1), ("data", "model"))
# ONE gather key for every prefill/decode step — the served model is a fixed
# function (see scheduler module docstring); solo references use the same key
GATHER_KEY = jax.random.PRNGKey(7)
RING = 32
VOCAB = 256
PROMPT_LENS = (4, 6)  # bounded so prefill retraces stay cheap
_RID = itertools.count()


def _cfg(family: str) -> ModelConfig:
    base = dict(name=f"sched-{family}", arch_type=family, n_layers=2,
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


def scheduler(family, slots) -> ContinuousScheduler:
    if (family, slots) not in _scheds:
        m, params = model_and_params(family)
        spec = DecodeSpec(cache_len=RING, batch_global=slots,
                          batch_sharded=False, sampling=True)
        _scheds[(family, slots)] = ContinuousScheduler(
            m, MESH, spec, params, gather_key=GATHER_KEY)
    return _scheds[(family, slots)]


def solo_tokens(family, prompt, gen, temperature=0.0, top_k=0, seed=0):
    """Reference: the request alone in a batch-of-1 engine, fixed gather
    key (memoized — many scheduler scenarios share solo requests)."""
    key = (family, tuple(prompt), gen, temperature, top_k, seed)
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
        fold_step_keys=False)
    _solo_out[key] = np.asarray(jax.device_get(out))[0]
    return _solo_out[key]


def make_requests(rng, n, max_gen=5, sampled=False):
    reqs = []
    for _ in range(n):
        plen = int(rng.choice(PROMPT_LENS))
        reqs.append(Request(
            rid=f"t{next(_RID)}",
            prompt=rng.integers(0, VOCAB, size=plen).tolist(),
            max_new_tokens=int(rng.integers(1, max_gen + 1)),
            temperature=float(rng.choice([0.0, 0.7, 1.3])) if sampled else 0.0,
            top_k=int(rng.choice([0, 1, 3])) if sampled else 0,
            seed=int(rng.integers(0, 100))))
    return reqs


def run_scheduler(sched, reqs):
    for r in reqs:
        sched.submit(r)
    done = sched.run()
    return [done[r.rid].tokens for r in reqs]


# ---------------------------------------------------------------------------
# Tentpole invariant: interleaved greedy == solo batch-of-1, property-driven
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("family", ["dense", "moe"])
@settings(max_examples=4, deadline=None)
@given(seed=st.integers(0, 10_000), slots=st.sampled_from([2, 3]))
def test_interleaved_greedy_matches_solo(family, seed, slots):
    """Random arrival orders / prompt lengths / generation lengths / slot
    counts: every greedy request's tokens match its solo batch-of-1 run
    token-for-token.  This compares the resident path (weights encoded once
    by the scheduler) with the per-step gather of
    ``generate(fold_step_keys=False)``."""
    rng = np.random.default_rng(seed)
    sched = scheduler(family, slots)
    reqs = make_requests(rng, int(rng.integers(3, 6)))
    outs = run_scheduler(sched, reqs)
    assert sched.stats()["weight_encodes"] == 1
    for r, got in zip(reqs, outs):
        ref = solo_tokens(family, r.prompt, r.max_new_tokens)[: r.max_new_tokens]
        np.testing.assert_array_equal(got, ref, err_msg=f"{family} {r.rid}")


@pytest.mark.parametrize("family", ["dense", "moe"])
def test_interleaved_insensitive_to_arrival_order(family):
    """The same request set, submitted in different orders (hence decoded
    against different slot neighbours), yields identical per-request
    streams."""
    rng = np.random.default_rng(99)
    reqs = make_requests(rng, 5)
    sched = scheduler(family, 3)
    a = dict(zip((r.rid for r in reqs), run_scheduler(sched, reqs)))
    perm = [reqs[i] for i in [3, 0, 4, 2, 1]]
    renamed = [Request(rid=f"t{next(_RID)}", prompt=r.prompt,
                       max_new_tokens=r.max_new_tokens, seed=r.seed)
               for r in perm]
    b = run_scheduler(sched, renamed)
    for orig, got in zip(perm, b):
        np.testing.assert_array_equal(got, a[orig.rid])


# ---------------------------------------------------------------------------
# Cache hygiene: slot reuse must look like a fresh engine
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("family", ["dense", "moe"])
def test_slot_reuse_no_stale_kv(family):
    """More requests than slots forces freed-slot reuse mid-decode; every
    reused slot's request must match the fresh batch-of-1 engine, and a
    second pass over the same prompts (pool now dirty with the first pass's
    KV) must reproduce it.  This compares the resident path with the
    per-step gather of ``generate(fold_step_keys=False)``."""
    rng = np.random.default_rng(5)
    sched = scheduler(family, 2)
    reqs = make_requests(rng, 5, max_gen=4)
    first = run_scheduler(sched, reqs)
    for r, got in zip(reqs, first):
        np.testing.assert_array_equal(
            got, solo_tokens(family, r.prompt, r.max_new_tokens))
    again = [Request(rid=f"t{next(_RID)}", prompt=r.prompt,
                     max_new_tokens=r.max_new_tokens) for r in reqs]
    second = run_scheduler(sched, again)
    for a, b in zip(first, second):
        np.testing.assert_array_equal(a, b)


def test_ring_wrap_composes_with_slot_reuse():
    """Sliding-window model: generation long enough to wrap the KV ring,
    through slots that are freed and reused — wrap + reuse must still match
    the solo run (which wraps the same ring).  This compares the resident
    path with the per-step gather of ``generate(fold_step_keys=False)``."""
    cfg = ModelConfig(name="wrap", arch_type="dense", n_layers=2, d_model=64,
                      vocab_size=VOCAB, n_heads=4, n_kv_heads=2, head_dim=16,
                      d_ff=128, sliding_window=0, long_context="sliding_window",
                      long_context_window=16)
    m = Model(cfg, MS, QSDPConfig(min_quant_size=256))
    params = m.init_params(jax.random.PRNGKey(0))
    spec = DecodeSpec(cache_len=16, batch_global=2, batch_sharded=False,
                      sampling=True)
    sched = ContinuousScheduler(m, MESH, spec, params, gather_key=GATHER_KEY)
    solo = ServeEngine(
        m, MESH, DecodeSpec(cache_len=16, batch_global=1, batch_sharded=False,
                            sampling=True))
    rng = np.random.default_rng(3)
    # gen 14 from prompt 8: positions reach 21 > ring 16 — wraps; 3 requests
    # on 2 slots forces reuse after a wrapped generation
    reqs = [Request(rid=f"t{next(_RID)}",
                    prompt=rng.integers(0, VOCAB, size=8).tolist(),
                    max_new_tokens=g) for g in (14, 6, 14)]
    outs = run_scheduler(sched, reqs)
    for r, got in zip(reqs, outs):
        ref = solo.generate(
            params, {"tokens": jnp.asarray(np.asarray(r.prompt, np.int32)[None])},
            {"tokens": P(None)}, n_tokens=r.max_new_tokens, key=GATHER_KEY,
            fold_step_keys=False)
        np.testing.assert_array_equal(got, np.asarray(jax.device_get(ref))[0])


# ---------------------------------------------------------------------------
# Sampling determinism
# ---------------------------------------------------------------------------


def test_sampling_reproducible_across_runs_and_compositions():
    """temperature/top-k requests with fixed per-request seeds reproduce
    exactly across scheduler runs AND across different batch compositions
    (different co-resident requests)."""
    rng = np.random.default_rng(11)
    sched = scheduler("dense", 3)
    reqs = make_requests(rng, 4, sampled=True)
    a = run_scheduler(sched, reqs)
    # same requests again (new rids), plus extra greedy traffic interleaved
    renamed = [Request(rid=f"t{next(_RID)}", prompt=r.prompt,
                       max_new_tokens=r.max_new_tokens,
                       temperature=r.temperature, top_k=r.top_k, seed=r.seed)
               for r in reqs]
    fillers = make_requests(rng, 3)
    order = [renamed[1], fillers[0], renamed[0], fillers[1], renamed[3],
             fillers[2], renamed[2]]
    done = dict(zip((r.rid for r in order), run_scheduler(sched, order)))
    for orig, ren in zip(reqs, renamed):
        np.testing.assert_array_equal(done[ren.rid],
                                      a[reqs.index(orig)])
    # and each sampled stream matches its solo batch-of-1 run
    for r, got in zip(reqs, a):
        np.testing.assert_array_equal(
            got, solo_tokens("dense", r.prompt, r.max_new_tokens,
                             r.temperature, r.top_k, r.seed))


def test_temp0_topk1_reduce_to_greedy_bit_exactly():
    """temperature=0 and top_k=1 rows of the sampling path must equal the
    pure-greedy engine (DecodeSpec(sampling=False)) token-for-token."""
    m, params = model_and_params("dense")
    prompt = np.arange(1, 7, dtype=np.int32)
    greedy_eng = ServeEngine(
        m, MESH, DecodeSpec(cache_len=RING, batch_global=1,
                            batch_sharded=False, sampling=False))
    ref = np.asarray(jax.device_get(greedy_eng.generate(
        params, {"tokens": jnp.asarray(prompt[None])}, {"tokens": P(None)},
        n_tokens=5, key=GATHER_KEY, fold_step_keys=False)))[0]
    for temperature, top_k in ((0.0, 0), (0.0, 3), (1.3, 1)):
        got = solo_tokens("dense", prompt.tolist(), 5, temperature, top_k,
                          seed=42)
        np.testing.assert_array_equal(got, ref, err_msg=f"{temperature}/{top_k}")


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000), k=st.sampled_from([1, 2, 3, 8]))
def test_sampled_tokens_stay_in_topk(seed, k):
    """sample_vocab_parallel property: a sampled token is always inside the
    row's top-k logit set, and temp<=0 rows equal the argmax."""
    from jax import shard_map
    from repro.models.layers import sample_vocab_parallel

    rng = np.random.default_rng(seed)
    t, v = 4, 16
    logits = jnp.asarray(rng.normal(size=(t, v)).astype(np.float32))
    temp = jnp.asarray(rng.choice([0.0, 0.5, 1.0], size=t).astype(np.float32))
    top_k = jnp.full((t,), k, jnp.int32)
    keys = jnp.asarray(
        np.stack([np.asarray(jax.random.PRNGKey(int(s)))
                  for s in rng.integers(0, 1 << 30, size=t)]))

    fn = shard_map(
        lambda lg, tp, tk, kk: sample_vocab_parallel(lg, v, tp, tk, kk),
        mesh=MESH, in_specs=(P(), P(), P(), P()), out_specs=P(),
        check_vma=False)
    toks = np.asarray(jax.device_get(jax.jit(fn)(logits, temp, top_k, keys)))
    lg = np.asarray(logits)
    for i in range(t):
        topk_ids = np.argsort(lg[i])[::-1][:k]
        kth = lg[i][topk_ids[-1]]
        assert lg[i][toks[i]] >= kth, (i, toks[i], k)
        if temp[i] <= 0 or k == 1:
            assert toks[i] == int(np.argmax(lg[i]))


# ---------------------------------------------------------------------------
# Scheduler surface: streaming events, stats, validation
# ---------------------------------------------------------------------------


def test_streaming_events_are_contiguous_per_request():
    sched = scheduler("dense", 2)
    rng = np.random.default_rng(21)
    reqs = make_requests(rng, 4, max_gen=4)
    for r in reqs:
        sched.submit(r)
    events = []
    done = sched.run(on_token=events.append)
    seen: dict = {}
    for ev in events:
        assert ev.index == seen.get(ev.rid, -1) + 1, "gap in streamed tokens"
        seen[ev.rid] = ev.index
    for r in reqs:
        toks = [ev.token for ev in events if ev.rid == r.rid]
        np.testing.assert_array_equal(np.asarray(toks, np.int32),
                                      done[r.rid].tokens)
        dones = [ev.done for ev in events if ev.rid == r.rid]
        assert dones[-1] and not any(dones[:-1])


def test_scheduler_stats_and_occupancy():
    sched = scheduler("dense", 2)
    base = sched.stats()
    rng = np.random.default_rng(31)
    reqs = make_requests(rng, 3, max_gen=3)
    run_scheduler(sched, reqs)
    st_ = sched.stats()
    assert st_["prefills"] - base["prefills"] == 3
    assert st_["tokens_generated"] - base["tokens_generated"] == sum(
        r.max_new_tokens for r in reqs)
    assert 0 < st_["mean_occupancy"] <= 2


def test_scheduler_validation_errors():
    m, params = model_and_params("dense")
    spec = DecodeSpec(cache_len=RING, batch_global=2, batch_sharded=False,
                      sampling=False)
    sched = ContinuousScheduler(m, MESH, spec, params)
    with pytest.raises(ValueError, match="sampling"):
        sched.submit(Request(rid="s", prompt=[1, 2], max_new_tokens=2,
                             temperature=0.9))
    with pytest.raises(ValueError, match="exceeds"):
        sched.submit(Request(rid="long", prompt=list(range(RING + 1)),
                             max_new_tokens=1))
    with pytest.raises(ValueError, match="non-empty"):
        sched.submit(Request(rid="empty", prompt=[], max_new_tokens=1))
    with pytest.raises(ValueError, match="max_new_tokens"):
        sched.submit(Request(rid="zero", prompt=[1], max_new_tokens=0))
    sched.submit(Request(rid="dup", prompt=[1, 2], max_new_tokens=1))
    with pytest.raises(ValueError, match="duplicate"):
        sched.submit(Request(rid="dup", prompt=[1, 2], max_new_tokens=1))


def test_eos_frees_slot_early():
    """A request that hits its eos_id stops (eos included in the stream) and
    its slot is reused; remaining requests are unaffected."""
    m, params = model_and_params("dense")
    rng = np.random.default_rng(41)
    sched = scheduler("dense", 2)
    prompt = rng.integers(0, VOCAB, size=4).tolist()
    free_run = solo_tokens("dense", prompt, 8)
    eos = int(free_run[2])  # the 3rd token the model would emit
    # the stream stops at the FIRST emission of eos, which a random model
    # may repeat earlier than the 3rd token
    stop = list(free_run).index(eos) + 1
    reqs = [Request(rid=f"t{next(_RID)}", prompt=prompt, max_new_tokens=8,
                    eos_id=eos),
            make_requests(rng, 1, max_gen=4)[0],
            make_requests(rng, 1, max_gen=4)[0]]
    outs = run_scheduler(sched, reqs)
    np.testing.assert_array_equal(outs[0], free_run[:stop])
    for r, got in zip(reqs[1:], outs[1:]):
        np.testing.assert_array_equal(
            got, solo_tokens("dense", r.prompt, r.max_new_tokens))


def test_paged_pool_capacity_validation():
    """Satellite: with the paged pool the submit-time bound is pool blocks,
    not ring length — the solo path raises a clear PoolExhausted when the
    lanes cannot all fit, and both entry points insist on chunked prefill
    (paged serving has no whole-prompt float path)."""
    from repro.serve.kv_pool import PoolExhausted

    m, params = model_and_params("dense")
    # batch 2 x 4 blocks/slot = 8 blocks needed; the pool holds one row (4)
    pspec = DecodeSpec(cache_len=RING, batch_global=2, batch_sharded=False,
                       sampling=True, kv_block_size=8, kv_pool_blocks=4)
    with pytest.raises(ValueError, match="chunked admission"):
        ContinuousScheduler(m, MESH, pspec, params, gather_key=GATHER_KEY)
    eng = ServeEngine(m, MESH, pspec)
    prompt = {"tokens": jnp.ones((2, 4), jnp.int32)}
    with pytest.raises(ValueError, match="chunked prefill"):
        eng.generate(params, prompt, {"tokens": P(None)}, n_tokens=2,
                     key=GATHER_KEY, fold_step_keys=False)
    with pytest.raises(PoolExhausted, match="kv-pool-blocks"):
        eng.generate(params, prompt, {"tokens": P(None)}, n_tokens=2,
                     key=GATHER_KEY, fold_step_keys=False, prefill_chunk=4)
