"""The scheduler's served weights are encoded once, for its fixed gather key.

``ContinuousScheduler`` serves ONE ``gather_key`` for its whole life, so
the encode half of every weight gather (quantize + serialize into the
coalesced u8 wire buffer) is a constant.  The scheduler runs it once, in
one program (``serve.engine.encode_served_weights``), and its decode,
chunk, verify and draft steps take that encoded tree in place of the f32
params: they only gather and decode.

This suite pins the mechanism on a tiny dense and a tiny moe model, ring
and paged, with and without speculation: the encode runs once however many
launches follow, its bytes depend on the gather key, and the step programs
contain no quantizer and take no f32 layer matrix.  That the served tokens
are bit-identical to the per-step gather is asserted by the solo
comparisons of test_serve_scheduler.py, test_chunked_prefill.py and
test_speculative.py.
"""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.analysis.jaxpr_audit import QUANTIZER_NAMES, _eqn_callee, iter_jaxprs
from repro.core.qsdp import EncodedLayer, MeshSpec, QSDPConfig
from repro.launch.mesh import make_mesh
from repro.models.config import ModelConfig
from repro.models.decode import ENCODED, DecodeSpec
from repro.models.transformer import Model
from repro.serve import ContinuousScheduler, Request, ServeEngine

MS = MeshSpec(axes=("data", "model"), shape=(1, 1))
MESH = make_mesh((1, 1), ("data", "model"))
GATHER_KEY = jax.random.PRNGKey(7)
RING, VOCAB, SLOTS, CHUNK = 32, 256, 3, 4
_RID = itertools.count()
_state: dict = {}


def model_and_params(family):
    if family not in _state:
        kw = dict(n_experts=4, moe_top_k=2) if family == "moe" else {}
        cfg = ModelConfig(name=f"enc-{family}", arch_type=family, n_layers=2,
                          d_model=64, vocab_size=VOCAB, n_heads=4, n_kv_heads=2,
                          head_dim=16, d_ff=128, **kw)
        m = Model(cfg, MS, QSDPConfig(min_quant_size=256))
        _state[family] = (m, m.init_params(jax.random.PRNGKey(0)))
    return _state[family]


def _spec(paged, speculative, slots=SLOTS):
    return DecodeSpec(cache_len=RING, batch_global=slots, batch_sharded=False,
                      sampling=True, kv_block_size=8 if paged else 0,
                      draft_bits=4 if speculative else 0,
                      draft_depth=3 if speculative else 0)


def scheduler(family, paged, speculative, key=GATHER_KEY) -> ContinuousScheduler:
    k = ("sched", family, paged, speculative, tuple(np.asarray(key)))
    if k not in _state:
        m, params = model_and_params(family)
        _state[k] = ContinuousScheduler(
            m, MESH, _spec(paged, speculative), params, gather_key=key,
            prefill_chunk=CHUNK, prefill_buckets=2)
    return _state[k]


def _requests(seed, n=5):
    rng = np.random.default_rng(seed)
    return [Request(rid=f"e{next(_RID)}",
                    prompt=rng.integers(0, VOCAB, size=int(rng.integers(3, 11))).tolist(),
                    max_new_tokens=int(rng.integers(4, 9)))
            for _ in range(n)]


def _sample(b):
    return ({"temp": jnp.zeros((b,), jnp.float32), "top_k": jnp.ones((b,), jnp.int32),
             "key": jnp.zeros((b, 2), jnp.uint32)},)


def _step_jaxpr(sched, program):
    """The jaxpr of one of the scheduler's step programs, traced with the
    arguments the scheduler passes it (nothing compiles or runs)."""
    b = sched.B
    lanes = jnp.zeros((b,), jnp.int32)
    bt = (jnp.asarray(sched.block_tables),) if sched.pool is not None else ()
    sample = _sample(b)
    head = (sched.params, sched.cache)
    if program == "decode":
        return jax.make_jaxpr(sched.engine.decode_step())(
            *head, lanes, lanes, *bt, sched.gather_key, *sample)
    if program == "draft":
        return jax.make_jaxpr(sched.draft_engine.decode_step())(
            sched.draft_params, sched.cache, lanes, lanes, *bt, sched.gather_key,
            *sample)
    if program == "chunk":
        bucket = sched.buckets[-1]
        return jax.make_jaxpr(sched.engine.prefill_chunk_step(bucket))(
            *head, jnp.zeros((b, bucket), jnp.int32), lanes, lanes, *bt,
            sched.gather_key, *sample)
    k = sched.spec.draft_depth
    return jax.make_jaxpr(sched.engine.verify_step(k))(
        *head, jnp.zeros((b, k), jnp.int32), lanes, lanes, *bt, sched.gather_key,
        *sample)


def _quantizers(closed) -> list[str]:
    """Quantizer calls and per-bucket min reductions anywhere in a program
    (the weight quantizer's bucket reduce is its only `reduce_min`)."""
    found = []
    for jx in iter_jaxprs(closed.jaxpr):
        for eqn in jx.eqns:
            callee = _eqn_callee(eqn)
            if any(q in callee for q in QUANTIZER_NAMES):
                found.append(callee)
            elif eqn.primitive.name == "reduce_min":
                found.append("reduce_min")
    return found


@pytest.mark.parametrize("speculative", [False, True], ids=["plain", "spec"])
@pytest.mark.parametrize("paged", [False, True], ids=["ring", "paged"])
@pytest.mark.parametrize("family", ["dense", "moe"])
def test_weights_encoded_once(family, paged, speculative):
    """Many decode, chunk and verify launches, one encode."""
    sched = scheduler(family, paged, speculative)
    for r in _requests(1):
        sched.submit(r)
    sched.run()
    st = sched.stats()
    assert st["weight_encodes"] == 1
    assert st["prefill_chunks"] > 1
    assert st["decode_launches" if not speculative else "verify_launches"] > 1
    if speculative:
        assert st["draft_launches"] > 1
    for n in ("embed", "final_norm", f"layers/{ENCODED}"):
        assert isinstance(sched.params[n], EncodedLayer), n
    assert not any(n.startswith("layers/") and n != f"layers/{ENCODED}"
                   for n in sched.params)


@pytest.mark.parametrize("family", ["dense", "moe"])
def test_encoded_bytes_follow_the_gather_key(family):
    """Two schedulers with different gather keys hold different encoded
    bytes (the stochastic-shift quantizer draws from the key); the same key
    encodes the same bytes."""
    a = scheduler(family, True, False)
    same = scheduler(family, False, False)
    other = scheduler(family, True, False, key=jax.random.PRNGKey(8))
    for n in ("embed", f"layers/{ENCODED}"):
        wa = np.asarray(a.params[n].wire)
        np.testing.assert_array_equal(wa, np.asarray(same.params[n].wire))
        assert (wa != np.asarray(other.params[n].wire)).any(), n
    assert other.stats()["weight_encodes"] == 1


@pytest.mark.parametrize("program", ["decode", "chunk", "verify", "draft"])
@pytest.mark.parametrize("family", ["dense", "moe"])
def test_steps_run_no_quantizer(family, program):
    """The scheduler's step programs only gather and decode: no quantizer
    call and no per-bucket min reduce — where the per-step gather of the
    same step, over the f32 params, has them."""
    sched = scheduler(family, True, True)
    assert _quantizers(_step_jaxpr(sched, program)) == []
    m, params = model_and_params(family)
    per_step = ServeEngine(m, MESH, _spec(True, False, sched.B))
    lanes = jnp.zeros((sched.B,), jnp.int32)
    closed = jax.make_jaxpr(per_step.decode_step())(
        params, per_step.init_cache(), lanes, lanes,
        jnp.asarray(sched.block_tables), sched.gather_key, *_sample(sched.B))
    assert _quantizers(closed)


@pytest.mark.parametrize("program", ["decode", "chunk", "verify", "draft"])
@pytest.mark.parametrize("family", ["dense", "moe"])
def test_steps_take_no_f32_layer_matrix(family, program):
    """The codes come in place of the f32 matrices, not beside them: every
    weight the step programs take is u8, and no f32 argument has more than
    one dimension (what is left in f32 is the per-lane temperature)."""
    sched = scheduler(family, True, True)
    tree = sched.draft_params if program == "draft" else sched.params
    for n, v in tree.items():
        leaf = v.wire if hasattr(v, "wire") else v
        assert leaf.dtype == jnp.uint8, n
    closed = _step_jaxpr(sched, program)
    big_f32 = [v.aval.shape for v in closed.jaxpr.invars
               if v.aval.dtype == jnp.float32 and v.aval.ndim > 1]
    assert big_f32 == []


@pytest.mark.parametrize("family", ["dense", "moe"])
def test_encoded_weights_decode_to_the_per_step_gather(family):
    """Layer by layer, the weights a step decodes from the encoded tree equal
    bit for bit what the per-step gather makes from the f32 params under the
    same folded key, and so do the non-layer leaves."""
    sched = scheduler(family, True, False)
    m, params = model_and_params(family)
    dm, key = sched.engine.dm, sched.gather_key
    n_layers = m.cfg.n_layers

    def both(served, raw):
        enc_grp, raw_grp = m._group(served, "layers"), m._group(raw, "layers")
        out = {}
        for i in range(n_layers):
            lkey = jax.random.fold_in(key, i)
            lw = {k: jax.tree.map(lambda a: a[i], v) for k, v in enc_grp.items()}
            got = dm._gather_layer_w("layers", list(lw), lw, lkey)
            want = m.engine.gather_layer("layers/", {k: v[i] for k, v in raw_grp.items()},
                                         lkey)
            out[f"layer{i}"] = (got, want)
        for n in ("embed", "final_norm"):
            out[n] = ({n: dm._gather_leaf(served, n, key)},
                      {n: m.engine.gather(n, raw[n], key)})
        return out

    fn = jax.shard_map(both, mesh=MESH, in_specs=(sched.engine._pspecs, m.param_pspecs()),
                       out_specs=P(), check_vma=False)
    out = jax.jit(fn)(sched.params, params)
    for where, (got, want) in out.items():
        assert sorted(got) == sorted(want), where
        for n in want:
            np.testing.assert_array_equal(np.asarray(got[n]), np.asarray(want[n]),
                                          err_msg=f"{where} {n}")
