"""Backend dispatch: fused Pallas quantize→pack / unpack→dequantize kernels
vs the jnp reference in core.quant — bit-exact wire bytes — plus the
code-form (rowquant) serve path."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core.quant import QuantConfig, dequantize, quantize
from repro.kernels import ops, ref
from repro.launch.mesh import make_mesh

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# Fused kernels vs jnp reference: identical wire bytes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["nearest", "stochastic", "shift"])
@pytest.mark.parametrize("bits", [2, 4, 8])
def test_fused_quantize_pack_bit_exact(bits, mode):
    """pallas-interpret and jnp produce byte-identical (codes, scale, zero)
    for every packed width and every rounding mode."""
    cfg = dict(bits=bits, bucket_size=256, mode=mode)
    x = jax.random.normal(KEY, (3000,)) * 2.0
    k = jax.random.PRNGKey(3)
    qj = quantize(x, QuantConfig(**cfg, backend="jnp"), k)
    qp = quantize(x, QuantConfig(**cfg, backend="pallas"), k)
    np.testing.assert_array_equal(np.asarray(qj.codes), np.asarray(qp.codes))
    np.testing.assert_array_equal(np.asarray(qj.scale), np.asarray(qp.scale))
    np.testing.assert_array_equal(np.asarray(qj.zero), np.asarray(qp.zero))


@pytest.mark.parametrize("rand_bits", [16, 32])
def test_fused_quantize_pack_stochastic_rand_bits(rand_bits):
    """Both stochastic-rounding threshold widths draw the same PRNG stream
    in both backends."""
    x = jax.random.normal(KEY, (2048,))
    k = jax.random.PRNGKey(5)
    mk = lambda b: QuantConfig(bits=4, bucket_size=512, mode="stochastic",
                               rand_bits=rand_bits, backend=b)
    qj, qp = quantize(x, mk("jnp"), k), quantize(x, mk("pallas"), k)
    np.testing.assert_array_equal(np.asarray(qj.codes), np.asarray(qp.codes))


@pytest.mark.parametrize("bits", [2, 3, 4, 8])
def test_fused_unpack_dequantize_bit_exact(bits):
    """Under jit (the production context — every step is a jitted shard_map)
    the fused unpack→dequantize kernel matches the jnp decode bitwise.
    Eager jnp differs by <=1 ULP only through XLA's FMA fusion."""
    cfg = QuantConfig(bits=bits, bucket_size=256, mode="shift")
    x = jax.random.normal(KEY, (3000,)) * 1.7
    q = quantize(x, cfg, jax.random.PRNGKey(1), backend="jnp")
    dj = jax.jit(lambda q: dequantize(q, backend="jnp"))(q)
    dp = dequantize(q, backend="pallas")
    np.testing.assert_array_equal(np.asarray(dj), np.asarray(dp))
    d_eager = dequantize(q, backend="jnp")
    np.testing.assert_allclose(np.asarray(d_eager), np.asarray(dp),
                               rtol=1e-6, atol=1e-6)


def test_dispatch_identical_wire_bytes_end_to_end():
    """The satellite acceptance check: core.quant produces identical wire
    bytes whichever backend is selected, including shapes after padding."""
    for n in (100, 1024, 4097):
        for bits in (2, 4, 8):
            cfg = dict(bits=bits, bucket_size=1024, mode="shift")
            x = jax.random.normal(jax.random.PRNGKey(n), (n,))
            k = jax.random.PRNGKey(9)
            qj = quantize(x, QuantConfig(**cfg, backend="jnp"), k)
            qp = quantize(x, QuantConfig(**cfg, backend="pallas"), k)
            assert qj.codes.shape == qp.codes.shape
            assert qj.wire_bytes == qp.wire_bytes
            np.testing.assert_array_equal(np.asarray(qj.codes), np.asarray(qp.codes))
            np.testing.assert_array_equal(np.asarray(qj.scale), np.asarray(qp.scale))
            np.testing.assert_array_equal(np.asarray(qj.zero), np.asarray(qp.zero))


def test_resolve_backend_env(monkeypatch):
    monkeypatch.delenv("REPRO_QUANT_BACKEND", raising=False)
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    # auto on CPU -> jnp; forcing interpret opts into the kernels
    assert ops.resolve_backend() in ("jnp", "pallas")
    if jax.default_backend() != "tpu":
        assert ops.resolve_backend() == "jnp"
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
        assert ops.resolve_backend() == "pallas"
    monkeypatch.setenv("REPRO_QUANT_BACKEND", "jnp")
    assert ops.resolve_backend() == "jnp"
    # a cfg-level "auto" (the QuantConfig default) must defer to the env,
    # so the documented env knob works through core.quant.quantize
    assert ops.resolve_backend("auto") == "jnp"
    monkeypatch.setenv("REPRO_QUANT_BACKEND", "pallas")
    assert ops.resolve_backend("auto") == "pallas"
    assert ops.resolve_backend("jnp") == "jnp"  # per-call override wins
    monkeypatch.setenv("REPRO_QUANT_BACKEND", "bogus")
    with pytest.raises(AssertionError):
        ops.resolve_backend()


def test_forced_interpret_on_tpu_is_an_error(monkeypatch):
    """On a TPU the kernels run compiled: forcing interpret mode there
    raises instead of silently running them on the host."""
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    assert ops._default_interpret() is False
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert ops._default_interpret() is False
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    with pytest.raises(RuntimeError, match="REPRO_PALLAS_INTERPRET"):
        ops._default_interpret()


def test_quantized_collectives_backend_agnostic():
    """all_gather / reduce-scatter wire payloads are backend-independent
    (quantize is vmapped over per-peer chunks inside the collectives)."""
    from repro.core import collectives as coll
    from jax import shard_map

    mesh = make_mesh((1,), ("data",))
    x = jax.random.normal(KEY, (2048,))
    outs = {}
    for b in ("jnp", "pallas"):
        cfg = QuantConfig(bits=4, bucket_size=512, mode="stochastic", backend=b)

        def f(x):
            g = coll.all_gather_quantized(x, ("data",), cfg, jax.random.PRNGKey(2))
            r = coll.reduce_scatter_quantized(x, ("data",), cfg, jax.random.PRNGKey(3))
            return g, r

        outs[b] = shard_map(f, mesh=mesh, in_specs=P("data"),
                            out_specs=(P("data"), P("data")), check_vma=False)(x)
    for a, b in zip(jax.tree.leaves(outs["jnp"]), jax.tree.leaves(outs["pallas"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Segment-affine rowquant matmul (wire-code consumption)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_seg", [1, 2, 4])
def test_rowquant_matmul_segment_affine(n_seg):
    k, n = 128, 512
    w = jax.random.normal(KEY, (k, n))
    codes = jax.random.randint(jax.random.PRNGKey(1), (k, n), 0, 256).astype(jnp.uint8)
    scale = jax.random.uniform(jax.random.PRNGKey(2), (k, n_seg)) * 0.1 + 0.01
    zero = jax.random.normal(jax.random.PRNGKey(3), (k, n_seg)) * 0.1
    x = jax.random.normal(jax.random.PRNGKey(4), (32, k))
    y = ops.rowquant_matmul(x, codes, scale, zero, block_n=128)
    y_ref = ref.rowquant_matmul_ref(x, codes, scale, zero)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=2e-4, atol=1e-2)


# ---------------------------------------------------------------------------
# Serve path: gathered weights stay in code form through the matmul
# ---------------------------------------------------------------------------


def _tiny_dense_model():
    from repro.core.qsdp import MeshSpec, QSDPConfig
    from repro.models.config import ModelConfig
    from repro.models.transformer import Model

    ms = MeshSpec(axes=("data", "model"), shape=(1, 1))
    qs = QSDPConfig(min_quant_size=256, bucket_size=128)
    cfg = ModelConfig(name="tiny_rowquant", arch_type="dense", n_layers=2,
                      d_model=128, vocab_size=512, n_heads=8, n_kv_heads=4,
                      head_dim=16, d_ff=256)
    return Model(cfg, ms, qs)


def test_gather_rowquant_eligibility():
    model = _tiny_dense_model()
    eng = model.engine
    # MLP weights: 2D, rows a multiple of the bucket -> code form
    assert eng.rowquant_eligible("layers/w_gate")
    assert eng.rowquant_eligible("layers/w_down")
    # norms are excluded from quantization entirely
    assert not eng.rowquant_eligible("layers/attn_norm")


def test_gather_rowquant_matches_dense_gather():
    """dequant(RowQuantWeight) == the dense gather's weight (same codes)."""
    from jax import shard_map
    from repro.kernels.ops import RowQuantWeight

    model = _tiny_dense_model()
    eng = model.engine
    mesh = make_mesh((1, 1), ("data", "model"))
    params = model.init_params(jax.random.PRNGKey(0))
    name = "layers/w_gate"
    local = params[name][0]  # layer 0 slice

    def f(local):
        k = jax.random.PRNGKey(11)
        dense = eng.gather(name, local, k)
        rq = eng.gather_rowquant(name, local, k)
        return dense, rq

    dense, rq = shard_map(
        f, mesh=mesh,
        in_specs=P("model", ("data",), None),
        out_specs=(P(), RowQuantWeight(P(), P(), P())), check_vma=False)(local)
    assert isinstance(rq, RowQuantWeight)
    n_seg = rq.scale.shape[1]
    seg = rq.codes.shape[1] // n_seg
    w = (rq.codes.astype(jnp.float32)
         * jnp.repeat(rq.scale, seg, axis=1) + jnp.repeat(rq.zero, seg, axis=1))
    np.testing.assert_allclose(np.asarray(w, np.float32),
                               np.asarray(dense, np.float32),
                               rtol=1e-2, atol=1e-2)  # dense is bf16


@pytest.mark.parametrize("arch", ["seamless_m4t_large_v2", "zamba2_7b"])
def test_serve_rowquant_decode_audio_hybrid(arch):
    """The audio decoder and the hybrid shared-attention stack also route
    their MLP gathers through the code-form path."""
    from repro import configs
    from repro.core.qsdp import MeshSpec, QSDPConfig
    from repro.models.decode import DecodeSpec
    from repro.models.transformer import Model
    from repro.serve.engine import ServeEngine

    cfg = configs.get_smoke(arch)
    model = Model(cfg, MeshSpec(axes=("data", "model"), shape=(1, 1)),
                  QSDPConfig(min_quant_size=256, bucket_size=128))
    mesh = make_mesh((1, 1), ("data", "model"))
    params = model.init_params(jax.random.PRNGKey(0))
    B, S = 2, 16
    batch = {"tokens": jnp.ones((B, S), jnp.int32)}
    bspecs = {"tokens": P(("data",))}
    enc_len = 0
    if cfg.arch_type == "audio":
        enc_len = S // cfg.enc_frames_ratio
        batch["audio_embeds"] = 0.1 * jax.random.normal(
            jax.random.PRNGKey(5), (B, enc_len, cfg.d_model))
        bspecs["audio_embeds"] = P(("data",))
    prefix = "dec/" if cfg.arch_type == "audio" else "shared/"
    assert model.engine.rowquant_eligible(prefix + "w_gate")
    spec = DecodeSpec(cache_len=64, batch_global=B, batch_sharded=False,
                      enc_len=enc_len)
    dense = ServeEngine(model, mesh, spec).generate(params, batch, bspecs, 5)
    rq = ServeEngine(
        model, mesh, dataclasses.replace(spec, rowquant_mlp=True)
    ).generate(params, batch, bspecs, 5)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(rq))


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_serve_rowquant_decode_matches_dense(backend, monkeypatch):
    """End-to-end: greedy decode through the code-form MLP path produces the
    same tokens as the dense-dequant path, with both matmul backends."""
    monkeypatch.setenv("REPRO_QUANT_BACKEND", backend)
    from repro.models.decode import DecodeSpec
    from repro.serve.engine import ServeEngine

    model = _tiny_dense_model()
    mesh = make_mesh((1, 1), ("data", "model"))
    params = model.init_params(jax.random.PRNGKey(0))
    batch = {"tokens": jnp.ones((2, 16), jnp.int32)}
    bspecs = {"tokens": P(("data",))}
    spec = DecodeSpec(cache_len=64, batch_global=2, batch_sharded=False)
    out = ServeEngine(model, mesh, spec).generate(params, batch, bspecs, 6)
    out_rq = ServeEngine(
        model, mesh, dataclasses.replace(spec, rowquant_mlp=True)
    ).generate(params, batch, bspecs, 6)
    assert out.shape == out_rq.shape == (2, 6)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out_rq))
