"""Serving a quantized-domain checkpoint with zero conversion: the stored
wire codes feed QSDPEngine.gather_rowquant_wire / rowquant_matmul directly,
never passing through a quantize or dequantize of the dense matrix."""
import jax
import jax.numpy as jnp
import numpy as np
from functools import partial
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.core.qsdp import MeshSpec, QSDPConfig
from repro.core.quant import QuantizedParam, qparam_decode
from repro.models.config import ModelConfig
from repro.models.decode import DecodeSpec
from repro.models.transformer import Model
from repro.optim import AdamWConfig, make_adamw
from repro.serve import ServeEngine
from repro.serve.engine import prepare_wire_params, wire_param_pspecs
from repro.train import load_checkpoint, save_checkpoint
from repro.train.step import (
    dequantize_train_state,
    init_train_state,
    quantize_train_state,
    state_pspecs,
)

MS = MeshSpec(axes=("data", "model"), shape=(1, 1))
# full-precision collectives + f32 compute so the ONLY difference between
# wire-serve and f32-serve is the MLP matmul route (codes vs dense) — which
# decodes to identical values
QS = QSDPConfig(quantize_weights=False, quantize_grads=False, coalesce=True,
                bucket_size=64, min_quant_size=256, compute_dtype="float32")


def _model():
    cfg = ModelConfig(name="wq", arch_type="dense", n_layers=2, d_model=64,
                      vocab_size=128, n_heads=4, n_kv_heads=2, head_dim=16,
                      d_ff=128)
    return Model(cfg, MS, QS)


def _quantized_state(model):
    opt = make_adamw(AdamWConfig(lr=1e-3))
    state = init_train_state(model, opt, jax.random.PRNGKey(0))
    return quantize_train_state(state, model, jax.random.PRNGKey(5))


def test_gather_rowquant_wire_is_zero_conversion(mesh11):
    """RowQuantWeight built from stored codes carries the checkpoint BYTES
    (codes + per-bucket affine) untouched, and its affine decode equals the
    dequantized parameter to within one fp rounding (the mul+add may or may
    not be FMA-contracted depending on the surrounding program)."""
    model = _model()
    state = _quantized_state(model)
    prepared = prepare_wire_params(model, state.params)
    name = "layers/w_gate"
    qp = prepared[name]
    assert isinstance(qp, QuantizedParam) and qp.wire.ndim == 4
    dense = qparam_decode(state.params[name])  # (L, 1, 1, n_local)
    spec = model.specs[name]
    k_dim, n_dim = spec.tp_local_shape(1)
    eng = model.engine
    bucket = qp.cfg.bucket_size

    @partial(shard_map, mesh=mesh11,
             in_specs=(P(None, "model", ("data",), None),),
             out_specs=(P(), P(), P()), check_vma=False)
    def gather_layer0(wire):
        qp0 = QuantizedParam(wire[0], qp.cell_shape, qp.cfg)
        rw = eng.gather_rowquant_wire(name, qp0)
        return rw.codes[None], rw.scale[None], rw.zero[None]

    with mesh11:
        codes, scale, zero = (x[0] for x in gather_layer0(qp.wire))
    # byte-identity with the stored wire segment of layer 0
    from repro.core.quant import wire_unpack
    q0 = wire_unpack(qp.wire[0].reshape(-1), qp.n, qp.cfg)
    np.testing.assert_array_equal(np.asarray(codes).reshape(-1, bucket),
                                  np.asarray(q0.codes))
    np.testing.assert_array_equal(np.asarray(scale).reshape(-1), np.asarray(q0.scale))
    np.testing.assert_array_equal(np.asarray(zero).reshape(-1), np.asarray(q0.zero))
    # value-identity up to one fp rounding of the affine
    seg = n_dim // scale.shape[1]
    w = (np.asarray(codes, np.float32) * np.repeat(np.asarray(scale), seg, axis=1)
         + np.repeat(np.asarray(zero), seg, axis=1))
    ref = np.asarray(dense[0]).reshape(k_dim, n_dim)
    np.testing.assert_allclose(w, ref, rtol=0, atol=1.2e-7)


def test_prepare_wire_params_forms():
    model = _model()
    state = _quantized_state(model)
    prepared = prepare_wire_params(model, state.params)
    for base in ("w_gate", "w_up", "w_down"):
        v = prepared[f"layers/{base}"]
        assert isinstance(v, QuantizedParam)
        assert v.wire.ndim == 4 and v.wire.shape[0] == 2  # per-layer slices
    # everything else decoded to dense f32 rest leaves
    for name, v in prepared.items():
        if name.split("/")[-1] not in ("w_gate", "w_up", "w_down"):
            assert not isinstance(v, QuantizedParam), name
            ref = state.params[name]
            if isinstance(ref, QuantizedParam):
                np.testing.assert_array_equal(np.asarray(v),
                                              np.asarray(qparam_decode(ref)))
    # pspecs: wire leaves get the stacked wire spec
    ps = wire_param_pspecs(model, prepared)
    assert ps["layers/w_gate"] == P(None, "model", ("data",), None)
    assert ps["layers/attn_norm"] == model.specs["layers/attn_norm"].rest_pspec(MS)


def test_serve_from_wire_matches_f32_serve(tmp_path, mesh11):
    """generate() from a v2 quantized checkpoint (codes straight into the
    rowquant matmul) == generate() from the dequantized f32 params."""
    model = _model()
    state = _quantized_state(model)
    path = str(tmp_path / "qckpt")
    save_checkpoint(path, state)
    loaded = load_checkpoint(path, mesh11,
                             state_pspecs(model, quantized_state=True),
                             model=model)
    prepared = prepare_wire_params(model, loaded.params)
    f32_params = dequantize_train_state(state).params

    spec = DecodeSpec(cache_len=32, batch_global=2, batch_sharded=False)
    prompt = {"tokens": jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0, 128)}
    ps = {"tokens": P(None)}
    with mesh11:
        eng_w = ServeEngine(model, mesh11, spec, params=prepared)
        toks_w = np.asarray(jax.device_get(
            eng_w.generate(prepared, prompt, ps, n_tokens=4)))
        eng_f = ServeEngine(model, mesh11, spec)
        toks_f = np.asarray(jax.device_get(
            eng_f.generate(f32_params, prompt, ps, n_tokens=4)))
    np.testing.assert_array_equal(toks_w, toks_f)
