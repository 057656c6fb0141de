"""Quantized-domain train-state checks that need a multi-device mesh (run
under 8 emulated CPU devices; invoked by tests/test_distributed.py).

Validates:
  1. (2,4) mesh, 10 steps: loss + dequantized params + Adam moments of
     `quantized_state=True` are BIT-EXACT vs the f32 `quantize_master=True`
     QDQ path started from the same quantization-grid initial state (the
     acceptance criterion; the (1,1) case runs in-process in
     tests/test_quantized_state.py).
  2. checkpoint format v2 resharding: an f32 state saved on (1,1) loads on
     (2,4) — and back — with bit-identical logical params/moments/step.
  3. a QUANTIZED state saved on (1,1) loads on (2,4) (dequantize=True) with
     bit-identical decoded values, and byte-identical wire on the same
     layout; reverse direction likewise.

Exit code 0 + 'ALL-OK' on success.
"""
from _mesh_common import check, finish, force_host_devices

force_host_devices(8)

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.qsdp import MeshSpec, QSDPConfig, from_rest
from repro.core.quant import QuantizedParam
from repro.launch.mesh import make_mesh
from repro.models.config import ModelConfig
from repro.models.transformer import Model
from repro.optim import AdamWConfig, make_adamw
from repro.train.checkpoint import load_checkpoint, save_checkpoint
from repro.train.step import (
    dequantize_train_state,
    init_train_state,
    make_jitted_train_step,
    quantize_train_state,
    state_pspecs,
)

MCFG = ModelConfig(name="t", arch_type="dense", n_layers=2, d_model=64,
                   vocab_size=128, n_heads=4, n_kv_heads=4, head_dim=16,
                   d_ff=128)


def build(mesh_shape):
    mesh = make_mesh(mesh_shape, ("data", "model"))
    ms = MeshSpec(axes=("data", "model"), shape=mesh_shape)
    model = Model(MCFG, ms, QSDPConfig(min_quant_size=256))
    return mesh, ms, model


def batch_for(model):
    tokens = jax.random.randint(jax.random.PRNGKey(3), (8, 32), 0,
                                MCFG.vocab_size)
    return {"tokens": tokens, "labels": tokens}


# ---------------------------------------------------------------------------
# 1. (2,4) bit-exactness over 10 steps
# ---------------------------------------------------------------------------

mesh, ms, model = build((2, 4))
opt = make_adamw(AdamWConfig(lr=1e-3))
s0 = init_train_state(model, opt, jax.random.PRNGKey(0))
qs0 = quantize_train_state(s0, model, jax.random.PRNGKey(9))
fs0 = dequantize_train_state(qs0)
batch = batch_for(model)

step_q = make_jitted_train_step(model, opt, mesh, quantized_state=True,
                                donate=False)
step_f = make_jitted_train_step(model, opt, mesh, quantize_master=True,
                                donate=False)
sq, sf = qs0, fs0
losses_equal = True
with mesh:
    for i in range(10):
        k = jax.random.fold_in(jax.random.PRNGKey(7), i)
        sq, mq = step_q(sq, batch, k)
        sf, mf = step_f(sf, batch, k)
        losses_equal &= float(mq["loss"]) == float(mf["loss"])
check("qstate-2x4-loss-bitexact-10steps", losses_equal)
dq = dequantize_train_state(sq)
ok = all(bool(jnp.all(dq.params[k] == sf.params[k])) for k in sf.params)
check("qstate-2x4-params-bitexact", ok)
ok = all(bool(jnp.all(dq.opt.mu[k] == sf.opt.mu[k]))
         and bool(jnp.all(dq.opt.nu[k] == sf.opt.nu[k])) for k in sf.opt.mu)
check("qstate-2x4-moments-bitexact", ok)
n_wire = sum(isinstance(v, QuantizedParam) for v in sq.params.values())
check("qstate-2x4-has-wire-leaves", n_wire > 0, f"n={n_wire}")


# ---------------------------------------------------------------------------
# 2. checkpoint v2 resharding, f32 state: (1,1) <-> (2,4) bit-identical
# ---------------------------------------------------------------------------


def logical(state, model):
    out = {}
    for k, v in state.params.items():
        out[k] = np.asarray(from_rest(v, model.specs[k], model.ms))
    return out


def logical_tree(tree, model):
    return {k: np.asarray(from_rest(v, model.specs[k], model.ms))
            for k, v in tree.items()}


import tempfile

mesh11_, ms11, model11 = build((1, 1))
mesh24, ms24, model24 = build((2, 4))

opt11 = make_adamw(AdamWConfig(lr=1e-3))
state11 = init_train_state(model11, opt11, jax.random.PRNGKey(4))
state24 = init_train_state(model24, make_adamw(AdamWConfig(lr=1e-3)),
                           jax.random.PRNGKey(4))

with tempfile.TemporaryDirectory() as td:
    save_checkpoint(td, state11)
    loaded24 = load_checkpoint(td, mesh24, state_pspecs(model24), model=model24)
l_src = logical(state11, model11)
l_dst = logical(loaded24, model24)
ok = all(np.array_equal(l_src[k], l_dst[k]) for k in l_src)
check("ckpt-reshard-f32-1x1-to-2x4-params", ok)
mu_src = logical_tree(state11.opt.mu, model11)
mu_dst = logical_tree(loaded24.opt.mu, model24)
ok = (all(np.array_equal(mu_src[k], mu_dst[k]) for k in mu_src)
      and int(loaded24.opt.step) == int(state11.opt.step))
check("ckpt-reshard-f32-1x1-to-2x4-opt", ok)

with tempfile.TemporaryDirectory() as td:
    save_checkpoint(td, state24)
    loaded11 = load_checkpoint(td, mesh11_, state_pspecs(model11), model=model11)
l_src = logical(state24, model24)
l_dst = logical(loaded11, model11)
ok = all(np.array_equal(l_src[k], l_dst[k]) for k in l_src)
check("ckpt-reshard-f32-2x4-to-1x1-params", ok)


# ---------------------------------------------------------------------------
# 3. quantized state across meshes
# ---------------------------------------------------------------------------

q11 = quantize_train_state(state11, model11, jax.random.PRNGKey(5))

# same layout: wire bytes survive the checkpoint untouched
with tempfile.TemporaryDirectory() as td:
    save_checkpoint(td, q11)
    rq11 = load_checkpoint(td, mesh11_,
                           state_pspecs(model11, quantized_state=True),
                           model=model11)
ok = all(
    (np.array_equal(np.asarray(v.wire), np.asarray(rq11.params[k].wire))
     if isinstance(v, QuantizedParam)
     else np.array_equal(np.asarray(v), np.asarray(rq11.params[k])))
    for k, v in q11.params.items())
check("ckpt-qstate-same-layout-byte-identical", ok)

# cross layout: decoded values are bit-identical (decode is deterministic)
with tempfile.TemporaryDirectory() as td:
    save_checkpoint(td, q11)
    try:
        load_checkpoint(td, mesh24, state_pspecs(model24), model=model24)
        check("ckpt-qstate-cross-layout-requires-dequantize", False)
    except ValueError:
        check("ckpt-qstate-cross-layout-requires-dequantize", True)
    rq24 = load_checkpoint(td, mesh24, state_pspecs(model24), model=model24,
                           dequantize=True)
ref = logical(dequantize_train_state(q11), model11)
got = logical(rq24, model24)
ok = all(np.array_equal(ref[k], got[k]) for k in ref)
check("ckpt-qstate-1x1-to-2x4-decoded-bitexact", ok)

# reverse: quantize on (2,4), read back on (1,1)
q24 = quantize_train_state(state24, model24, jax.random.PRNGKey(5))
with tempfile.TemporaryDirectory() as td:
    save_checkpoint(td, q24)
    rq11b = load_checkpoint(td, mesh11_, state_pspecs(model11), model=model11,
                            dequantize=True)
ref = logical(dequantize_train_state(q24), model24)
got = logical(rq11b, model11)
ok = all(np.array_equal(ref[k], got[k]) for k in ref)
check("ckpt-qstate-2x4-to-1x1-decoded-bitexact", ok)


finish()
