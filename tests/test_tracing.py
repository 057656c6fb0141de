"""The program's profiler names (``repro.obs``): device scopes at the layer
boundaries, named jitted programs, and the scheduler's host spans and
wait counters.

Scopes are metadata only: the compiled train, decode and prefill-chunk
steps, stripped of their debug information, equal the same programs built
with every scope turned into a null context.
"""
import collections
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.qsdp import MeshSpec, QSDPConfig
from repro.launch.mesh import make_mesh
from repro.models.config import ModelConfig
from repro.models.transformer import Model
from repro.optim import AdamWConfig, make_adamw
from repro.serve import ContinuousScheduler, Request
from repro.serve.common import make_serve_spec
from repro.serve.engine import ServeEngine
from repro.train.step import init_train_state, make_jitted_train_step

MS = MeshSpec(axes=("data", "model"), shape=(1, 1))
CFG = ModelConfig(name="tiny-trace", arch_type="dense", n_layers=2, d_model=128,
                  vocab_size=512, n_heads=8, n_kv_heads=4, head_dim=16, d_ff=256)
QCFG = QSDPConfig(weight_bits=8, grad_bits=8, min_quant_size=256, bucket_size=128)
TRAIN_SCOPES = {"attention", "mlp", "qsdp_gather", "qsdp_reduce_scatter"}
SERVE_SCOPES = {"attention", "mlp", "qsdp_gather", "kv_write"}
CHUNK, SLOTS = 16, 2


def _serve_spec():
    return make_serve_spec(CFG, MS, SLOTS, 40, 8, kv_block_size=8)


def scopes_of(hlo: str) -> collections.Counter:
    """Innermost program scope of each instruction's op_name."""
    out = collections.Counter()
    for name in re.findall(r'op_name="([^"]*)"', hlo):
        found = re.findall(re.escape(obs.PREFIX) + r"(\w+)", name)
        if found:
            out[found[-1]] += 1
    return out


DEBUG_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def strip_metadata(hlo: str) -> str:
    """The program without its debug information: each instruction's
    ``metadata={...}`` and the source-location tables it points into, and
    the numbers that tell same-named instructions apart (XLA derives an
    instruction's name from its op name, so scopes renumber them),
    replaced in order of appearance."""
    blocks = [b for b in hlo.split("\n\n") if b.split("\n", 1)[0] not in DEBUG_TABLES]
    text = re.sub(r",? metadata=\{[^}]*\}", "", "\n\n".join(blocks))
    seen: dict = {}
    return re.sub(r"\b[A-Za-z_][\w\-]*(?:\.\d+)+\b",
                  lambda m: seen.setdefault(m.group(0), f"#{len(seen)}"), text)


def module_name(hlo: str) -> str:
    return re.match(r"HloModule (\S+?),", hlo).group(1)


@pytest.fixture(scope="module")
def model():
    return Model(CFG, MS, QCFG)


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1, 1), ("data", "model"))


def _train_hlo(model, mesh) -> str:
    opt = make_adamw(AdamWConfig(lr=1e-3))
    state = jax.eval_shape(lambda: init_train_state(model, opt, jax.random.PRNGKey(0),
                                                    mesh))
    batch = {k: jax.ShapeDtypeStruct((2, 64), jnp.int32) for k in ("tokens", "labels")}
    step = make_jitted_train_step(model, opt, mesh, donate=False)
    return step.lower(state, batch, jax.random.PRNGKey(1)).compile().as_text()


def _serve_hlos(model, mesh) -> dict:
    spec = _serve_spec()
    eng = ServeEngine(model, mesh, spec)
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    cache = jax.eval_shape(eng.init_cache)
    bt = jax.ShapeDtypeStruct((SLOTS, spec.blocks_per_slot), jnp.int32)
    lanes = jax.ShapeDtypeStruct((SLOTS,), jnp.int32)
    key = jax.random.PRNGKey(2)
    decode = eng.decode_step().lower(params, cache, lanes, lanes, bt, key)
    chunk = eng.prefill_chunk_step(CHUNK).lower(
        params, cache, jax.ShapeDtypeStruct((SLOTS, CHUNK), jnp.int32),
        lanes, lanes, bt, key)
    return {"decode": decode.compile().as_text(), "chunk": chunk.compile().as_text()}


@pytest.fixture(scope="module")
def hlos(model, mesh):
    """The compiled programs with scopes off (built first), then on."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(obs, "scope", lambda name: contextlib.nullcontext())
        off = {"train": _train_hlo(model, mesh), **_serve_hlos(model, mesh)}
    on = {"train": _train_hlo(model, mesh), **_serve_hlos(model, mesh)}
    return off, on


def test_train_step_carries_its_scopes(hlos):
    _, on = hlos
    assert TRAIN_SCOPES <= set(scopes_of(on["train"]))


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_serving_steps_carry_their_scopes(hlos, program):
    _, on = hlos
    assert SERVE_SCOPES <= set(scopes_of(on[program]))


def test_backward_ops_keep_their_scope(hlos):
    """Ops of the backward pass (JAX's transpose/jvp wrappers around the
    name) count to the scope, and reduce-scatters appear only there."""
    _, on = hlos
    names = re.findall(r'op_name="([^"]*)"', on["train"])
    back = [n for n in names if "transpose(" in n and obs.PREFIX + "attention" in n]
    assert back
    assert "qsdp_reduce_scatter" not in scopes_of(on["decode"])


@pytest.mark.parametrize("program,name", [("train", "jit_train_step"),
                                          ("decode", "jit_serve_decode"),
                                          ("chunk", "jit_serve_prefill_chunk")])
def test_programs_are_named(hlos, program, name):
    off, on = hlos
    assert module_name(on[program]) == name
    assert module_name(off[program]) == name


@pytest.mark.parametrize("program", ["train", "decode", "chunk"])
def test_scopes_change_only_metadata(hlos, program):
    off, on = hlos
    assert not scopes_of(off[program])  # the null scope really was in force
    assert strip_metadata(on[program]) == strip_metadata(off[program])


def _run_scheduler(model, mesh, n_requests=5):
    params = model.init_params(jax.random.PRNGKey(0))
    sched = ContinuousScheduler(model, mesh, _serve_spec(), params,
                                gather_key=jax.random.PRNGKey(3),
                                prefill_chunk=CHUNK, prefill_buckets=2)
    rng = np.random.default_rng(0)
    for i in range(n_requests):  # more requests than slots: some queue
        sched.submit(Request(rid=f"r{i}", prompt=rng.integers(0, 512, 5 + 7 * i).tolist(),
                             max_new_tokens=3 + i % 3))
    return sched, sched.run()


@pytest.fixture(scope="module")
def scheduler_run(model, mesh):
    return _run_scheduler(model, mesh)


def test_admit_time_lies_between_submit_and_first_token(scheduler_run):
    _, done = scheduler_run
    assert len(done) == 5
    for c in done.values():
        assert c.submit_time <= c.admit_time <= c.first_token_time <= c.finish_time
        assert c.submit_step <= c.admit_step <= c.first_token_step
    # two slots for five requests: the later ones waited for a slot
    assert max(c.admit_time - c.submit_time for c in done.values()) > 0


def test_wait_counters_sum_the_requests_waits(scheduler_run):
    sched, done = scheduler_run
    st = sched.stats()
    assert st["first_tokens"] == len(done)
    assert st["queue_wait_s"] == pytest.approx(
        sum(c.admit_time - c.submit_time for c in done.values()), rel=1e-9)
    assert st["prefill_wait_s"] == pytest.approx(
        sum(c.first_token_time - c.admit_time for c in done.values()), rel=1e-9)


def test_spans_change_nothing_without_a_profiler(model, mesh, scheduler_run):
    """With no profiler running a span is an inert context: the scheduler
    streams the same tokens with every span replaced by a null context."""
    with obs.span("test.inert"):
        pass
    _, done = scheduler_run
    with pytest.MonkeyPatch.context() as mp:
        import repro.serve.scheduler as sched_mod
        mp.setattr(sched_mod, "span", lambda name: contextlib.nullcontext())
        _, bare = _run_scheduler(model, mesh)
    assert {r: c.tokens.tolist() for r, c in done.items()} == \
        {r: c.tokens.tolist() for r, c in bare.items()}


def test_spans_land_in_a_profiler_trace(model, mesh, tmp_path):
    """Under ``jax.profiler.trace`` the scheduler's spans are host events
    named ``repro.sched.*``."""
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)):
        _run_scheduler(model, mesh, n_requests=3)
    files = list(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    assert files
    pd = ProfileData.from_file(str(files[0]))
    names = {e.name for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    assert {"repro.sched.admit", "repro.sched.prefill_launch", "repro.sched.prefill_sync",
            "repro.sched.decode_launch", "repro.sched.decode_sync",
            "repro.sched.emit"} <= names
