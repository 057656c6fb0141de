"""Training cells: the program's jitted QSDP train step, driven from the seed.

Set-up builds one object, the compiled step with its train state, from the
functions ``launch/train.py`` composes (``Model``, ``make_adamw``,
``init_train_state``, ``make_jitted_train_step``), and runs the first
``check_steps`` steps through the same call and feed as the window.  The
losses of those steps, the first gradient as the optimizer holds it after
one step (its first moment over ``1 - b1``), and the parameters' change
over the steps (read before the window's first step donates them) are what
the plain reference is compared with once the window has closed.
"""
from __future__ import annotations

import gc
import math
import statistics
import time


from bench import harness, traffic as traffic_gen
from bench.harness import span

MODEL_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
              "d_ff", "vocab_size", "rope_theta", "norm_eps")
TRAIN_KEYS = ("lr", "warmup_steps", "total_steps", "b1", "b2", "eps",
              "weight_decay", "grad_clip")


def model_dims(config: dict) -> dict:
    return {k: config[k] for k in MODEL_KEYS}


def build(config: dict, chips: int, seed: int, devices=None):
    """The program's step, state and mesh for `config` on `chips` devices."""
    import jax
    from repro.core.qsdp import MeshSpec, QSDPConfig
    from repro.launch.mesh import make_mesh
    from repro.models.config import ModelConfig
    from repro.models.transformer import Model
    from repro.optim import AdamWConfig, cosine_schedule, make_adamw
    from repro.train.step import init_train_state, make_jitted_train_step

    dims = model_dims(config)
    mcfg = ModelConfig(name=config["name"], arch_type="dense", tie_embeddings=True,
                       **dims)
    mesh = make_mesh((chips, 1), ("data", "model"), devices=devices)
    ms = MeshSpec(axes=("data", "model"), shape=(chips, 1))
    q = config["qsdp"]
    qsdp = QSDPConfig(weight_bits=q["weight_bits"], grad_bits=q["grad_bits"],
                      bucket_size=q["bucket_size"],
                      min_quant_size=q["min_quant_size"])
    model = Model(mcfg, ms, qsdp)
    t = config["train"]
    opt = make_adamw(AdamWConfig(
        lr=t["lr"], b1=t["b1"], b2=t["b2"], eps=t["eps"],
        weight_decay=t["weight_decay"], grad_clip=t["grad_clip"],
        schedule=cosine_schedule(t["lr"], t["warmup_steps"], t["total_steps"])))
    key = jax.random.PRNGKey(harness.seed32(seed))
    state = init_train_state(model, opt, key, mesh)
    step = make_jitted_train_step(model, opt, mesh)
    return model, opt, mesh, ms, state, step, key


class Feed:
    """Batches of the Markov corpus, made on the host and placed with the
    step's batch sharding; step i's rows are a pure function of (seed, i)."""

    def __init__(self, vocab, traffic, seed, batch, mesh, batch_axes):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        self.table = traffic_gen.markov_table(vocab, traffic["branching"],
                                              harness.seed32(seed))
        self.seed, self.batch, self.seq = harness.seed32(seed), batch, traffic["seq_len"]
        self.sharding = NamedSharding(mesh, P(batch_axes))
        self._put = jax.device_put

    def host(self, i):
        return traffic_gen.markov_batch(self.table, self.seed, i, self.batch, self.seq)

    def __call__(self, i):
        tokens, labels = self.host(i)
        return {"tokens": self._put(tokens, self.sharding),
                "labels": self._put(labels, self.sharding)}


def first_steps(model, state, step, feed, step_key, key, n: int, b1: float):
    """Run the first `n` steps through the window's own call and feed, and
    read what the reference is compared with: each step's loss, the first
    gradient as the optimizer holds it after one step (its first moment
    over ``1 - b1``), and the parameters' change over the `n` steps (the
    initial weights made again from the seed)."""
    import jax
    import jax.numpy as jnp

    norms = jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(v))) for k, v in t.items()})
    prog = {"loss": []}
    for i in range(n):
        with span("setup.check_step"):
            state, m = step(state, feed(i), step_key(i))
            prog["loss"].append(float(m["loss"]))
        if i == 0:
            prog["grad"] = {k: float(v) / (1 - b1) for k, v in norms(state.opt.mu).items()}
    init = jax.jit(model.init_params,
                   out_shardings=jax.tree.map(lambda a: a.sharding, state.params))
    p0 = init(key)
    prog["update"] = {k: float(v) for k, v in norms(
        jax.tree.map(jnp.subtract, state.params, p0)).items()}
    return state, prog


def run(spec: dict, seed: int, seconds: float, trace: bool, t_start: float,
        device: dict) -> dict:
    import jax
    import jax.numpy as jnp

    config, tr, cell, chips = spec["config"], spec["traffic"], spec["cell"], spec["chips"]
    batch = config["train"]["batch_per_chip"] * chips
    n_check = cell["check_steps"]
    devices = jax.devices()[:chips]
    with span("setup.build"):
        model, opt, mesh, ms, state, step, key = build(config, chips, seed, devices)
        feed = Feed(config["vocab_size"], tr, seed, batch, mesh, ms.fsdp_axes)
        step_key = lambda i: jax.random.fold_in(
            jax.random.PRNGKey(harness.seed32(seed) + 1), i)
        jax.block_until_ready(state)
    t_state = time.perf_counter()
    with mesh:
        state, prog = first_steps(model, state, step, feed, step_key, key, n_check,
                                  config["train"]["b1"])
        i = n_check
        nxt = feed(i)
        jax.block_until_ready(nxt)
    setup_s = time.perf_counter() - t_start

    # the window
    prof = harness.Profile(spec["name"], trace)
    tokens_per_step = batch * tr["seq_len"]
    done, failed = 0, 0
    with mesh, prof.window():
        t0 = time.perf_counter()
        while True:
            with span("step_dispatch"):
                state, m = step(state, nxt, step_key(i))
            i += 1
            with span("batch_make"):
                nxt = feed(i)
            with span("step_wait"):
                jax.block_until_ready((state, m))
            done += 1
            failed += not math.isfinite(float(m["loss"]))
            t1 = time.perf_counter()
            if t1 - t0 >= seconds:
                break
    window_s = t1 - t0
    peak = harness.memory_peak_bytes(devices)
    e2e = {"train_tokens_per_s": done * tokens_per_step / window_s,
           "setup_s": setup_s}
    del state, m, nxt
    gc.collect()

    checks = compare(spec, seed, feed, prog)
    ctx = {"e2e": e2e, "spec": spec, "chips": chips, "steps": done,
           "peaks": harness.peaks(device["kind"]), "memory_peak_bytes": peak}
    split = {"state_s": t_state - t_start, "first_steps_s": setup_s - (t_state - t_start)}
    return harness.result_line(spec, checks, done, failed, device, prof, ctx,
                               {"setup_split": split})


def gap_numbers(prog: dict, ref: dict) -> dict:
    """The three compared numbers of a training cell.

    loss_gap: the largest |loss| gap over the checked steps.
    grad_norm_gap, update_norm_gap: over leaves, the gap between the
    program's per-leaf norm and the reference's, over the larger of the
    reference's norm of that leaf and of the median leaf; leaves whose
    reference gradient is under a thousandth of the median leaf's (moved by
    round-off alone) are left out."""
    med_raw = statistics.median(ref["grad_raw"].values())
    leaves = [k for k, v in ref["grad_raw"].items() if v >= 1e-3 * med_raw]

    def worst(name):
        med = statistics.median(ref[name][k] for k in leaves)
        return max(abs(prog[name][k] - ref[name][k]) / max(ref[name][k], med)
                   for k in leaves)

    return {
        "loss_gap": max(abs(a - b) for a, b in zip(prog["loss"], ref["loss"])),
        "grad_norm_gap": worst("grad"),
        "update_norm_gap": worst("update"),
    }


def reference_run(spec: dict, seed: int, feed, **kw) -> dict:
    """The plain reference over the same first batches (control: `bits`;
    faults: `rows_used`)."""
    import importlib

    import jax

    config = spec["config"]
    ref_mod = importlib.import_module(f"bench.configs.{config['reference']}")
    batches = [feed.host(i) for i in range(spec["cell"]["check_steps"])]
    train = {k: config["train"][k] for k in TRAIN_KEYS}
    return ref_mod.train_steps(model_dims(config), train,
                               jax.random.PRNGKey(harness.seed32(seed)), batches,
                               rows=spec["cell"]["ref_rows_per_block"], **kw)


def compare(spec: dict, seed: int, feed, prog: dict) -> dict:
    ref = reference_run(spec, seed, feed)
    nums = gap_numbers(prog, ref)
    lim = spec["cell"]["limits"]
    return {k: {"value": v, "limit": lim[k]} for k, v in nums.items()}
