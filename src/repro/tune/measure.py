"""Shortlist measurement: run the real jitted train step for a handful of
timed steps per candidate (the same harness benchmarks/bench_step.py uses),
so the plan's final ranking rests on measured medians, not only on the
analytic model."""
from __future__ import annotations

import time

import jax
import numpy as np

from ..core.qsdp import MeshSpec, QSDPConfig, layer_gather_launches
from ..launch.mesh import make_mesh
from ..models.transformer import Model
from ..optim import AdamWConfig, make_adamw
from ..train.step import init_train_state, make_jitted_train_step


def measure_train_step(mcfg, ms: MeshSpec, qcfg: QSDPConfig, batch: dict,
                       *, n_micro: int = 1, steps: int = 3,
                       seed: int = 0) -> dict:
    """Median per-step wall ms of `qcfg` on the given mesh/model/batch
    (compile + 1 warmup excluded), plus the analytic launch count so the
    plan records what the measurement exercised."""
    mesh = make_mesh(ms.shape, ms.axes)
    model = Model(mcfg, ms, qcfg)
    opt = make_adamw(AdamWConfig(lr=1e-3))
    state = init_train_state(model, opt, jax.random.PRNGKey(seed))
    step = make_jitted_train_step(model, opt, mesh, n_micro=n_micro)
    key = jax.random.PRNGKey(seed + 7)
    times = []
    with mesh:
        t0 = time.perf_counter()
        state, metrics = step(state, batch, key)  # compile
        float(metrics["loss"])
        compile_s = time.perf_counter() - t0
        # one more untimed step so the timed loop sees the steady state
        # (device-resident donated buffers, no sharding-driven recompile)
        state, metrics = step(state, batch, jax.random.fold_in(key, -1))
        float(metrics["loss"])
        for i in range(steps):
            t0 = time.perf_counter()
            state, metrics = step(state, batch, jax.random.fold_in(key, i))
            float(metrics["loss"])
            times.append(1e3 * (time.perf_counter() - t0))
    layer_names = [n for n in model.specs if n.startswith("layers/")]
    return {
        "step_ms_median": float(np.median(times)),
        "step_ms_all": [float(t) for t in times],
        "compile_s": float(compile_s),
        "loss_final": float(metrics["loss"]),
        "layer_gather_launches": layer_gather_launches(model.engine,
                                                       layer_names),
    }
