"""What every cell shares: finding a cell's files by name, the device check,
the compile cache, seeds, host spans, the profiler window and the result
line.

A cell is named in ``BENCHMARK.json``; its configuration, traffic mix and
correctness limits are read from ``bench/configs/<config>.json``,
``bench/traffic/<traffic>.json`` and ``bench/cells/<workload>.json``, and the
per-layer metrics from ``bench/metrics/<metric>.py``.  Nothing here names a
cell, a configuration or a metric.
"""
from __future__ import annotations

import contextlib
import glob
import importlib.util
import json
import os
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
OUT = ROOT / "bench_out"  # run-time outputs (traces); listed in .gitignore
SPAN_PREFIX = "bench."


class NoDevice(SystemExit):
    """Raised when JAX finds no accelerator, or fewer chips than the cell
    asks for: the run exits non-zero and prints no result."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_spec(workload: str) -> dict:
    """Everything a run of `workload` needs, joined from its files."""
    bench = load_json(ROOT / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise SystemExit(f"unknown workload {workload!r}")
    per_layer = [m for m in bench["per_layer"]
                 if workload in m.get("workloads", [workload])]
    end_to_end = [m for m in bench["end_to_end"]
                  if workload in m.get("workloads", [workload])]
    return {
        "name": workload,
        "chips": entry["chips"],
        "config": load_json(BENCH / "configs" / f"{entry['config']}.json"),
        "traffic": load_json(BENCH / "traffic" / f"{entry['traffic']}.json"),
        "cell": load_json(BENCH / "cells" / f"{workload}.json"),
        "end_to_end": end_to_end,
        "per_layer": per_layer,
    }


def seed32(seed: int) -> int:
    """Map any whole-number seed (the driver's exceed 32 bits) to the
    non-negative int32 that ``jax.random.PRNGKey`` takes."""
    return int(seed) % 2147483647


def require_chips(n: int) -> dict:
    """Stamp the device JAX finds; refuse anything but `n` or more TPUs."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX found {devs[0].platform!r} devices")
    if len(devs) < n:
        raise NoDevice(f"the cell asks for {n} chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": n}


def peaks(device_kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in bench/peaks.json")
    return table[device_kind]


def enable_compile_cache() -> str:
    """The program's persistent compile cache (``$JAX_COMPILATION_CACHE_DIR``
    or ``.jax_cache/`` in the checkout), with every program cached, however
    quick its compile, so that only a cell's first run compiles."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache as enable

    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def span(name: str):
    """A host span in the profiler's trace (``bench.<name>``); costs about a
    microsecond when no trace is being taken."""
    import jax

    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of `devices`."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)


class Profile:
    """The profiler over one window (or a slice of it), written under
    ``bench_out/trace/`` (cleared first, so a run never reads an older
    trace)."""

    def __init__(self, workload: str, enabled: bool):
        self.enabled = enabled
        self.dir = OUT / "trace" / workload
        self._span = None

    def start(self) -> None:
        import jax

        if not self.enabled or self._span is not None:
            return
        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True)
        jax.profiler.start_trace(str(self.dir))
        self._span = span("window")
        self._span.__enter__()

    def stop(self) -> None:
        import jax

        if not self._span:
            return
        self._span.__exit__(None, None, None)
        self._span = False  # stopped; never restarted
        jax.profiler.stop_trace()

    @contextlib.contextmanager
    def window(self):
        self.start()
        try:
            yield
        finally:
            self.stop()

    def reduce(self, n_devices: int) -> dict:
        from bench import trace_reduce

        files = glob.glob(str(self.dir / "plugins" / "profile" / "*" / "*.xplane.pb"))
        if not files:
            raise RuntimeError(f"no trace written under {self.dir}")
        red = trace_reduce.reduce_file(max(files, key=os.path.getmtime),
                                       n_devices=n_devices)
        shutil.rmtree(self.dir, ignore_errors=True)
        return red


def load_metric(name: str):
    """The reader of per-layer metric `name`: ``bench/metrics/<name>.py``,
    whose ``read(ctx)`` returns the value, or None where it finds nothing
    to read."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def per_layer_metrics(spec: dict, ctx: dict) -> dict:
    out = {}
    for m in spec["per_layer"]:
        v = load_metric(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def result_line(spec: dict, checks: dict, attempted: int, failed: int,
                device: dict, prof: Profile, ctx: dict, extra: dict) -> dict:
    """The run's result: its end-to-end metrics (``ctx["e2e"]``), or with a
    trace its per-layer metrics, each metric's reader given `ctx` and the
    reduced trace; `extra` keys, then the compared numbers (`checks`),
    last."""
    result = {
        "correct": all(v["value"] <= v["limit"] for v in checks.values()),
        "attempted": attempted, "failed": failed,
        "device": dict(device, memory_peak_bytes=ctx["memory_peak_bytes"]),
    }
    if prof.enabled:
        red = prof.reduce(device["count"])
        result["metrics"] = per_layer_metrics(spec, dict(ctx, trace=red))
        result["device"].update(busy_s=red["busy_s"], window_s=red["window_s"])
        result["breakdown"] = red["breakdown"]
    else:
        result["metrics"] = {m["name"]: {"value": ctx["e2e"][m["name"]], "unit": m["unit"]}
                             for m in spec["end_to_end"]}
    return dict(result, **extra, checks=checks)


def emit(result: dict) -> None:
    """The compared numbers as the last lines of standard error, then the
    result as the last line of standard output (``checks`` comes last)."""
    checks = result.pop("checks")
    for k, v in checks.items():
        print(f"check {k}: {v['value']!r} limit {v['limit']!r}", file=sys.stderr)
    result["checks"] = checks
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
