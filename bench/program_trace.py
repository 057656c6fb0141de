"""The program's own names in a profiler trace: named programs, device
scopes and host spans.

The program (``src/repro/obs.py``) names what it runs with one prefix,
``repro.``:

* each jitted program, so a device's ``XLA Modules`` line reads
  ``jit_train_step``, ``jit_serve_decode``, ``jit_serve_prefill_chunk``...;
* the device work of each layer, as a scope in every op's ``op_name``
  metadata (``repro.attention``, ``repro.mlp``, ``repro.kv_write``,
  ``repro.qsdp_gather``, ``repro.qsdp_reduce_scatter``); an op takes its
  innermost scope, and the ops of a backward pass, under JAX's
  ``transpose(jvp(...))`` and ``checkpoint`` wrappers, keep it;
* the scheduler's host work, as spans ``repro.sched.*``.

A program that predates these names (one jitted as ``jit_fn``, with no
scopes) reads as absent: every reader here returns None for it.

Two ways in:

* From the reduced trace (``bench/trace_reduce.py``), which keeps each
  device's module events and the op time by instruction name:
  :func:`launches` reads the named programs, and :func:`train_scope_ms`
  maps the training step's instruction names to their scopes through the
  compiled step's HLO text (compiled again from the cell's files; the
  persistent compile cache hands back the executable the run used).
* From a whole trace (``.xplane.pb``): :func:`extract` adds the program's
  host spans to the benchmark's; :func:`reduce_programs` attributes each op
  to the launch that contains it in time and splits each program's device
  time by scope (the instruction scopes of its compiled forms), counting
  only launches that lie wholly in the window, and labels each idle gap by
  the innermost span, of either prefix, covering its middle.  A trace's op
  events carry no ``op_name`` themselves (their statistics are offsets and
  durations), hence the compiled text.  ``bench/scope_split.py`` runs a
  cell and prints that split.
"""
from __future__ import annotations

import re
from collections import defaultdict

from bench import trace_reduce

PREFIX = "repro."  # the program's repro.obs.PREFIX
SPAN_PREFIXES = (PREFIX, trace_reduce.SPAN_PREFIX)
_SCOPE = re.compile(re.escape(PREFIX) + r"(\w+)")
_MODULE = re.compile(r"^jit_(\w+?)(?:\(.*)?$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?op_name=\"([^\"]*)\"")


def program_of(module_event: str):
    """``jit_serve_decode(123)`` -> ``serve_decode``."""
    m = _MODULE.match(module_event)
    return m.group(1) if m else None


def scope_of(op_name: str):
    """The innermost program scope in an op's ``op_name``, or None."""
    found = _SCOPE.findall(op_name)
    return found[-1] if found else None


def instruction_scopes(hlo_text: str) -> dict:
    """{instruction name: its scope or None} over a compiled HLO module."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            out[m.group(1)] = scope_of(m.group(2))
    return out


def launches(trace: dict, program: str) -> list:
    """Per device, the (start, end) of each launch of `program` that lies
    wholly inside the traced window (the reduced trace's ``window`` span)."""
    win = [(s, e) for n, s, e in trace["host"] if n == "window"]
    t0, t1 = win[0] if win else (float("-inf"), float("inf"))
    return [[(s, e) for n, s, e in d["modules"]
             if program_of(n) == program and t0 < s and e < t1]
            for d in trace["devices"]]


def programs_in(trace: dict) -> set:
    """The programs whose launches the reduced trace holds."""
    return {program_of(n) for d in trace["devices"] for n, _, _ in d["modules"]}


# -- the training step's scopes, from the reduced trace -----------------------

def train_step_lowered(spec: dict, chips: int):
    """The cell's training step lowered as ``bench/drivers/train.py``
    builds it, on abstract state, batch and key, so the lowering, and with
    it the compile cache's key, is the run's own."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.train.step import state_pspecs

    from bench.drivers import train as train_cell

    built = {}

    def build():
        model, opt, mesh, ms, state, step, _ = train_cell.build(
            spec["config"], chips, 0, jax.devices()[:chips])
        built.update(model=model, opt=opt, mesh=mesh, ms=ms, step=step)
        return state

    shapes = jax.eval_shape(build)
    model, opt, mesh, ms = (built[k] for k in ("model", "opt", "mesh", "ms"))
    specs = state_pspecs(model, quantized_moments=getattr(opt, "quantized_moments", False))
    state = jax.tree.map(
        lambda s, p: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=NamedSharding(mesh, p)),
        shapes, specs)
    rows = spec["config"]["train"]["batch_per_chip"] * chips
    tok = jax.ShapeDtypeStruct((rows, spec["traffic"]["seq_len"]), jnp.int32,
                               sharding=NamedSharding(mesh, P(ms.fsdp_axes)))
    step_key = jax.random.fold_in(jax.random.PRNGKey(1), 0)
    with mesh:
        return built["step"].lower(state, {"tokens": tok, "labels": tok}, step_key)


def train_step_text(spec: dict, chips: int) -> str:
    """The compiled HLO text of the cell's training step (from the compile
    cache, where the run left the executable)."""
    return train_step_lowered(spec, chips).compile().as_text()


def train_scope_ms(ctx: dict, scope: str):
    """Device time under `scope` in the training step, ms per step of the
    window: the op time of every instruction of the step whose innermost
    scope is `scope`, averaged over the chips (``op_s``).  None where the
    window ran no ``train_step``, or where the step carries no scopes.  The
    small programs beside it in the window (the step key's ``threefry``,
    microseconds a step) may reuse an instruction name of the step's, and
    then add their time to its scope."""
    tr = ctx.get("trace")
    if not tr or not ctx.get("steps") or "train_step" not in programs_in(tr):
        return None
    if "train_step_scopes" not in ctx:  # one compile for every reader of the run
        ctx["train_step_scopes"] = instruction_scopes(
            train_step_text(ctx["spec"], ctx["chips"]))
    scopes = ctx["train_step_scopes"]
    if not any(scopes.values()):
        return None
    t = sum(v for k, v in tr["op_s"].items() if scopes.get(k) == scope)
    return t / ctx["steps"] * 1e3


# -- a whole trace: programs, scopes and spans ----------------------------------


def extract(pd) -> dict:
    """``trace_reduce.extract``, with the program's host spans added to the
    benchmark's."""
    ex = trace_reduce.extract(pd)
    ex["host"] = sorted(ex["host"] + [
        (e.name, e.start_ns, e.start_ns + e.duration_ns)
        for plane in pd.planes if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events if e.name.startswith(PREFIX)],
        key=lambda s: s[1])
    return ex


def _span_at(host, t: float) -> str:
    """The innermost span of either prefix covering host time `t`."""
    best = None
    for name, s, e in host:
        if s > t:
            break
        if e >= t and (best is None or s >= best[1]):
            best = (name, s, e)
    if best is None:
        return "no span"
    return best[0].split(".", 1)[1]


def reduce_programs(ex: dict, n_devices: int, scopes: dict) -> dict:
    """Each program's launches, busy time and time by scope inside the
    window (the host span ``bench.window``), counting only launches that lie
    wholly inside it; the window's idle gaps by the innermost host span of
    either prefix; averaged over devices as ``trace_reduce.reduce`` does.

    An op belongs to the launch whose module event contains it in time;
    loop and call ops, which only contain others, are left out.  `scopes`
    maps a program to the instruction scopes (:func:`instruction_scopes`)
    of each of its compiled forms (one per shape it runs); a launch takes
    the form that names the most of its ops.  An op with no scope there
    counts as ``unscoped`` (``unscoped_ops``: the largest by time)."""
    host = sorted((s for s in ex["host"] if s[0].startswith(SPAN_PREFIXES)),
                  key=lambda s: s[1])
    win = [s for s in host if s[0] == trace_reduce.SPAN_PREFIX + "window"]
    devs = ex["devices"][:n_devices]
    if win:
        t0, t1 = win[0][1], win[0][2]
    else:
        t0 = min(s for d in devs for _, s, _ in d["ops"])
        t1 = max(e for d in devs for _, _, e in d["ops"])
    progs: dict = defaultdict(lambda: {"launches": 0.0, "busy_s": 0.0,
                                       "scope_s": defaultdict(float),
                                       "unscoped_ops": defaultdict(float)})
    gaps: dict = defaultdict(float)
    for d in devs:
        mods = sorted((s, e, program_of(n) or n) for n, s, e in d["modules"]
                      if t0 < s and e < t1)
        ops = sorted((s, e, n) for n, s, e in d["ops"]
                     if not trace_reduce.CONTAINER.match(n))
        j = 0
        for s, e, prog in mods:
            while j < len(ops) and ops[j][0] < s:
                j += 1
            inside = []
            while j < len(ops) and ops[j][0] < e:
                if ops[j][1] <= e:
                    inside.append(ops[j])
                j += 1
            form = max(scopes.get(prog) or [{}],
                       key=lambda f: sum(n in f for _, _, n in inside))
            p = progs[prog]
            p["launches"] += 1 / len(devs)
            p["busy_s"] += trace_reduce._length(
                trace_reduce.merge((a, b) for a, b, _ in inside)) * 1e-9 / len(devs)
            for a, b, n in inside:
                sc = form.get(n)
                p["scope_s"][sc or "unscoped"] += (b - a) * 1e-9 / len(devs)
                if sc is None:
                    p["unscoped_ops"][n] += (b - a) * 1e-9 / len(devs)
        busy = trace_reduce.merge((max(a, t0), min(b, t1)) for a, b, _ in ops
                                  if b > t0 and a < t1)
        for a, b in zip([(t0, t0)] + busy, busy + [(t1, t1)]):
            if b[0] > a[1]:
                gaps[_span_at(host, (a[1] + b[0]) / 2)] += (b[0] - a[1]) * 1e-9 / len(devs)
    return {"window_s": (t1 - t0) * 1e-9,
            "programs": {k: dict(v, scope_s=dict(v["scope_s"]),
                                 unscoped_ops=dict(sorted(v["unscoped_ops"].items(),
                                                          key=lambda kv: -kv[1])[:8]))
                         for k, v in progs.items()},
            "idle_gaps": dict(sorted(gaps.items(), key=lambda kv: -kv[1]))}
