"""The program's names read out of a trace (bench/program_trace.py), on
small synthetic traces worked out by hand (times in ns), and the training
step's scopes on a tiny step compiled on the CPU."""
import importlib.util

import pytest

from bench import harness, program_trace as pt, trace_reduce
from bench.tests import tiny

FWD = "jit(train_step)/while/body/closed_call/jvp()/repro.attention/dot_general"
BWD = "jit(train_step)/while/body/transpose(jvp(repro.attention))/dot_general"
REMAT = "jit(train_step)/while/body/checkpoint/repro.mlp/mul"
KV_IN_ATTN = "jit(serve_decode)/while/body/repro.attention/repro.kv_write/scatter"


def metric(name):
    spec = importlib.util.spec_from_file_location(
        name, harness.BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_scope_through_transform_wrappers():
    assert pt.scope_of(FWD) == "attention"
    assert pt.scope_of(BWD) == "attention"
    assert pt.scope_of(REMAT) == "mlp"
    assert pt.scope_of("jit(train_step)/while/body/add") is None


def test_innermost_scope_wins():
    assert pt.scope_of(KV_IN_ATTN) == "kv_write"


def test_program_names():
    assert pt.program_of("jit_serve_decode(123)") == "serve_decode"
    assert pt.program_of("jit_train_step") == "train_step"
    assert pt.program_of("jit_serve_prefill_chunk(9)") == "serve_prefill_chunk"
    assert pt.program_of("fusion.3") is None


def test_instruction_scopes():
    hlo = ('  %fusion.12 = f32[4]{0} fusion(%p), kind=kLoop, calls=%fc, '
           f'metadata={{op_name="{BWD}" source_file="a.py" source_line=3}}\n'
           '  ROOT %copy.2 = f32[4]{0} copy(%fusion.12), metadata={op_name="jit(f)/copy"}\n'
           '  %param.1 = f32[4]{0} parameter(0)\n')
    assert pt.instruction_scopes(hlo) == {"fusion.12": "attention", "copy.2": None}


# a serving slice: window [100, 1000]; decode launches [50, 250] (cut by
# the window's start: left out), [300, 500] and [600, 880]; a chunk launch
# [900, 980]; one more decode launch runs past the window's end.  The decode
# and chunk programs reuse the name fusion.1 for ops of different scopes.
HOST = [("bench.window", 100, 1000), ("bench.sched_step", 100, 1000),
        ("repro.sched.decode_launch", 240, 300), ("repro.sched.decode_sync", 500, 600),
        ("repro.sched.emit", 880, 900), ("other.span", 0, 1000)]
OPS = [("fusion.1", 60, 240),      # in the cut launch
       ("while.5", 300, 500),      # a loop: only contains others
       ("fusion.1", 300, 380),
       ("fusion.2", 380, 450),
       ("fusion.3", 450, 500),
       ("fusion.1", 600, 700),
       ("fusion.4", 700, 850),
       ("fusion.1", 900, 960),
       ("fusion.9", 960, 980),
       ("fusion.1", 990, 1100)]
MODS = [("jit_serve_decode(1)", 50, 250), ("jit_serve_decode(1)", 300, 500),
        ("jit_serve_decode(1)", 600, 880), ("jit_serve_prefill_chunk(2)", 900, 980),
        ("jit_serve_decode(1)", 990, 1100)]
SCOPES = {"serve_decode": [{"fusion.1": "qsdp_gather", "fusion.2": "kv_write",
                            "fusion.3": None, "fusion.4": "attention"}],
          # two compiled forms (two bucket shapes): the launch takes the one
          # that names its ops
          "serve_prefill_chunk": [{"fusion.1": "attention", "fusion.5": "mlp"},
                                  {"fusion.1": "mlp", "fusion.9": "kv_write"}]}


def test_ops_attributed_to_programs_by_time():
    red = pt.reduce_programs({"host": HOST, "devices": [{"ops": OPS, "modules": MODS}]},
                             1, SCOPES)
    assert red["window_s"] == pytest.approx(900e-9)
    dec = red["programs"]["serve_decode"]
    assert dec["launches"] == 2  # the cut launches at both edges are left out
    assert dec["busy_s"] == pytest.approx(450e-9)
    assert dec["scope_s"] == pytest.approx({"qsdp_gather": 180e-9, "kv_write": 70e-9,
                                            "unscoped": 50e-9, "attention": 150e-9})
    assert dec["unscoped_ops"] == pytest.approx({"fusion.3": 50e-9})
    chunk = red["programs"]["serve_prefill_chunk"]
    assert chunk["scope_s"] == pytest.approx({"mlp": 60e-9, "kv_write": 20e-9})


def test_idle_gap_labelled_by_a_program_span():
    """Program spans nest inside ``bench.sched_step``: a gap takes the
    innermost of either kind, and a gap under neither is the step's."""
    red = pt.reduce_programs({"host": HOST, "devices": [{"ops": OPS, "modules": MODS}]},
                             1, SCOPES)
    # idle [240, 300] in decode_launch, [500, 600] in decode_sync, [850,
    # 900] (middle 875, before emit starts) and [980, 990] in sched_step
    assert red["idle_gaps"] == pytest.approx({"sched.decode_launch": 60e-9,
                                              "sched.decode_sync": 100e-9,
                                              "sched_step": 60e-9})


def test_extract_keeps_both_kinds_of_host_span():
    class E:
        def __init__(self, name, s, d):
            self.name, self.start_ns, self.duration_ns = name, s, d

    class L:
        def __init__(self, name, events):
            self.name, self.events = name, events

    class Pl:
        def __init__(self, name, lines):
            self.name, self.lines = name, lines

    class PD:
        planes = [Pl("/host:CPU", [L("python", [E("bench.window", 0, 100),
                                                E("repro.sched.emit", 10, 5),
                                                E("other", 0, 1)])]),
                  Pl("/device:TPU:0", [L("XLA Ops", [E("%fusion.1 = f32[] fusion()", 2, 3)]),
                                       L("XLA Modules", [E("jit_serve_decode(4)", 1, 9)])])]

    ex = pt.extract(PD())
    assert ex["host"] == [("bench.window", 0, 100), ("repro.sched.emit", 10, 15)]
    assert ex["devices"] == [{"ops": [("fusion.1", 2, 5)],
                              "modules": [("jit_serve_decode(4)", 1, 10)]}]


def test_reduced_trace_readers():
    """On the benchmark's reduced trace: the named programs' launches, the
    prefill share, and nothing from a program without names."""
    ex = {"host": HOST, "devices": [{"ops": OPS, "modules": MODS}]}
    red = trace_reduce.reduce(ex, 1)
    assert pt.launches(red, "serve_decode") == [[(300, 500), (600, 880)]]
    assert metric("serve_prefill_share")({"trace": red}) == pytest.approx(
        100 * 80 / (200 + 280 + 80))
    bare = {"host": HOST, "devices": [{"ops": OPS, "modules": [("jit_fn(1)", 300, 500)]}]}
    red = trace_reduce.reduce(bare, 1)
    assert metric("serve_prefill_share")({"trace": red}) is None
    ctx = {"trace": red, "steps": 3, "spec": None, "chips": 1}
    for m in ("train_attention_ms", "train_mlp_ms", "train_qsdp_gather_ms",
              "train_qsdp_reduce_scatter_ms"):
        assert metric(m)(ctx) is None  # no train_step: nothing is compiled


def test_train_step_lowered_as_the_run_lowers_it():
    """The step the readers compile is the run's own program: the same
    lowering as the cell's step on its real state and batch, so the
    compile cache returns the run's executable; and it carries the four
    training scopes."""
    import jax
    from bench.drivers import train as train_cell

    spec = tiny.spec("gpt350m-train-1chip")
    devices = jax.devices()[:1]
    model, opt, mesh, ms, state, step, _ = train_cell.build(spec["config"], 1, 5, devices)
    feed = train_cell.Feed(spec["config"]["vocab_size"], spec["traffic"], 5,
                           spec["config"]["train"]["batch_per_chip"], mesh, ms.fsdp_axes)
    with mesh:
        real = step.lower(state, feed(0), jax.random.fold_in(jax.random.PRNGKey(9), 2))
    lowered = pt.train_step_lowered(spec, 1)
    assert lowered.as_text() == real.as_text()
    scopes = set(pt.instruction_scopes(lowered.compile().as_text()).values())
    assert {"attention", "mlp", "qsdp_gather", "qsdp_reduce_scatter"} <= scopes


def test_train_scope_reader():
    """Only ``train_step`` ran: each scope's op time per step; the scopes'
    times add up with the unscoped ones to the op time."""
    spec = tiny.spec("gpt350m-train-1chip")
    scopes = pt.instruction_scopes(pt.train_step_text(spec, 1))
    names = {s: next(k for k, v in scopes.items() if v == s)
             for s in ("attention", "mlp", "qsdp_gather", "qsdp_reduce_scatter")}
    unscoped = next(k for k, v in scopes.items() if v is None)
    op_s = {names["attention"]: 0.3, names["mlp"]: 0.2, names["qsdp_gather"]: 0.05,
            names["qsdp_reduce_scatter"]: 0.04, unscoped: 0.01}
    trace = {"op_s": op_s, "host": [], "devices": [{"modules": [("jit_train_step(1)", 0, 9)]}]}
    ctx = {"trace": trace, "steps": 2, "spec": spec, "chips": 1}
    got = {s: pt.train_scope_ms(ctx, s) for s in names}
    assert got == pytest.approx({"attention": 150.0, "mlp": 100.0, "qsdp_gather": 25.0,
                                 "qsdp_reduce_scatter": 20.0})
    trace["devices"][0]["modules"][0] = ("jit_fn(2)", 0, 9)
    assert pt.train_scope_ms(dict(ctx, trace=trace), "mlp") is None  # no train_step


def test_serve_scopes_name_the_decode_and_chunk_forms():
    """The split tool's serving programs: the decode step and one form of
    the chunked prefill per bucket, each carrying the serving scopes."""
    from bench import scope_split

    spec = tiny.spec("gpt1.3b-serve-chat-1chip")
    scopes = scope_split.serve_scopes(spec)
    assert len(scopes["serve_decode"]) == 1
    assert len(scopes["serve_prefill_chunk"]) == spec["config"]["serve"]["prefill_buckets"]
    for forms in scopes.values():
        for form in forms:
            assert {"attention", "mlp", "kv_write", "qsdp_gather"} <= set(form.values())
