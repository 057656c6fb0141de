"""Run one cell with a trace, and print where its device time went by
program and scope, and its idle time by host span.

    python3 bench/scope_split.py --workload <name> --seed <n> --seconds <s>

The cell runs as ``bench/run.py --trace 1`` runs it and prints the same
result line.  Before the run's own reduction removes the window's trace,
this reads it whole (``bench/program_trace.py``), with each program's
instruction scopes taken from its compiled text (compiled again from the
cell's files, so the compile cache hands back the run's executables), and
last it prints one more JSON line: for each program its launches, busy
time, time by scope and largest unscoped ops; the window's idle gaps by the
innermost host span, the benchmark's or the program's; the run's
end-to-end numbers as they read with the profiler on; and the seconds the
per-layer readers took.
"""
import json
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def serve_scopes(spec: dict) -> dict:
    """Instruction scopes of the serving cell's decode step and of its
    chunked prefill at each bucket, lowered on the scheduler that
    ``bench/drivers/serve.py`` builds, with the arguments its steps pass."""
    import jax
    import jax.numpy as jnp

    from bench.drivers import serve as serve_cell
    from bench.program_trace import instruction_scopes

    _, sched = serve_cell.build(spec["config"], spec["traffic"], 0, jax.devices()[:1])
    eng, lanes = sched.engine, jnp.zeros(sched.B, jnp.int32)
    head = (sched.params, sched.cache)
    tail = (jnp.asarray(sched.block_tables), sched.gather_key)

    def scopes(step, *args):
        return instruction_scopes(step.lower(*head, *args, *tail).compile().as_text())

    return {"serve_decode": [scopes(eng.decode_step(), lanes, lanes)],
            "serve_prefill_chunk": [
                scopes(eng.prefill_chunk_step(b), jnp.zeros((sched.B, b), jnp.int32),
                       lanes, lanes) for b in sched.buckets]}


def main(argv=None) -> int:
    from bench import harness, program_trace, run

    argv = list(argv if argv is not None else sys.argv[1:])
    spec = harness.cell_spec(argv[argv.index("--workload") + 1])
    seen = {}
    reduce, result_line = harness.Profile.reduce, harness.result_line
    per_layer_metrics = harness.per_layer_metrics

    def reduce_keeping_programs(self, n_devices):
        from jax.profiler import ProfileData

        try:
            if spec["traffic"]["kind"] == "train":
                scopes = {"train_step": [program_trace.instruction_scopes(
                    program_trace.train_step_text(spec, spec["chips"]))]}
            else:
                scopes = serve_scopes(spec)
            files = sorted(self.dir.glob("plugins/profile/*/*.xplane.pb"),
                           key=lambda p: p.stat().st_mtime)
            ex = program_trace.extract(ProfileData.from_file(str(files[-1])))
            seen.update(program_trace.reduce_programs(ex, n_devices, scopes))
        except Exception:  # the run's own reduction still runs and reports
            seen["error"] = traceback.format_exc()
        return reduce(self, n_devices)

    def result_line_keeping_e2e(spec, checks, attempted, failed, device, prof, ctx, extra):
        seen["e2e_traced"] = ctx["e2e"]
        return result_line(spec, checks, attempted, failed, device, prof, ctx, extra)

    def timed_metrics(spec, ctx):
        t = time.perf_counter()
        out = per_layer_metrics(spec, ctx)
        seen["metrics_s"] = time.perf_counter() - t
        return out

    harness.Profile.reduce = reduce_keeping_programs
    harness.result_line = result_line_keeping_e2e
    harness.per_layer_metrics = timed_metrics
    rc = run.main(argv + ["--trace", "1"])
    print(json.dumps({"scope_split": seen}), flush=True)
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
