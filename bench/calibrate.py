"""Readings that a cell's correctness limits are set from; not run by the
benchmark's own runs.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 --control-seeds 3 \
        --seconds 10

For each seed, one run of the cell's timed path (a short window) and its
comparison with the plain reference, as ``bench/run.py`` makes it; for the
first ``--control-seeds`` seeds also the control (the reference computed at
the next lower precision than the configuration states: 4-bit weights and
gradients for its 8-bit ones) and the faults the cell can have, each
compared with the reference in the same way.  One JSON line per seed on
standard output.

Training faults: half of each batch left out (the mean over the rest), and
a step that returns its state unchanged (which reads exactly 1 on
``update_norm_gap`` and needs no run).  Serving fault: every served token
replaced by another drawn from the seed.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)

    import numpy as np

    from bench import harness
    from bench.drivers import serve, train

    spec = harness.cell_spec(args.workload)
    device = harness.require_chips(spec["chips"])
    harness.enable_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    kind = spec["traffic"]["kind"]
    serve_compare = serve.compare
    for n, seed in enumerate(seeds):
        extra = {}
        with_control = n < args.control_seeds
        if kind == "train":
            def compare(spec, seed, feed, prog):
                ref = train.reference_run(spec, seed, feed)
                if with_control:
                    ctl = train.reference_run(spec, seed, feed, bits=4)
                    extra["control"] = train.gap_numbers(ctl, ref)
                    half = train.reference_run(spec, seed, feed,
                                               rows_used=feed.batch // 2)
                    extra["fault_half_batch"] = train.gap_numbers(half, ref)
                nums = train.gap_numbers(prog, ref)
                lim = spec["cell"]["limits"]
                return {k: {"value": v, "limit": lim[k]} for k, v in nums.items()}
            train.compare = compare
            driver = train
        else:
            def compare(spec, seed, served):
                out = serve_compare(spec, seed, served)
                if with_control:
                    picked = serve.sample(served, seed, spec["cell"]["check_tokens"])
                    extra["control"] = {"served_logit_gap": max(
                        serve.reference_gaps(spec, seed, picked, bits=4))}
                    rng = np.random.default_rng(seed)
                    vocab = spec["config"]["vocab_size"]
                    altered = [(p, rng.integers(0, vocab, len(s)).tolist())
                               for p, s in picked]
                    extra["fault_token_altered"] = {"served_logit_gap": max(
                        serve.reference_gaps(spec, seed, altered))}
                return out
            serve.compare = compare
            driver = serve
        t0 = time.perf_counter()
        res = driver.run(spec, seed, args.seconds, False, t0, device)
        line = {"seed": seed, "correct": res["correct"],
                "checks": {k: v["value"] for k, v in res["checks"].items()},
                "metrics": {k: v["value"] for k, v in res["metrics"].items()},
                "memory_peak_bytes": res["device"]["memory_peak_bytes"],
                "seconds": time.perf_counter() - t0, **extra}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
