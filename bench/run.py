"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json``; what it drives is read
from its files under ``bench/`` (see ``bench/harness.py``).  With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  The run exits non-zero, and prints no result, where JAX finds no
TPU or fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    spec = harness.cell_spec(args.workload)
    try:
        device = harness.require_chips(spec["chips"])
        harness.peaks(device["kind"])
    except (harness.NoDevice, KeyError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    harness.enable_compile_cache()
    driver = importlib.import_module(f"bench.drivers.{spec['traffic']['kind']}")
    result = driver.run(spec, args.seed, args.seconds, bool(args.trace),
                        T_START, device)
    harness.emit(result)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
