"""Find the knee of a serving cell: the highest offered rate whose
completions keep pace with its arrivals.

    python3 bench/sweep.py --workload <name> --rates 1,2,3,4 --seconds 51

One process builds and warms the cell's scheduler once, then offers the
rates in the order given, each through the cell's traffic with its rate
replaced: a ramp of the traffic's ``ramp_s`` at that rate (on top of what
the rate before left running), then one measured window.  One JSON line per
rate: requests due in the window, how many had their first token and how
many finished inside it, the queue of requests waiting for a slot when the
window opened and when it closed, output tokens per second offered and
served, the TTFT percentiles and the inter-token gap, and the mean slot
occupancy.  A rate keeps pace while no more requests wait for a slot when
the window closes than when it opened (or one), and all but the last
request due in the window have had their first token inside it.  The
last line names the knee, the highest rate below the first that does not
keep pace, and four fifths of it, the rate the cell's traffic file takes.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    import jax

    from bench import harness, traffic
    from bench.drivers import serve

    spec = harness.cell_spec(args.workload)
    harness.require_chips(1)
    harness.enable_compile_cache()
    config, tr, cell = spec["config"], spec["traffic"], spec["cell"]
    setup, sched = serve.build(config, tr, args.seed, jax.devices()[:1])
    serve.warm(sched, config["vocab_size"])
    print(json.dumps({"setup_s": time.perf_counter() - T_START}), flush=True)
    knee, paced = None, True
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        reqs = traffic.request_trace(tr, args.seed + i, args.seconds, config["vocab_size"],
                                     rate=rate)
        reqs = [dataclasses.replace(r, rid=f"{i}-{r.rid}") for r in reqs]
        w = serve.open_loop(sched, reqs, args.seconds, tr.get("ramp_s", 0), cell["drain_s"])
        keep = ("window_requests", "first_tokens_in_window", "finished_in_window",
                "queued_at_open", "queued_at_close", "mean_occupancy", "decode_steps",
                "prefill_chunks", "kv_blocks_peak", "offered_tokens_per_s", "ttft_ms")
        print(json.dumps({"rate": rate, **w["e2e"], **{k: w[k] for k in keep}}),
              flush=True)
        paced = (w["queued_at_close"] <= max(1, w["queued_at_open"])
                 and w["first_tokens_in_window"] >= w["window_requests"] - 1)
        if not paced:
            break
        knee = rate
    print(json.dumps({"knee": knee, "cell_rate": None if knee is None else round(0.8 * knee, 3),
                      "saturated": not paced}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
