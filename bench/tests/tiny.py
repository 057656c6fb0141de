"""Tiny specs of the real cells for CPU tests: the cell's own files and
limits, with the model cut to two narrow layers and the traffic shrunk."""
from bench import harness

DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
TINY = dict(n_layers=2, d_model=256, n_heads=4, n_kv_heads=4, head_dim=64,
            d_ff=512, vocab_size=1024)


def spec(workload: str) -> dict:
    s = harness.cell_spec(workload)
    s["config"].update(TINY)
    if s["traffic"]["kind"] == "train":
        s["config"]["train"]["batch_per_chip"] = 2
        s["traffic"]["seq_len"] = 128
    else:
        s["config"]["serve"].update(slots=4, kv_pool_blocks=64, prefill_chunk=32)
        s["traffic"].update(
            rate=8.0, ramp_s=1.0, prompt_len={"median": 24, "sigma": 0.7, "min": 8, "max": 64},
            output_len={"median": 8, "sigma": 0.7, "min": 4, "max": 16})
        s["cell"].update(check_tokens=60, drain_s=30, trace_seconds=1)
    return s
