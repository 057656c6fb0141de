"""Model FLOP utilization of a training cell: trained tokens per second
times the model FLOP per token (bench/costs.py) over the chips' bf16 peak."""
from bench import costs


def read(ctx):
    tps = ctx["e2e"].get("train_tokens_per_s")
    if not tps:
        return None
    spec = ctx["spec"]
    flops = costs.train_flops_per_token(spec["config"], spec["traffic"]["seq_len"])
    return 100.0 * tps * flops / (ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
