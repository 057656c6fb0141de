"""FLOP per token and quantization bytes, against counts made by hand for
gpt-350m (24 layers, d_model 1024, 16 x 64 heads, d_ff 4096, vocab 50304)."""
from bench import costs, harness

CFG = harness.load_json(harness.BENCH / "configs" / "gpt-350m.json")

# per layer: q, k, v, o 1024 x 1024 each; gate, up, down 1024 x 4096 each
LAYER = 4 * 1024 * 1024 + 3 * 1024 * 4096  # 16,777,216
HEAD = 50304 * 1024  # tied embedding used as the LM head: 51,511,296


def test_matmul_params():
    assert costs.matmul_params(CFG) == 24 * LAYER + HEAD == 454_164_480


def test_train_flops_per_token():
    attn = 6 * 24 * 2048 * 16 * 64  # causal: half of 2 x 2 x 2048 x 1024, x3
    assert attn == 301_989_888
    assert costs.train_flops_per_token(CFG, 2048) == 6 * 454_164_480 + attn


def test_quant_bytes_one_chip():
    # per element of a tensor, on one chip (local == full):
    # gather: read f32 4 + write code 1 + meta 8/1024, read code 1 + meta
    #         8/1024, write bf16 2
    # reduce-scatter: read f32 4 + write code 1 + meta, read code 1 + meta,
    #         write f32 4
    gather = 4 + 1 + 8 / 1024 + 1 + 8 / 1024 + 2
    reduce = 4 + 1 + 8 / 1024 + 1 + 8 / 1024 + 4
    layers = 24 * LAYER * (2 * gather + reduce)  # forward + remat gathers
    embed = HEAD * (gather + reduce)
    assert layers == 10_487_857_152
    assert embed == 928_813_056
    got = costs.quant_bytes_per_step(CFG, CFG["qsdp"], chips=1)
    assert got == layers + embed == 11_416_670_208


def test_quant_bytes_shrink_per_device_with_chips():
    one = costs.quant_bytes_per_step(CFG, CFG["qsdp"], chips=1)
    four = costs.quant_bytes_per_step(CFG, CFG["qsdp"], chips=4)
    assert four < one
