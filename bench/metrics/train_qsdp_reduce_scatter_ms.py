"""Device time of the training step under the program's scope
``qsdp_reduce_scatter``: QSDP's gradient reduce-scatter (quantize, wire
collective, dequantize and sum). In ms per step of the traced window,
averaged over the chips (bench/program_trace.py)."""
from bench import program_trace


def read(ctx):
    return program_trace.train_scope_ms(ctx, "qsdp_reduce_scatter")
