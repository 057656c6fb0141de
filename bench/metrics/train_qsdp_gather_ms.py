"""Device time of the training step under the program's scope ``qsdp_gather``:
QSDP's weight gather (quantize, wire collective, dequantize), in the forward
and its recomputation. In ms per step of the traced window, averaged over
the chips (bench/program_trace.py)."""
from bench import program_trace


def read(ctx):
    return program_trace.train_scope_ms(ctx, "qsdp_gather")
