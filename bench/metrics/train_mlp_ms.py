"""Device time of the training step under the program's scope ``mlp``: the
SwiGLU block, forward, recomputation and backward together. In ms per step
of the traced window, averaged over the chips (bench/program_trace.py)."""
from bench import program_trace


def read(ctx):
    return program_trace.train_scope_ms(ctx, "mlp")
