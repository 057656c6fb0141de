"""Peak device memory of a training run: peak_bytes_in_use on the fullest
chip, in GiB. The headroom sets the batch a job can run."""


def read(ctx):
    peak = ctx.get("memory_peak_bytes")
    return peak / 2**30 if peak else None
