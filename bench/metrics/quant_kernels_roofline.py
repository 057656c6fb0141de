"""Share of their roofline that the quantize/pack and unpack/dequantize
kernels reach in a training step: the least time, the bytes the step's
quantization needs (bench/costs.py, from the parameter shapes and the QSDP
configuration) over peak HBM bandwidth, divided by the kernels' device time
in the trace, per device."""
import re

from bench import costs

KERNEL = re.compile(r"quantiz|pack")  # the Pallas kernels: *quantize_packed*, *dequantize_packed*


def read(ctx):
    tr = ctx.get("trace")
    if not tr:
        return None
    kernel_s = sum(v for k, v in tr["op_s"].items() if KERNEL.search(k))
    if kernel_s <= 0:
        return None
    cfg = ctx["spec"]["config"]
    need = costs.quant_bytes_per_step(cfg, cfg["qsdp"], ctx["chips"]) * ctx["steps"]
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / kernel_s
