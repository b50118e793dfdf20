"""mfu.ttft: model operations of the prompts prefilled in the traced
window, over the device time of the prefill programs (``_prefill_impl``
modules in the trace) times the chip's bf16 peak."""

from pbench import trace
from pbench.readers import prefill_flops_in_window


def read(run):
    if run.trace is None:
        return None
    dev_s = trace.module_s(run.trace, "_prefill_impl")
    work = prefill_flops_in_window(run)
    if dev_s <= 0 or work == 0:
        return None
    return 100.0 * work / (dev_s * run.peak()["bf16_flops"])
