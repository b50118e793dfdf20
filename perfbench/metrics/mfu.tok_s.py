"""mfu.tok_s: model operations of every prompt and output token processed
in the traced window, over the window's seconds times the chip's bf16 peak."""

from pbench.readers import decode_flops_in_window, prefill_flops_in_window


def read(run):
    work = prefill_flops_in_window(run) + decode_flops_in_window(run)
    if work == 0:
        return None
    return 100.0 * work / (run.seconds * run.peak()["bf16_flops"])
