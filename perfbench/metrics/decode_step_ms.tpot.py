"""decode_step_ms.tpot: device time of the decode-chunk program (its
``jit_decode_chunk`` modules) in the traced window per decode step the
scheduler counted in the window (``decode_steps``)."""

from pbench import trace

MODULE = "jit_decode_chunk"


def read(run):
    if run.trace is None:
        return None
    steps = run.sched["after"]["decode_steps"] - \
        run.sched["before"]["decode_steps"]
    dev_s = trace.module_s(run.trace, MODULE)
    if steps <= 0 or dev_s <= 0:
        return None
    return 1000.0 * dev_s / steps
