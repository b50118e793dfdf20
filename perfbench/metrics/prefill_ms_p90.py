"""prefill_ms_p90: p90 of the server's prefill span (admission to first
token at the scheduler's sync, ``usage.prefill_ms``) over the window's
finished requests."""

from pbench.readers import usage_p90


def read(run):
    return usage_p90(run, "prefill_ms")
