"""queue_ms_p90: p90 of the server's queue span (submit to admission,
``usage.queue_ms``) over the window's finished requests."""

from pbench.readers import usage_p90


def read(run):
    return usage_p90(run, "queue_ms")
