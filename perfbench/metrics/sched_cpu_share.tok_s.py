"""sched_cpu_share.tok_s: the worker thread's CPU time over the host part
of its ticks in the traced window (tick attributes ``cpu_s`` and
``host_s``); the rest it waited for the GIL or was not run."""

from pbench import spans


def read(run):
    got = spans.of_run(run)
    ticks = spans.in_window(got, spans.TICK) if got else []
    host = spans.attr_sum(ticks, "host_s")
    if host <= 0:
        return None
    return 100.0 * spans.attr_sum(ticks, "cpu_s") / host
