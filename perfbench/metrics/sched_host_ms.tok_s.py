"""sched_host_ms.tok_s: mean host time of a scheduler tick in the traced
window, outside its one sync (the program's ``max.sched.tick`` spans carry
each tick's ``host_s``): the time the chip waits on the host per tick."""

from pbench import spans


def read(run):
    got = spans.of_run(run)
    ticks = spans.in_window(got, spans.TICK) if got else []
    if not ticks:
        return None
    return 1000.0 * spans.attr_sum(ticks, "host_s") / len(ticks)
