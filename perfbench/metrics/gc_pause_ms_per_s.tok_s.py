"""gc_pause_ms_per_s.tok_s: milliseconds per second of the traced window
in which the serving process stood in garbage collection (the program's
``max.gc`` spans, every generation and thread)."""

from pbench import spans, trace


def read(run):
    got = spans.of_run(run)
    if got is None:
        return None
    return 1000.0 * spans.clipped_s(got, spans.GC) / trace.window_s(run.trace)
