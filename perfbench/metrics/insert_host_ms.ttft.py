"""insert_host_ms.ttft: mean host time of one admission in the traced
window, before its prefill runs (the program's ``max.engine.prefill_prep``
and ``max.engine.prefill_dispatch`` spans): serial with each first
token."""

from pbench import spans


def read(run):
    got = spans.of_run(run)
    preps = spans.in_window(got, spans.PREP) if got else []
    if not preps:
        return None
    dispatches = spans.in_window(got, spans.DISPATCH)
    total_ns = sum(sp[2] for sp in preps + dispatches)
    return total_ns / 1e6 / len(preps)
