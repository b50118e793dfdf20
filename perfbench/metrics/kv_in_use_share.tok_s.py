"""kv_in_use_share.tok_s: live context tokens per decode step over the
tokens of KV the batch reserves (``max_batch`` x ``max_seq``), over the
traced window's ticks (tick attributes ``kv_tokens`` and ``steps``, from
the engine's host length mirror)."""

from pbench import spans


def read(run):
    got = spans.of_run(run)
    ticks = spans.in_window(got, spans.TICK) if got else []
    steps = spans.attr_sum(ticks, "steps")
    if steps <= 0:
        return None
    reserved = run.max_batch * run.config["serve"]["max_seq"]
    return 100.0 * spans.attr_sum(ticks, "kv_tokens") / (steps * reserved)
