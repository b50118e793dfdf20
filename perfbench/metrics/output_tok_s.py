"""output_tok_s: output tokens delivered inside the window, over the
window's seconds."""

from pbench import stats


def read(run):
    return stats.tokens_between(run.outcomes, run.t0, run.t1) / run.seconds
