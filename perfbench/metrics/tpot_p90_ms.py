"""tpot_p90_ms: 90th percentile over the window's requests of
(last token - first token) / (tokens - 1), client side."""

from pbench import stats


def read(run):
    p = stats.percentile(stats.present(
        stats.tpot_s(o) for o in run.due_in_window), 90)
    return None if p is None else 1e3 * p
