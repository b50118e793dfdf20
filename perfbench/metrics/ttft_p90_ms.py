"""ttft_p90_ms: 90th percentile over the window's requests of the time
from a request's due time to its first streamed token (client side)."""

from pbench import stats


def read(run):
    p = stats.percentile(stats.present(
        stats.ttft_s(o) for o in run.due_in_window), 90)
    return None if p is None else 1e3 * p
