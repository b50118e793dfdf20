"""Share of the traced window with no operation running on the device
(1 - union of device-op intervals / window)."""

from pbench.readers import idle_share_pct


def read(run):
    return idle_share_pct(run)
