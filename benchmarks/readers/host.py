"""Numbers the driver's own clock took: a value of `run.stats`, or a
percentile of one of its lists."""

from drivers.common import percentile


def read(rec, *, key: str, percentile_q: float | None = None):
    val = rec.run.stats.get(key)
    if val is None or val == []:
        return None
    if percentile_q is not None:
        return percentile(val, percentile_q)
    return float(val)
