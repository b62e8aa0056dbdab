"""A percentile of one of the per-request lists that the serve entry
reduced from the scheduler's events on the harness's clock (seconds in,
milliseconds out): ``tpot_s``, ``queue_wait_s``."""

from perfbench.lib import stats


def read(view, params, peaks):
    xs = view.get(params["list"])
    if not xs:
        return None
    return stats.percentile(xs, float(params["stat"].lstrip("p"))) * 1e3
