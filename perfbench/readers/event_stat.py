"""A percentile of one field of the per-request events the scheduler
reported (seconds in, milliseconds out)."""

from perfbench.lib import stats


def read(view, params, peaks):
    xs = [e[params["field"]] for e in view.get("events", ())
          if e.get("kind") == "serve_request"
          and e.get("event") == params["event"] and params["field"] in e]
    if not xs:
        return None
    return stats.percentile(xs, float(params["stat"].lstrip("p"))) * 1e3
