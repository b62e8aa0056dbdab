"""A statistic over the program's spans of given names inside the window:
the duration in ms, or one of the span's arguments."""

from perfbench.lib import stats


def read(view, params, peaks):
    lo, hi = view["window_us"]
    names = set(params["names"])
    xs = []
    for s in view["spans"]:
        if s["name"] not in names or s["t1_us"] <= lo or s["t0_us"] >= hi:
            continue
        if "arg" in params:
            if params["arg"] not in s["args"]:
                continue
            xs.append(float(s["args"][params["arg"]]))
        else:
            xs.append((s["t1_us"] - s["t0_us"]) / 1e3)
    if not xs:
        return None
    stat = params["stat"]
    if stat == "mean":
        v = sum(xs) / len(xs)
    elif stat == "sum_per_epoch":
        v = sum(xs) / max(len(view["epoch_ends"]), 1)
    else:
        v = stats.percentile(xs, float(stat.lstrip("p")))
    if "percent_of_engine" in params:
        v = 100.0 * v / view["job"]["engine"][params["percent_of_engine"]]
    return v
