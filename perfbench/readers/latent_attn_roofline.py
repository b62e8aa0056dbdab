"""The paged decode kernel's LATENT call against its roofline: the least
time the chip could take to read the latent rows of the pages the slots'
rows map (``flops_longcatflash.latent_read_min_seconds``: every mapped
page's rows once a call at the PUBLISHED 576 values, against the products
the query heads owe those keys) over the summed device time of the
kernel's events in the stretch. The calls are counted from the events
themselves; the pages a call are the mean ``kv_full_pages`` of the
``decode_step`` spans that fall in the stretch (of the window, where the
stretch is not known). The event-name pattern is data in the metric's
file. No event matched: 0.0, nothing was booked under the name; spans
without the argument: nothing to read."""

import re

from perfbench.lib import flops_longcatflash as flops


def read(view, params, peaks):
    tracks = view.get("tracks")
    if not tracks or view["kind"] != "serve":
        return None
    rx = re.compile(params["event_pattern"])
    mine = [(a, b) for ops in tracks.values() for a, b, n in ops
            if rx.search(n)]
    spent = sum(b - a for a, b in mine) / len(tracks) / 1e6
    if spent <= 0:
        return 0.0
    lo, hi = view.get("capture_stretch_us") or view["window_us"]
    hi = hi or float("inf")
    pages = [float(s["args"]["kv_full_pages"]) for s in view["spans"]
             if s["name"] == "decode_step" and "kv_full_pages" in s["args"]
             and s["t1_us"] > lo and s["t0_us"] < hi]
    if not pages:
        return None
    least = flops.latent_read_min_seconds(
        view["config"], sum(pages) / len(pages),
        view["job"]["engine"]["page_tokens"], peaks)["seconds"]
    return 100.0 * (len(mine) / len(tracks)) * least / spent
