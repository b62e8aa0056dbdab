"""The flash forward kernel of the ``LongCat-Flash`` cell's prefills against
its roofline: the least time the chip could take for the attention of the
padded prompts the stretch holds at the PUBLISHED head widths
(``flops_longcatflash.flash_prefill_min_seconds``: 192 for q and k, 128
for v and o, every earlier key) over the summed device time of the kernel's
events. The prompts are counted from the events themselves: one an
attention sublayer (two a layer) a prompt. The event-name pattern is data
in the metric's file. No event matched: 0.0, nothing was booked under the
name."""

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
    cfg = view["config"]
    prompts = len(mine) / len(tracks) / (2 * cfg["num_layers"])
    least = flops.flash_prefill_min_seconds(
        cfg, view["job"]["engine"]["prompt_pad"], peaks)["seconds"]
    return 100.0 * prompts * least / spent
