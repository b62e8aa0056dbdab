"""Causal flash attention forward + backward against its roofline: the
least time the chip could take at the cell's shapes for the steps the
capture covers (``flops.flash_train_min_seconds``) over the summed device
time of the kernels' events. The event-name pattern is data in the
metric's file. No events matched: nothing to report."""

from perfbench.lib import capture, flops


def read(view, params, peaks):
    tracks = view.get("tracks")
    if not tracks or view["kind"] != "train":
        return None
    spent = capture.op_seconds(tracks, params["event_pattern"])
    if spent <= 0:
        return None
    job = view["job"]
    per_chip = job["batch_size"] * view["captured_steps"] / view["chips"]
    least = flops.flash_train_min_seconds(
        view["config"], job["seq_len"], per_chip, peaks)["seconds"]
    return 100.0 * least / spent
