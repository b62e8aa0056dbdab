"""The whole window's share of the chips' peak: model FLOPs of all the real
tokens of the window (from shapes, ``perfbench/lib/flops.py``) over window
wall x chips x peak FLOP/s."""


def read(view, params, peaks):
    if not view.get("model_flops") or not view.get("wall_s"):
        return None
    return 100.0 * view["model_flops"] / (
        view["wall_s"] * view["chips"] * peaks["bf16_flops_per_s"])
