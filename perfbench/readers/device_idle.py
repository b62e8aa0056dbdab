"""1 - busy / window over the capture, in percent."""


def read(view, params, peaks):
    c = view.get("capture")
    if not c or c["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - c["busy_s"] / c["window_s"])
