"""The ratio of two arguments summed over the program's spans of given
names inside the window: ``params.over`` / ``params.under`` (forwards a
token emitted: ``forwards`` over ``tokens_emitted`` of ``decode_step``).
A program whose spans lack either argument reads nothing."""


def read(view, params, peaks):
    lo, hi = view["window_us"]
    names = set(params["names"])
    over = under = 0.0
    for s in view["spans"]:
        if s["name"] not in names or s["t1_us"] <= lo or s["t0_us"] >= hi:
            continue
        if params["over"] in s["args"] and params["under"] in s["args"]:
            over += float(s["args"][params["over"]])
            under += float(s["args"][params["under"]])
    return over / under if under else None
