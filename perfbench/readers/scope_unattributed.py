"""Share of the device's busy time, in percent, that no scope of the
program accounts for: ops without a name stack that ``layers`` could not
book by program either (a train step's compiler-made copies, a program
traced outside every scope), or (100 %) a build that has no scopes."""

from perfbench.readers import layers as layers_lib


def read(view, params, peaks):
    lay = layers_lib.parse(view)
    if lay is None:
        return None
    shares = []
    for dev in lay["devices"]:
        total = sum(b - a for a, b, _ in dev["leaves"])
        bare = sum(b - a for a, b, p in dev["leaves"] if not p)
        shares.append(100.0 * bare / total)
    return sum(shares) / len(shares)
