"""The device's idle time inside the stretch by what the HOST was doing:
every piece of an idle interval goes to the INNERMOST of the program's
mirrored spans (``tpudist:<name>`` annotations, shifted onto the device's
clock) that covers it: the shortest one, so a child wins over its parent;
the brackets ``profile_window`` and ``epoch`` (``layers.SKIP``) never
win. Reported: the idle time under ``params.names``, in milliseconds per
``params.per``; or, with ``params.unattributed``, the percentage of all
idle time that no span covers. Mean over devices. A capture without the
mirrored spans reads 0 ms and 100 %."""

from perfbench.readers import layers as layers_lib


def read(view, params, peaks):
    lay = layers_lib.parse(view)
    if lay is None:
        return None
    spans = [s for s in lay["spans"] if s[2] not in layers_lib.SKIP]
    vals = []
    for dev in lay["devices"]:
        by = layers_lib.innermost(dev["idle"], spans)
        total = sum(by.values())
        if params.get("unattributed"):
            vals.append(100.0 * by.get("", 0.0) / total if total else 0.0)
        else:
            vals.append(sum(by.get(n, 0.0) for n in params["names"])
                        / layers_lib.per_count(lay, params.get("per"))
                        / 1e3)
    return sum(vals) / len(vals)
