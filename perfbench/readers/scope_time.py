"""Device time of the ops whose scope path matches ``params.scope`` (and
not ``params.exclude``), in milliseconds: summed over the stretch and
divided by how many ``params.per`` (``step``, ``dispatch``, ``prefill``)
it holds, mean over devices. With ``params.module`` (a pattern of program
names on the ``XLA Modules`` line) the sum is taken inside each matching
program instance and the median instance is reported: one prefill's
device time, not the stretch's divided by a count (an empty ``scope``
matches every op of the instance, the unnamed ones too: the program's
whole device time; 0 where the capture shows no instance of it). A
capture whose ops carry no scope reads 0: nothing is booked under the
name."""

import re

from perfbench.lib import stats
from perfbench.readers import layers as layers_lib


def read(view, params, peaks):
    lay = layers_lib.parse(view)
    if lay is None:
        return None
    want = re.compile(params["scope"])
    skip = re.compile(params["exclude"]) if params.get("exclude") else None

    def counts(path):
        return bool(want.search(path)) and not (skip and skip.search(path))

    module = re.compile(params["module"]) if params.get("module") else None
    per_dev = []
    for dev in lay["devices"]:
        mine = [(a, b) for a, b, p in dev["leaves"] if counts(p)]
        if module:
            inst = [(a, b) for a, b, n in dev["modules"] if module.search(n)]
            xs = [sum(min(b, hi) - max(a, lo) for a, b in mine
                      if min(b, hi) > max(a, lo)) for lo, hi in inst]
            per_dev.append(stats.percentile(xs, 50) if xs else 0.0)
        else:
            per_dev.append(sum(b - a for a, b in mine)
                           / layers_lib.per_count(lay, params.get("per")))
    return sum(per_dev) / len(per_dev) / 1e3
