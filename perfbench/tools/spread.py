"""The spread a bound is set from: for each metric of the result lines in
the given files (one file per set, one line per run), the median and the
distance between the quartiles as a share of the median, per set, and the
wider of the two.

    python3 perfbench/tools/spread.py set1.jsonl set2.jsonl
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench.lib import stats      # noqa: E402


def main(paths):
    sets = []
    for p in paths:
        rows = [json.loads(line) for line in open(p) if line.startswith("{")]
        sets.append(rows)
        bad = [r for r in rows if not r["correct"]]
        print(f"{p}: {len(rows)} runs, {len(bad)} not correct")
    names = sorted({n for rows in sets for r in rows for n in r["metrics"]})
    for n in names:
        out = []
        for i, rows in enumerate(sets):
            # each side's first run compiles: setup_s leaves it out
            vals = [r["metrics"][n]["value"] for r in rows if n in r["metrics"]]
            if n == "setup_s" and i == 0:
                vals = vals[1:]
            out.append((stats.median(vals), stats.iqr_share(vals), vals))
        widest = max(o[1] for o in out)
        print(f"{n}: " + "; ".join(
            f"set{i + 1} median {m:.6g} spread {100 * s:.3f} %"
            for i, (m, s, _) in enumerate(out))
            + f"; widest {100 * widest:.3f} % -> bound >= {5 * widest:.4f}")
        for i, (_, _, vals) in enumerate(out):
            print(f"   set{i + 1}: " + " ".join(f"{v:.6g}" for v in vals))
    for rows in sets:
        for r in rows:
            c = r.get("compared", {})
            print("   compared: " + " ".join(
                f"{k}={v['value']:.4g}" for k, v in c.items()))


if __name__ == "__main__":
    main(sys.argv[1:])
