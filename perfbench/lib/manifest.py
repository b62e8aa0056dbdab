"""BENCHMARK.json as the harness reads it: which metrics a cell reports in
which trace mode, and the check that the last line meets the manifest
before it is printed. One place builds the line; one place refuses it."""

from __future__ import annotations

import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "perfbench")


def load(path: str | None = None) -> dict:
    with open(path or os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def workload(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"workload {name!r} is not in BENCHMARK.json "
                   f"(has: {[w['name'] for w in manifest['workloads']]})")


def config_entry(manifest: dict, name: str) -> dict:
    for c in manifest["configs"]:
        if c["name"] == name:
            return c
    raise KeyError(f"configuration {name!r} is not in BENCHMARK.json")


def _reported_in(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def end_to_end(manifest: dict, cell: str) -> dict:
    """{name: unit} of the end-to-end metrics this cell reports."""
    return {m["name"]: m["unit"] for m in manifest["end_to_end"]
            if _reported_in(m, cell)}


def per_layer(manifest: dict, cell: str) -> dict:
    """{name: unit} of the per-layer metrics this cell MAY report. A metric
    with a ``workloads`` key is due in exactly those cells; one without is
    due in every cell that reports the end-to-end metric it moves."""
    e2e = end_to_end(manifest, cell)
    out = {}
    for m in manifest["per_layer"]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out[m["name"]] = m["unit"]
        elif m["moves"] in e2e:
            out[m["name"]] = m["unit"]
    return out


def expected(manifest: dict, cell: str, trace: int) -> dict:
    return per_layer(manifest, cell) if trace else end_to_end(manifest, cell)


def _num(x) -> bool:
    return isinstance(x, (int, float)) and not isinstance(x, bool) \
        and math.isfinite(x)


def validate_line(line: dict, manifest: dict, cell: str, trace: int,
                  may_lack=()) -> list:
    """Reasons why ``line`` is not the last line the contract asks for
    (empty when it is). With ``--trace 0`` every end-to-end metric of the
    cell is due. With ``--trace 1`` every per-layer metric of the cell is
    due too: a reader that found nothing in a cell that lists it is a
    fault of the cell's list, and is refused here before the driver sees
    it. ``may_lack``: metrics the tiny CPU rehearsal cannot read (their
    files say so), never passed on the chip."""
    bad = []
    for k in ("correct", "attempted", "failed", "metrics", "device"):
        if k not in line:
            bad.append(f"key {k!r} missing")
    if bad:
        return bad
    if not isinstance(line["correct"], bool):
        bad.append("correct is not a bool")
    for k in ("attempted", "failed"):
        if not isinstance(line[k], int) or isinstance(line[k], bool) \
                or line[k] < 0:
            bad.append(f"{k} is not a count")
    want = expected(manifest, cell, trace)
    got = line["metrics"]
    if not isinstance(got, dict):
        return bad + ["metrics is not an object"]
    for name in sorted(set(want) - set(got) - set(may_lack)):
        bad.append(f"metric {name!r} is due in {cell} with --trace "
                   f"{trace} and is missing")
    for name in sorted(set(got) - set(want)):
        bad.append(f"metric {name!r} is not one the manifest gives {cell} "
                   f"with --trace {trace}")
    for name, m in got.items():
        if not isinstance(m, dict) or set(m) != {"value", "unit"}:
            bad.append(f"metric {name!r} is not {{value, unit}}")
            continue
        if not _num(m["value"]):
            bad.append(f"metric {name!r} has no finite number: {m['value']!r}")
        if name in want and m["unit"] != want[name]:
            bad.append(f"metric {name!r} has unit {m['unit']!r}, the "
                       f"manifest says {want[name]!r}")
        if name in want and want[name] == "%" and _num(m["value"]) and (
                name.endswith("_roofline") or "mfu" in name.split(".")[0]) \
                and m["value"] > 100.0:
            bad.append(f"share {name!r} reads {m['value']} %, over 100: the "
                       f"count of operations or the time is wrong")
    dev = line["device"]
    if not isinstance(dev, dict):
        return bad + ["device is not an object"]
    for k in ("platform", "kind", "count", "memory_peak_bytes"):
        if k not in dev:
            bad.append(f"device.{k} missing")
    if "count" in dev and dev["count"] != workload(manifest, cell)["chips"] \
            and not os.environ.get("PERFBENCH_REHEARSAL"):
        bad.append(f"device.count {dev['count']} is not the cell's "
                   f"{workload(manifest, cell)['chips']} chip(s)")
    if "memory_peak_bytes" in dev and (not _num(dev["memory_peak_bytes"])
                                       or dev["memory_peak_bytes"] <= 0):
        bad.append("device.memory_peak_bytes is not above 0")
    if trace:
        w, b = dev.get("window_s"), dev.get("busy_s")
        if not _num(w) or not _num(b):
            bad.append(f"device.window_s/busy_s missing in a traced run "
                       f"(window_s={w!r}, busy_s={b!r})")
        elif not (0 < b <= w):
            bad.append(f"device.busy_s {b} is not in (0, window_s {w}]")
        bd = line.get("breakdown")
        if bd is not None:
            for k in ("device_ops", "idle_gaps"):
                rows = bd.get(k)
                if not isinstance(rows, list) or len(rows) > 10 or any(
                        not (isinstance(r, list) and len(r) == 2
                             and isinstance(r[0], str) and _num(r[1]))
                        for r in rows):
                    bad.append(f"breakdown.{k} is not at most 10 "
                               f"[name, seconds] pairs")
    if list(line)[-1] != "compared":
        bad.append("the compared numbers do not come last in the line")
    return bad


def emit(line: dict, manifest: dict, cell: str, trace: int,
         may_lack=()) -> int:
    """Print the last line, or refuse it. Returns the exit code. The
    compared numbers go to stderr first (the driver keeps the end of
    both); nothing is written to stdout after the line."""
    bad = validate_line(line, manifest, cell, trace, may_lack)
    sys.stdout.flush()
    for name, c in line.get("compared", {}).items():
        print(f"perfbench: compared {name} = {c.get('value')!r} "
              f"(limit {c.get('limit')!r})", file=sys.stderr)
    if bad:
        for b in bad:
            print(f"perfbench: last line refused: {b}", file=sys.stderr)
        sys.stderr.flush()
        return 3
    sys.stderr.flush()
    sys.stdout.write(json.dumps(line) + "\n")
    sys.stdout.flush()
    return 0
