"""The capture document read for the program's layers: which scope each
device op ran under, and which of the program's spans the host was in
while the device sat idle. Not a reader: the three readers beside it
(``scope_time``, ``scope_unattributed``, ``idle_by_span``) call
:func:`parse`, which reads the document once and leaves the result in
``view["layers"]`` for the next.

What the program gives (since PR 24), and what is read where it is absent:

* ``jax.named_scope`` paths. A v5e capture of jaxlib 0.9.0 carries an op's
  name stack as the ``tf_op`` argument of its ``XLA Ops`` event
  (``jit(f)/decode/while/body/closed_call/attn/qkv/dot_general:``). The
  path kept is the segments that are scope words (the metric's ``words``),
  autodiff's ``transpose(jvp(..))`` unwrapped. An op without one inside a
  ``while``/``cond`` that has one takes the container's. Ops the compiler
  makes (copies of loop-carried state) or moves (converts hoisted out of
  a loop) carry none: inside a program instance (an ``XLA Modules``
  event) whose named ops all sit under ONE outermost scope (``decode``,
  ``prefill``) they are booked under that scope, the program's own
  plumbing, and under its ``cast`` where the event's HLO text shows a
  convert of a stored weight. A train step has several outermost scopes
  and its nameless ops stay unattributed, as does everything in a
  program without scopes (the parent commit, the CPU rehearsal).
* the ring tracer's spans as ``TraceAnnotation("tpudist:<name>")`` on the
  host's line of the same document. A capture without them reads as all
  idle time unattributed, and counts dispatches from the cell's file.

The two lines of one document are NOT on one clock to the millisecond: in
a v5e capture the device line ran 0.3 to 1.5 ms ahead of the host line (my
chip runs, PR 24: a module's first op stamped that long before the host's
``DoEnqueueProgram`` that launched it; steady within a session, another
value in the next). The offset is measured, not
assumed: every ``XLA Modules`` event carries the ``run_id`` of the host's
``DoEnqueueProgram`` event, no program starts before it is enqueued, and
one enqueued on an idle device starts within microseconds, so the offset
is the largest (enqueue - device start) over the pairs. Times in
microseconds.

``scope_path``, ``leaves`` and the scope words repeat what the program
has in ``tpudist/scopes.py`` and ``tpudist/obs/devtime.py``, on purpose:
the yardstick must not import the parser of the program it measures, or
a change to that parser would move both sides of a comparison at once. A
test holds the two word lists and ``scope_path`` together.
"""

from __future__ import annotations

import bisect
import re

from perfbench.lib import capture

PREFIX = "tpudist:"
_WRAPPED = re.compile(r"^(?:[A-Za-z_]+\()*([A-Za-z0-9_.\-]+)\)*$")
# a convert of a program parameter that holds stored weights, as the
# event's ``long_name`` (the HLO instruction's text) shows it:
# ``convert(f32[24,2048,8192]{..} %params__layers____w_up__.1)``
_WEIGHT_CAST = re.compile(r" convert\([^%]*%\w*params__")
# every scope word the program may enter (tpudist/scopes.py:SCOPES, split
# at "/")
WORDS = frozenset((
    "embed", "norm", "attn", "qkv", "rope", "core", "out", "kv_write",
    "kv_gather", "ffn", "lm_head", "cast", "loss", "optimizer", "prefill",
    "kv_scatter", "decode", "sample"))


def scope_path(op_name) -> str:
    if not op_name:
        return ""
    kept = []
    for seg in op_name.rstrip(":").split("/")[:-1]:
        if seg.startswith(("jit(", "pjit(")):
            continue
        m = _WRAPPED.match(seg)
        if m and m.group(1) in WORDS:
            kept.append(m.group(1))
    return "/".join(kept)


# two ops that only touch (the next starts within 2 ns of this one's end,
# as stamps rounded to the nanosecond can) do not nest
_TOUCH_US = 2e-3


def leaves(evs):
    """One op line's ``(t0, t1, path)`` events -> the leaves, each with its
    own path or, lacking one, its nearest container's. A container is an
    event inside which another starts (``while``, ``cond``)."""
    evs = sorted(evs, key=lambda ev: (ev[0], -ev[1]))
    out, stack = [], []
    for i, (t0, t1, path) in enumerate(evs):
        while stack and stack[-1][0] <= t0:
            stack.pop()
        if not path and stack:
            path = stack[-1][1]
        if i + 1 < len(evs) and evs[i + 1][0] < t1 - _TOUCH_US:
            stack.append((t1, path))
        else:
            out.append((t0, t1, path))
    return out


def book_by_program(lv, modules, hoisted):
    """The nameless leaves of ``lv`` (sorted by start) inside a program
    instance whose named leaves share one outermost scope: booked under
    it, and under its ``cast`` where ``hoisted`` holds the event."""
    starts = [a for a, _, _ in lv]
    for m0, m1, *_ in modules:
        i, j = bisect.bisect_left(starts, m0), bisect.bisect_left(starts, m1)
        heads = {p.split("/", 1)[0] for _, _, p in lv[i:j] if p}
        if len(heads) != 1:
            continue
        head = heads.pop()
        for k in range(i, j):
            a, b, p = lv[k]
            if not p:
                lv[k] = (a, b, head + "/cast" if (a, b) in hoisted
                         else head)


def _read_doc(doc):
    """-> ({device: [(t0, t1, path)]}, {device: [(t0, t1, name, run_id)]},
    annotations [(t0, t1, name)], {run_id: enqueue t0}, the nameless
    weight converts {(t0, t1)}."""
    procs, threads = {}, {}
    for e in doc.get("traceEvents", []):
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            procs[e.get("pid")] = e.get("args", {}).get("name", "")
        elif e.get("name") == "thread_name":
            threads[(e.get("pid"), e.get("tid"))] = \
                e.get("args", {}).get("name", "")
    dev = {pid: n.split("/device:", 1)[1] for pid, n in procs.items()
           if n.startswith("/device:")}
    ops, modules, notes, enqueued, hoisted = {}, {}, [], {}, set()
    for e in doc.get("traceEvents", []):
        if e.get("ph") != "X" or "ts" not in e or "dur" not in e:
            continue
        pid, name = e.get("pid"), str(e.get("name", ""))
        args = e.get("args") or {}
        t0 = float(e["ts"])
        t1 = t0 + float(e["dur"])
        if pid in dev:
            tn = threads.get((pid, e.get("tid")), "")
            if tn == "XLA Ops":
                path = scope_path(args.get("tf_op"))
                ops.setdefault(dev[pid], []).append((t0, t1, path))
                if not path and _WEIGHT_CAST.search(
                        str(args.get("long_name", ""))):
                    hoisted.add((t0, t1))
            elif tn == "XLA Modules":
                modules.setdefault(dev[pid], []).append(
                    (t0, t1, name, args.get("run_id")))
            continue
        long_name = str(args.get("long_name", name))
        if long_name.startswith(PREFIX):
            notes.append((t0, t1, long_name[len(PREFIX):]))
        elif name == "DoEnqueueProgram" and "run_id" in args:
            enqueued[args["run_id"]] = t0
    return ops, modules, notes, enqueued, hoisted


# brackets around whole windows: they cover every gap and explain none
SKIP = ("profile_window", "epoch")


def innermost(idle, spans):
    """{name: microseconds} of the idle intervals, "" for uncovered."""
    out = {}
    for lo, hi in idle:
        over = [s for s in spans if s[1] > lo and s[0] < hi]
        cuts = sorted({lo, hi} | {t for s in over for t in s[:2]
                                  if lo < t < hi})
        for a, b in zip(cuts, cuts[1:]):
            cover = [s for s in over if s[0] <= a and s[1] >= b]
            name = min(cover, key=lambda s: s[1] - s[0])[2] if cover else ""
            out[name] = out.get(name, 0.0) + (b - a)
    return out


def _complement(busy, lo, hi):
    out, at = [], lo
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def parse(view):
    """``view["layers"]``: per device the leaf ops of the stretch with their
    scope paths, its busy and idle intervals and its program instances; the
    program's spans shifted onto the device's clock; how many steps,
    dispatches and prefills the stretch holds. None without a capture."""
    if "layers" in view:
        return view["layers"]
    view["layers"] = None
    if not view.get("capture_dir") or not view.get("tracks"):
        return None
    ops, modules, notes, enqueued, hoisted = {}, {}, [], {}, set()
    for p in capture.find_captures(view["capture_dir"]):
        o, m, n, q, h = _read_doc(capture.load_doc(p))
        hoisted |= h
        for k, v in o.items():
            ops.setdefault(k, []).extend(v)
        for k, v in m.items():
            modules.setdefault(k, []).extend(v)
        notes.extend(n)
        enqueued.update(q)
    # device clock + skew = host clock
    pairs = [enqueued[rid] - t0 for mods in modules.values()
             for t0, _, _, rid in mods if rid in enqueued]
    skew = max(pairs) if pairs else 0.0
    notes = sorted((a - skew, b - skew, n) for a, b, n in notes)
    job = view.get("job", {})
    want = int(job.get("capture_dispatches", 0) or 0)
    fences = [s for s in notes if s[2] == "decode_fence"]
    cut = view.get("capture") or {}
    if view.get("kind") == "serve" and fences:
        # the stretch: the first ``capture_dispatches`` dispatches of the
        # session, told by their ``decode_fence`` (a session opened inside
        # ``engine.decode`` misses the first dispatch's ``decode_step`` and
        # ``decode_enqueue``: a span entered before the session opened
        # leaves no event). It starts with the ``decode_step`` around the
        # first fence if that was recorded, else with the session
        fences = fences[:want or len(fences)]
        hi = fences[-1][1]
        lo = max((s[0] for s in notes if s[2] == "decode_step"
                  and s[0] <= fences[0][0] <= s[1]), default=float("-inf"))
        n_dispatch = len(fences)
    else:
        # the harness's own cut of the tracks
        lo, hi = cut.get("lo_us", 0.0), cut.get("hi_us", float("inf"))
        n_dispatch = want
    if not ops:
        # no device line (the CPU rehearsal): the harness's folded track,
        # which names no scope
        ops = {k: [(a, b, "") for a, b, _ in v]
               for k, v in view["tracks"].items()}
    devices = []
    for name in sorted(ops):
        lv = leaves(ops[name])
        book_by_program(lv, modules.get(name, ()), hoisted)
        lv = [(max(a, lo), min(b, hi), p) for a, b, p in lv
              if min(b, hi) > max(a, lo)]
        if not lv:
            continue
        busy = capture.merge_intervals([(a, b) for a, b, _ in lv])
        # the window runs from the stretch's first op, as the harness's
        # own does (device_idle_pct), to the stretch's end
        first = busy[0][0]
        end = hi if hi != float("inf") else busy[-1][1]
        devices.append({
            "leaves": lv, "busy": busy, "window": (first, end),
            "idle": _complement(busy, first, end),
            "modules": [(a, b, n) for a, b, n, _ in modules.get(name, ())
                        if b > lo and a < hi]})
    if not devices:
        return None
    spans = [s for s in notes if s[1] > lo and s[0] < hi]
    n_prefill = sum(1 for s in spans if s[2] == "prefill")
    if not notes and view.get("capture_stretch_us"):
        # no mirrored spans (the parent commit): the ring tracer's own,
        # on the host's clock, say how many prefills the stretch held
        h0, h1 = view["capture_stretch_us"]
        n_prefill = sum(1 for s in view.get("spans", ())
                        if s["name"] == "prefill" and s["t0_us"] >= h0
                        and s["t0_us"] < (h1 or float("inf")))
    view["layers"] = {
        "devices": devices, "spans": spans, "skew_us": skew,
        "per": {"step": view.get("captured_steps") or 0,
                "dispatch": n_dispatch, "prefill": n_prefill}}
    _say(view["layers"])
    return view["layers"]


def _say(lay):
    """The whole decomposition of the first device, for the run's log (the
    metrics carry a part of it): device ms by the first two segments of
    the scope path, idle ms by span."""
    dev = lay["devices"][0]
    by = {}
    for a, b, p in dev["leaves"]:
        k = "/".join(p.split("/")[:2]) or "(none)"
        by[k] = by.get(k, 0.0) + (b - a) / 1e3
    idle = innermost(dev["idle"],
                     [s for s in lay["spans"] if s[2] not in SKIP])
    print(f"perfbench: layers: window "
          f"{(dev['window'][1] - dev['window'][0]) / 1e3:.3f} ms, busy "
          f"{sum(b - a for a, b in dev['busy']) / 1e3:.3f} ms, device "
          f"line {lay['skew_us']:.1f} us ahead of the host line, per "
          f"{lay['per']}; device ms by scope: " + ", ".join(
              f"{k} {v:.3f}" for k, v in sorted(by.items(),
                                                key=lambda kv: -kv[1]))
          + "; idle ms by span: " + ", ".join(
              f"{k or '(none)'} {v / 1e3:.3f}" for k, v in sorted(
                  idle.items(), key=lambda kv: -kv[1])), flush=True)


def per_count(layers, per) -> float:
    """What a sum is divided by. A stretch that held none of the thing
    (no prefill in a short rehearsal) divides by 1: the sum is 0 there."""
    return float(max(layers["per"].get(per, 0), 1)) if per else 1.0
