"""perfbench: one cell of BENCHMARK.json, one process, one last line.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is data found by the names in
BENCHMARK.json: ``perfbench/configs/<config>.json`` (sizes),
``perfbench/traffic/<traffic>.json`` (the entry it drives and its
parameters), ``perfbench/metrics/<metric>.json`` (which reader, and what
it matches). A later PR adds files and manifest entries and edits none.
"""

from __future__ import annotations

import time

_T_IMPORT = time.perf_counter()

import argparse          # noqa: E402
import importlib         # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402
import tempfile          # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench.lib import manifest as manifest_lib     # noqa: E402


def process_age_s() -> float:
    """Seconds since this process was started (interpreter start-up and
    imports included), from /proc where there is one."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return up - ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - _T_IMPORT


class Ctx:
    """What an entry is given."""

    def __init__(self, args, manifest):
        self.manifest = manifest
        self.cell = manifest_lib.workload(manifest, args.workload)
        self.chips = self.cell["chips"]
        self.seed = int(args.seed) % (2 ** 32 - 1)
        self.seconds = float(args.seconds)
        self.trace = int(args.trace)
        self.fault = None
        self.rehearsal = bool(os.environ.get("PERFBENCH_REHEARSAL"))
        entry = manifest_lib.config_entry(manifest, self.cell["config"])
        with open(os.path.join(ROOT, entry["file"]), encoding="utf-8") as f:
            self.config = json.load(f)
        with open(os.path.join(manifest_lib.BENCH_DIR, "traffic",
                               self.cell["traffic"] + ".json"),
                  encoding="utf-8") as f:
            self.traffic = json.load(f)
        if self.rehearsal:
            # the harness's own tiny rehearsal: sizes from the files'
            # "rehearsal" groups, never reported as a device number
            self.config = {**self.config, **self.config["rehearsal"]}
            self.traffic = _merge(self.traffic, self.traffic["rehearsal"])
        self.workdir = None
        self.setup_s = None

    def note_window(self, t_start):
        """Called by the entry with the clock reading at which the measured
        window opened: set-up is the process's age at that moment."""
        self.setup_s = process_age_s() - (time.perf_counter() - t_start)

    def memory_peak_bytes(self) -> int:
        """Peak on the fullest chip: live buffers plus what loaded programs
        reserve (on this runtime a program's scratch is held apart under
        ``bytes_reserved``). Without memory_stats (the CPU rehearsal): peak
        resident size of the process."""
        import jax
        peak = 0
        for d in jax.local_devices():
            st = d.memory_stats() or {}
            res = int(st.get("bytes_reserved", 0))
            peak = max(peak, int(st.get("bytes_in_use", 0)) + res,
                       int(st.get("peak_bytes_in_use", 0))
                       + int(st.get("peak_bytes_reserved", res)))
        if not peak:
            import resource
            peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
        return peak


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(base[k], v) if isinstance(v, dict) \
            and isinstance(base.get(k), dict) else v
    return out


def metric_spec(name: str) -> dict:
    with open(os.path.join(manifest_lib.BENCH_DIR, "metrics",
                           name + ".json"), encoding="utf-8") as f:
        return json.load(f)


def read_metric(name: str, view: dict, peaks: dict):
    """One per-layer metric through its own reader, or None."""
    spec = metric_spec(name)
    reader = importlib.import_module("perfbench.readers." + spec["reader"])
    return reader.read(view, spec.get("params", {}), peaks)


def setup_jax(ctx: Ctx):
    """The process-level set-up the program's own CLIs make before they
    touch the backend, then the look for the chips."""
    from tpudist.utils import enable_compilation_cache, tune_tpu
    if ctx.rehearsal:
        # the CPU rehearsal keeps its programs apart from the repo's own
        # tests': one of those aborted on an XLA:CPU program that a
        # rehearsal had left in the shared directory
        os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(
            ROOT, ".jax_cache", "rehearsal"))
    tune_tpu()
    enable_compilation_cache()     # JAX_COMPILATION_CACHE_DIR if set, else
    import jax                     # the fixed <checkout>/.jax_cache
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if ctx.rehearsal:
        return dev
    if dev["platform"] != "tpu":
        raise SystemExit(f"perfbench: JAX found platform "
                         f"{dev['platform']!r}, not a TPU: no device number "
                         f"is taken here")
    if dev["count"] != ctx.chips:
        raise SystemExit(f"perfbench: cell {ctx.cell['name']} asks for "
                         f"{ctx.chips} chip(s), JAX found {dev['count']}")
    return dev


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    manifest = manifest_lib.load()
    ctx = Ctx(args, manifest)
    # quiet the multi-KB line XLA:CPU prints on every cache hit
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
    dev = setup_jax(ctx)
    from jax import monitoring
    compiles = {"n": 0, "armed": False}

    def on_event(event, **_):
        # a program built OR loaded while the window is open
        if event.startswith("/jax/compilation_cache/cache_") \
                and compiles["armed"]:
            compiles["n"] += 1
    monitoring.register_event_listener(on_event)
    ctx.arm_compile_count = lambda on: compiles.__setitem__("armed", on)

    entry = importlib.import_module(
        "perfbench.lib." + ctx.traffic["entry"] + "_entry")
    # checkpoints, traces and metrics of the run go to a fresh directory
    # under TMPDIR, never into the checkout
    ctx.workdir = tempfile.mkdtemp(prefix="perfbench-")
    try:
        return _run(ctx, entry, dev, compiles)
    finally:
        shutil.rmtree(ctx.workdir, ignore_errors=True)


def _run(ctx, entry, dev, compiles) -> int:
    from perfbench.lib import capture as capture_lib
    from perfbench.lib import peaks as peaks_lib
    cell = ctx.cell["name"]
    res = entry.run(ctx)
    want = manifest_lib.expected(ctx.manifest, cell, ctx.trace)
    print(f"perfbench: set-up took {ctx.setup_s:.2f} s, the window "
          f"{res['view']['wall_s']:.2f} s", flush=True)
    metrics = {}
    device = dict(dev, memory_peak_bytes=int(res["memory_peak"]))
    line = {"correct": False, "attempted": int(res["attempted"]),
            "failed": int(res["failed"]), "metrics": metrics,
            "device": device}
    if not ctx.trace:
        values = dict(res["e2e"], setup_s=ctx.setup_s)
        for name, unit in want.items():
            if values.get(name) is not None:
                metrics[name] = {"value": values[name], "unit": unit}
    else:
        view = res["view"]
        # the rehearsal has no row of its own: any row exercises the readers
        peaks = next(iter(peaks_lib.load().values())) if ctx.rehearsal \
            else peaks_lib.for_kind(dev["kind"])
        tracks = capture_lib.load_tracks(view["capture_dir"])
        stretch = view.get("capture_stretch_us")
        if stretch:
            # a session left open past the stretch it was opened for (its
            # close is paid after the window): its first device op is the
            # stretch's first dispatch, and it is cut to the stretch's
            # length on the host's clock
            lo = capture_lib.first_op_us(tracks)
            hi = lo + (stretch[1] - stretch[0]) if stretch[1] \
                else float("inf")
            tracks = capture_lib.cut(tracks, lo, hi)
            view["capture_anchor_us"] = stretch[0] - lo
        red = capture_lib.reduce_tracks(tracks)
        view["tracks"], view["capture"] = tracks, red
        device["window_s"], device["busy_s"] = red["window_s"], red["busy_s"]
        for name, unit in want.items():
            v = read_metric(name, view, peaks)
            if v is not None:
                metrics[name] = {"value": float(v), "unit": unit}
        # host spans onto the capture's clock: its timestamps count from
        # the start of the profiler session
        anchor = view.get("capture_anchor_us")
        if anchor is None:
            anchor = next((s["t0_us"] for s in view["spans"]
                           if s["name"] == "profile_window"), 0.0)
        host = [dict(s, t0_us=s["t0_us"] - anchor, t1_us=s["t1_us"] - anchor)
                for s in view["spans"]
                if s["t1_us"] > s["t0_us"] and s["name"] != "epoch"]
        line["breakdown"] = capture_lib.breakdown(tracks, host)
    compared = dict(res["compared"])
    compared["compiles_in_window"] = {"value": compiles["n"], "limit": 0}
    line["correct"] = all(c["value"] <= c["limit"] for c in compared.values())
    line["compared"] = compared
    may_lack = [n for n in want if ctx.rehearsal and ctx.trace
                and metric_spec(n).get("needs_chip")]
    return manifest_lib.emit(line, ctx.manifest, cell, ctx.trace, may_lack)


if __name__ == "__main__":
    sys.exit(main())
