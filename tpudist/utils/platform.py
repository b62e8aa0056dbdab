"""Process-level JAX set-up shared by every CLI entry point: the
persistent compilation cache, the listener that records jax's compile
phases as ring-tracer spans, and the libtpu tuning flags. The platform
itself is JAX's own business (``JAX_PLATFORMS``)."""

from __future__ import annotations

import os
import threading

from tpudist.obs import trace as trace_lib

# one fixed path inside the checkout: the directory is part of what a
# later process must reproduce to hit the cache, so it never moves
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


# persistent-cache traffic of this process, counted from jax's own
# monitoring events; the run-end kind=timing record carries the two
# counts, which is how a cache hit is told from a fast compile
CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": 0,
                "/jax/compilation_cache/cache_misses": 0}

# jax's own compile phases (each reported once it is over, with the
# program's ``fun_name``) -> the span the ring tracer records for it
COMPILE_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile_lower",
    "/jax/core/compile/backend_compile_duration": "compile_backend"}

# the persistent cache's answer to the compile under way in this thread:
# its hit or miss event fires inside the backend compile, before the
# compile's own duration event
_asked = threading.local()
_listening = False
_listen_lock = threading.Lock()


def _count_cache_event(event: str, **_) -> None:
    if event in CACHE_EVENTS:
        CACHE_EVENTS[event] += 1
        _asked.cache = "hit" if event.endswith("hits") else "miss"


def _record_compile(event, duration_secs=None, *_, **kw) -> None:
    """One compile phase as a span ending now, in the calling thread's
    ring. Called from inside jax's compile, so it never raises; a
    disabled tracer costs it no clock read."""
    try:
        name = COMPILE_SPANS.get(event)
        if name is None:
            return
        cache = None
        if name == "compile_backend":
            cache, _asked.cache = getattr(_asked, "cache", None), None
        tracer = trace_lib.get()
        if not tracer.enabled or not duration_secs >= 0:
            return
        t1 = trace_lib._now_ns()
        args = {"fun": str(kw.get("fun_name", ""))}
        if cache:
            args["cache"] = cache
        tracer.record(name, "compile", t1 - int(duration_secs * 1e9), t1,
                      **args)
    except Exception:
        pass


def listen_to_compiles() -> None:
    """Register the two listeners above with jax, once a process."""
    global _listening
    import jax
    with _listen_lock:
        if _listening:
            return
        jax.monitoring.register_event_listener(_count_cache_event)
        jax.monitoring.register_event_duration_secs_listener(
            _record_compile)
        _listening = True


def enable_compilation_cache() -> None:
    """Persistent XLA compilation cache, on for every entry point.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and
    this function sets no directory; otherwise the cache lives at
    ``<checkout>/.jax_cache`` (git-ignored). A repeat run (a CI re-run,
    a restarted worker, the next phase of ``chip_smoke.py``) then loads
    compiled programs instead of recompiling, and the run-end
    cost/memory hooks (``engine._cost_analysis_hook``) find the step
    program already compiled. The two floors drop to 0: the acceptance
    workload's programs are deliberately tiny, and the default floors
    would skip caching exactly the programs this workload compiles.
    The key covers the ops' name stacks (below): the first run after a
    change of scopes compiles cold.
    """
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", _DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # The program reads its own op metadata back (``tpudist.scopes``: the
    # name stack is how a capture says which layer an op belongs to), so
    # the metadata belongs to the program's identity. By default the key
    # is computed with debug info stripped, and a build that adds or
    # renames a scope would be handed the executable of one that has
    # none. The name stack goes into the key; Python call stacks stay
    # out of it (no frames in the locations), so that an edit which moves
    # a line, or a checkout under another path, is still a hit.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_traceback_in_locations_limit", 0)
    listen_to_compiles()


def tune_tpu(scoped_vmem_kib: int | None = None) -> None:
    """Set performance-tuning libtpu flags; call before first backend use.

    Raises XLA:TPU's scoped-VMEM limit from its 16 MiB default to 80 MiB
    so the compiler may form larger fusions (libtpu reads
    ``LIBTPU_INIT_ARGS`` when the backend initialises, not at import;
    libtpu 0.0.34 accepts the flag — what it is worth on the current
    tree is not measured).
    Respects an operator-provided LIBTPU_INIT_ARGS that already carries
    the flag; ``TPUDIST_SCOPED_VMEM_KIB=0`` disables, other values
    override."""
    if scoped_vmem_kib is None:
        raw = os.environ.get("TPUDIST_SCOPED_VMEM_KIB", "").strip()
        try:
            scoped_vmem_kib = int(raw) if raw else 81920
        except ValueError:
            print(f"tpudist: ignoring non-integer "
                  f"TPUDIST_SCOPED_VMEM_KIB={raw!r}")
            return
    if scoped_vmem_kib <= 0:
        return
    cur = os.environ.get("LIBTPU_INIT_ARGS", "")
    if "scoped_vmem_limit" in cur:
        return
    os.environ["LIBTPU_INIT_ARGS"] = (
        cur + f" --xla_tpu_scoped_vmem_limit_kib={scoped_vmem_kib}").strip()
