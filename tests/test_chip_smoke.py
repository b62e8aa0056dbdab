"""The two rules that let the chip tool run this repo: where the compile
cache lives, and that ``chip_smoke.py`` neither touches jax in its parent
nor carries on without a TPU."""

import os
import subprocess
import sys

import jax

from tpudist.utils import platform

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_compilation_cache_dir_rule(monkeypatch):
    """``JAX_COMPILATION_CACHE_DIR`` set: the code sets no directory (jax
    reads the variable itself). Unset: one fixed path inside the checkout,
    a pure function of where the package lives — never a temp, pid or
    time-derived name, which would miss on every later process."""
    calls = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.__setitem__(k, v))
    listeners, timers = [], []
    monkeypatch.setattr(jax.monitoring, "register_event_listener",
                        listeners.append)
    monkeypatch.setattr(jax.monitoring,
                        "register_event_duration_secs_listener",
                        timers.append)
    monkeypatch.setattr(platform, "_listening", False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
    platform.enable_compilation_cache()
    assert "jax_compilation_cache_dir" not in calls
    # the key covers the ops' name stacks (tpudist.scopes is read back from
    # captures) and no Python call stacks (tests/test_scopes.py has the
    # two-process proof)
    assert calls == {"jax_persistent_cache_min_compile_time_secs": 0,
                     "jax_persistent_cache_min_entry_size_bytes": 0,
                     "jax_compilation_cache_include_metadata_in_key": True,
                     "jax_traceback_in_locations_limit": 0}

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    monkeypatch.chdir("/")      # the path must not depend on the cwd
    seen = []
    for _ in range(2):
        calls.clear()
        platform.enable_compilation_cache()
        seen.append(calls["jax_compilation_cache_dir"])
    assert seen == [os.path.join(REPO, ".jax_cache")] * 2
    # one registration of each listener, however often it is asked for
    assert listeners == [platform._count_cache_event]
    assert timers == [platform._record_compile]
    # hits and misses are counted from jax's own events, nothing else
    monkeypatch.setattr(platform, "CACHE_EVENTS",
                        dict.fromkeys(platform.CACHE_EVENTS, 0))
    listeners[0]("/jax/compilation_cache/cache_hits")
    listeners[0]("/jax/some/other/event")
    platform._asked.cache = None
    assert sum(platform.CACHE_EVENTS.values()) == 1


def test_chip_smoke_refuses_without_tpu_and_stays_off_jax():
    """Under ``JAX_PLATFORMS=cpu`` the smoke exits non-zero at once, names
    the platform it found, prints no result line — and its own process
    never imported jax (a parent that has holds the chip its children
    need)."""
    driver = (
        "import runpy, sys\n"
        "try:\n"
        "    runpy.run_path('chip_smoke.py', run_name='__main__')\n"
        "    rc = 0\n"
        "except SystemExit as e:\n"
        "    rc = e.code\n"
        "print('JAX_IN_PARENT', 'jax' in sys.modules)\n"
        "sys.exit(rc)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", driver], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode not in (0, None), r.stdout + r.stderr
    assert "platform is 'cpu'" in r.stdout, r.stdout + r.stderr
    assert "JAX_IN_PARENT False" in r.stdout
    assert '"ok"' not in r.stdout
