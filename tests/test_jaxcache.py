"""utils/jaxcache.py: the compile cache is placed from outside
(JAX_COMPILATION_CACHE_DIR) or at one fixed in-checkout path — a directory
that moves (with the sources, the process, the cwd, the clock) never hits.

Each case runs in a child interpreter: the cache config is process-global
and JAX reads the variable at import."""

import json
import os
import subprocess
import sys

import pytest

from minbft_tpu.utils import jaxcache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = """
import json, sys
import jax
from minbft_tpu.utils import jaxcache
before = jax.config.jax_compilation_cache_dir
returned = jaxcache.enable_compilation_cache()
print(json.dumps({
    "before": before,
    "returned": returned,
    "config": jax.config.jax_compilation_cache_dir,
    "min_secs": jax.config.jax_persistent_cache_min_compile_time_secs,
}))
"""


def _probe(cwd, **env_over) -> dict:
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("JAX_COMPILATION_CACHE_DIR", "MINBFT_JAX_CACHE")
    }
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO, **env_over)
    res = subprocess.run(
        [sys.executable, "-c", _PROBE], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert res.returncode == 0, res.stderr[-2000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_env_dir_is_left_to_jax(tmp_path):
    placed = str(tmp_path / "placed")
    out = _probe(str(tmp_path), JAX_COMPILATION_CACHE_DIR=placed)
    # JAX read the variable itself; the function neither replaced nor
    # decorated it, and says where the cache is.
    assert out["before"] == placed
    assert out["config"] == placed
    assert out["returned"] == placed
    assert out["min_secs"] == 1.0


def test_unset_gives_one_in_checkout_path_from_any_cwd(tmp_path):
    a = _probe(str(tmp_path))
    b = _probe(REPO)
    want = os.path.join(REPO, ".jax_cache")
    assert a["config"] == b["config"] == want
    assert a["returned"] == b["returned"] == want
    assert a["before"] is None


def test_default_path_depends_on_nothing_that_moves():
    # No component from file contents, pid or time: the path is exactly
    # <checkout>/.jax_cache, and the module has no hash of the sources.
    assert jaxcache.DEFAULT_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    assert not hasattr(jaxcache, "tree_key")
    with open(jaxcache.__file__) as fh:
        src = fh.read()
    for moving in ("hashlib", "getpid", "time.", "MINBFT_JAX_CACHE_DIR"):
        assert moving not in src


def test_disable_switch_sets_nothing(tmp_path):
    out = _probe(str(tmp_path), MINBFT_JAX_CACHE="0")
    assert out["returned"] == ""
    assert out["config"] is None


@pytest.mark.parametrize(
    "names,want",
    [
        ([], 0),
        (["jit_a-1-cache", "jit_a-1-atime", "jit_b-2-cache", ".tmp"], 2),
    ],
)
def test_entry_count_counts_executables(tmp_path, names, want):
    for name in names:
        (tmp_path / name).write_bytes(b"x")
    assert jaxcache.entry_count(str(tmp_path)) == want
    assert jaxcache.entry_count(str(tmp_path / "absent")) == 0
    assert jaxcache.entry_count("") == 0


def test_jax_listener_sees_a_fresh_jit_and_names_its_events():
    """One jax.monitoring listener a process puts JAX's own trace, lower
    and compile durations on the process timeline; the names are the
    installed JAX's own (pinned here: a JAX that renames them shows as a
    failure, not as a silent hole in setup.jax_trace_s)."""
    import time

    import jax
    import jax.numpy as jnp
    from jax._src import dispatch

    from minbft_tpu.obs import trace as obs_trace

    assert obs_trace.JAX_EVENTS[:3] == (
        dispatch.JAXPR_TRACE_EVENT,
        dispatch.JAXPR_TO_MLIR_MODULE_EVENT,
        dispatch.BACKEND_COMPILE_EVENT,
    )
    from jax._src import compiler

    import inspect

    assert obs_trace.JAX_EVENTS[3] in inspect.getsource(compiler)

    jaxcache.record_jax_events()
    jaxcache.record_jax_events()  # once per process, however often asked
    from jax._src import monitoring

    listeners = monitoring.get_event_duration_listeners()
    assert listeners.count(obs_trace.note_jax_event) == 1

    mark = len(obs_trace.timeline()["jax"]["rows"])
    t0 = time.monotonic_ns()
    salt = float(t0 % 1000)  # a function JAX has not traced in this process

    def fresh(x):
        for k in range(400):  # enough operations to take milliseconds to trace
            x = jnp.sin(x) * salt + float(k)
        return x

    jax.jit(fresh)(jnp.arange(7.0)).block_until_ready()
    t1 = time.monotonic_ns()
    rows = obs_trace.timeline()["jax"]["rows"][mark:]
    names = {name for name, _t, _d in rows}
    assert set(obs_trace.JAX_EVENTS[:2]) <= names  # traced and lowered here
    assert names <= set(obs_trace.JAX_EVENTS)
    for _name, t_end, duration_ns in rows:
        assert t0 <= t_end <= t1 and 1_000_000 <= duration_ns <= t1 - t0  # from 1 ms up
