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
