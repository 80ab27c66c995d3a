"""Persistent JAX compilation cache, placed from outside or at one fixed path.

The crypto kernels are compile-dominated on cold processes (tens of
seconds per kernel shape on the TPU, minutes on the CPU): every entry
point (``chip_smoke.py``, ``benchmark/run.py``, ``peer run``, the tests) loads
the executables an earlier process compiled instead of compiling them
again.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no directory: whoever runs the program decides where the
cache lives (a machine that is thrown away after each run can only keep
one where its owner mounts it).  Where it is not set, the cache is
``<checkout>/.jax_cache`` — one fixed path, because the path is part of
what a hit depends on: a directory that moves with the source tree, the
process or the working directory never hits.  JAX's own key covers the
HLO, the compiler version and the device, so one directory serves every
kernel edit and every backend.
"""

from __future__ import annotations

import os

# <checkout>/.jax_cache (this file is <checkout>/minbft_tpu/utils/jaxcache.py).
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def cache_dir() -> str:
    """Where the cache is (the rule above; needs no JAX)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR


def switched_on() -> bool:
    """MINBFT_JAX_CACHE=0 turns the compile cache off, and with it the
    kernel store beside it (utils/kernelstore.py)."""
    return os.environ.get("MINBFT_JAX_CACHE", "1") != "0"


def enable_compilation_cache(min_compile_secs: float = 1.0) -> str:
    """Turn the persistent compilation cache on and return its directory.
    Call before the first kernel compile (import time is fine — this only
    sets config, it never initializes a backend).  Disable entirely with
    MINBFT_JAX_CACHE=0 (returns "")."""
    record_jax_events()
    if not switched_on():
        return ""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", min_compile_secs
    )
    return cache_dir()


_recording = False


def record_jax_events() -> None:
    """Put JAX's own trace / lower / compile / cache-retrieval durations
    on the process timeline (obs/trace.py ``timeline()["jax"]``): one
    ``jax.monitoring`` listener a process, registered where the program
    first configures JAX — here and in ``placement.replica_engine`` —
    so that every kernel trace of a replica's start-up is caught."""
    global _recording
    if _recording:
        return
    import jax

    from ..obs.trace import note_jax_event

    jax.monitoring.register_event_duration_secs_listener(note_jax_event)
    _recording = True


def entry_count(cache_dir: str) -> int:
    """Number of cached executables in ``cache_dir`` (0 when absent) —
    recorded before/after a run to show whether the kernels compiled or
    loaded."""
    if not cache_dir or not os.path.isdir(cache_dir):
        return 0
    return sum(
        1
        for name in os.listdir(cache_dir)
        if not name.startswith(".") and not name.endswith("-atime")
        and not os.path.isdir(os.path.join(cache_dir, name))  # the kernel store
    )
