"""Lowering-mode dispatch shared by the crypto kernels.

Every kernel in :mod:`minbft_tpu.ops` has two lowerings of the same
arithmetic: a fully **unrolled** straight-line form (what TPUs want — Mosaic
compiles it fast and fuses it completely) and a compact **loop** form
(``lax.scan``/``fori_loop``) for the CPU "SIM mode" backend, where XLA's
LLVM codegen is superlinear in basic-block size and chokes on big unrolled
graphs.  Dispatch is by backend at trace time; ``set_mode`` forces one for
equivalence tests.
"""

from __future__ import annotations

from ..utils import kernelstore

_FORCE_MODE = None  # None = auto by backend | "unrolled" | "loop" | "block"


def set_mode(mode) -> None:
    """Force a lowering (None = auto: block off-CPU, loop on CPU).

    - ``block``: scan over blocks of 4 unrolled CIOS iterations — the TPU
      default.  Measured on v5e at batch 4096: 122.8k ECDSA verifies/s
      with a 42s cold compile.
    - ``unrolled``: full straight-line trace-time expansion.  Measured
      102.8k verifies/s with a ~7 min cold compile — the giant basic block
      compiles 10x slower AND schedules worse than the blocked form, so
      this survives only as a differential-test reference and for
      experiments on other TPU generations.
    - ``loop``: outer loops as ``lax.scan`` — compiles in seconds
      everywhere; used by the CPU "SIM mode" backend and the protocol e2e
      paths (which need a sliver of kernel throughput)."""
    global _FORCE_MODE
    if mode not in (None, "unrolled", "loop", "block"):
        raise ValueError(mode)
    _FORCE_MODE = mode


def mode() -> str:
    if _FORCE_MODE is not None:
        return _FORCE_MODE
    import jax

    return "block" if jax.default_backend() != "cpu" else "loop"


def use_unrolled() -> bool:
    return mode() == "unrolled"


_UNSTORED = object()  # a key whose calls go through the plain jit


def _default_device():
    """Where a computation over uncommitted arguments runs."""
    import jax

    dev = jax.config.jax_default_device
    if dev is None:
        return jax.devices()[0]
    if isinstance(dev, str):
        return jax.devices(dev)[0]
    return dev


def _signature(args):
    """-> ``(avals, device)`` of a call, read off its arguments without
    tracing, or None where the call is not one the kernel store serves: a
    tracer (the kernel called under another transformation), a Python
    scalar, an argument spread over several devices."""
    import jax
    import numpy as np

    avals = []
    device = None
    for a in args:
        if type(a) is not np.ndarray:
            if isinstance(a, jax.core.Tracer) or not isinstance(a, jax.Array):
                return None
            devices = a.devices()
            if len(devices) != 1:
                return None
            if a.committed and device is None:
                (device,) = devices
        avals.append((a.shape, a.dtype))
    return tuple(avals), device or _default_device()


def per_mode_jit(fn, store=None):
    """``jax.jit`` keyed by the active lowering mode, its executables kept
    in the kernel store (utils/kernelstore.py).

    The mode is read from a Python global at *trace* time, which a plain
    module-level ``jax.jit`` would bake into its first compilation and then
    silently reuse for every mode (the jit cache keys on shapes only).  One
    jitted instance per mode keeps the caches — in-process and persistent —
    honest.

    Where there is a store (``store``, else the process's own: none on the
    CPU backend), the first call for a (mode, argument shapes and dtypes,
    device) loads the kernel's compiled executable from it and traces
    nothing; on a miss, or when anything on the load path goes wrong, it
    traces, lowers and compiles as ``jax.jit`` would (through the compile
    cache), writes the entry, and serves the ``jax.stages.Compiled`` from
    then on.  A ``Compiled`` belongs to the device it was compiled for, so
    a pinned engine on another chip gets an entry of its own.
    ``wrapper.resolve(*avals)`` makes that first load on the calling
    thread, ahead of the first call.
    ``store=False``: never (parallel/mesh.py's kernels, whose arguments
    arrive unplaced and are sharded by the jit itself)."""
    import threading

    import jax

    jits = {}  # mode -> jax.jit(fn)
    calls = {}  # (mode, avals, device) -> Compiled | _UNSTORED
    locks = {}  # the same key -> the lock its first call holds
    lock = threading.Lock()

    def jit_for(m):
        jitted = jits.get(m)
        if jitted is None:
            with lock:
                jitted = jits.setdefault(m, jax.jit(fn))
        return jitted

    def obtain(st, key, args, build=True):
        """The executable of ``key``, loaded or built by the first caller
        (the others wait on the key's lock) -> ``(it or _UNSTORED, the
        result of the first call over args made on the way, or None)``;
        ``(None, None)`` and the key left unresolved where ``build`` is
        False and the store has no entry for it."""
        with lock:
            key_lock = locks.setdefault(key, threading.Lock())
        with key_lock:
            call = calls.get(key)
            if call is not None:
                return call, None
            call, out = st.obtain(fn, key, args, build)
            if call is None and not build:
                return None, None
            calls[key] = call = call or _UNSTORED
            return call, out

    def first_call(st, m, key, args):
        """Load or build the executable of ``key`` and make its first call
        -> that call's result."""
        call, out = obtain(st, key, args)
        if out is not None:
            return out
        if call is _UNSTORED:
            return jit_for(m)(*args)
        return call(*args)

    def resolve(*avals):
        """Load on the CALLING thread the executable for arguments of
        ``avals`` (``(shape, dtype)`` each) on the default device (a
        pinned engine's scope), as a first call over zeros would; every
        later call of that key, from any thread, is served by it
        (warm-up's ``parallel/engine.py::BatchVerifier.load_kernels``).
        Nothing where there is no store, no entry (the key's first call
        builds it, as before) or the key is resolved already."""
        st = kernelstore.default_store() if store is None else store
        if not st:
            return
        import jax.numpy as jnp
        import numpy as np

        args = [jnp.asarray(np.zeros(shape, dtype)) for shape, dtype in avals]
        key = (mode(),) + _signature(args)
        if key not in calls:
            obtain(st, key, args, build=False)

    def wrapper(*args, **kwargs):
        m = mode()
        st = kernelstore.default_store() if store is None else store
        sig = None if (not st or kwargs) else _signature(args)
        if sig is None:
            return jit_for(m)(*args, **kwargs)
        key = (m,) + sig
        call = calls.get(key)
        if call is None:
            return first_call(st, m, key, args)
        if call is _UNSTORED:
            return jit_for(m)(*args)
        return call(*args)

    wrapper.__name__ = getattr(fn, "__name__", "kernel")
    wrapper.__wrapped__ = fn
    wrapper.resolve = resolve
    return wrapper
