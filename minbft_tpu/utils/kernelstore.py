"""The kernels' compiled executables, kept beside the compile cache.

The compile cache (utils/jaxcache.py) saves the backend compile of a
kernel, not what comes before it: every process still walks the kernel's
Python source into a jaxpr (16-28 s for a deployment's kernels), lowers it
(4 s) and hashes the module to find its cache entry.  This store keeps the
finished ``jax.stages.Compiled`` itself, serialized
(``jax.experimental.serialize_executable``; zlib over it), under a key built
WITHOUT tracing, so that a warm process loads its kernels and traces
nothing.  ``ops/lowering.py::per_mode_jit`` is its one caller.

The directory is ``<compile cache>/kernel_store`` and the same
``MINBFT_JAX_CACHE=0`` turns both off.  **It is as trusted as the
checkout**: an entry is a pickle, and unpickling runs code, where the
compile cache only ever held device code.  So the directory is created
0700 and its files 0600, and nothing is loaded from a directory or file
that another user owns or that group or others can write.

A stale hit would run an old kernel, so the key errs towards misses: the
kernel's name, the lowering mode, the arguments' shapes and dtypes, the
device (platform, kind, id, the runtime's build), the versions of jax,
jaxlib and numpy, ``XLA_FLAGS`` / ``LIBTPU_INIT_ARGS``, and a digest of
every source file the trace reads.  Anything that goes wrong on the load
path is counted (``load_failures``), falls back to building, and rewrites
the entry.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import pickle
import stat
import tempfile
import threading
import time
import zlib
from typing import Dict, Optional

from . import jaxcache

FORMAT = 1
SUBDIR = "kernel_store"

# What a kernel's trace reads: the kernels themselves and the module whose
# curve constants they take at trace time (ops/p256.py, ops/ed25519.py).
_PACKAGE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACED_SOURCES = ("ops", os.path.join("utils", "hostcrypto.py"))


@dataclasses.dataclass
class KernelStoreStats:
    """One kernel's traffic with the store, process-wide.  ``load_s`` is
    the whole of the load path (failed loads too); ``digest_s`` (making
    the key: the sources are hashed once a process, for the kernel that
    asks first), ``read_s`` (the file, decompressed) and ``deserialize_s``
    lie inside it; ``bytes`` are the files', read and written.
    ``off_main_loads`` / ``off_main_builds``: of ``loads`` / ``builds``,
    those made on a thread that is not the process's main one, where the
    runtime loads an executable 5-6 times slower (warm-up loads its
    kernels on the main thread: ``ops/lowering.py::per_mode_jit``)."""

    loads: int = 0
    builds: int = 0
    off_main_loads: int = 0
    off_main_builds: int = 0
    load_failures: int = 0
    save_failures: int = 0
    load_s: float = 0.0
    read_s: float = 0.0
    deserialize_s: float = 0.0
    digest_s: float = 0.0
    build_s: float = 0.0
    bytes: int = 0

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


_STATS: Dict[str, KernelStoreStats] = {}
_stats_lock = threading.Lock()


def stats_for(name: str) -> KernelStoreStats:
    with _stats_lock:
        st = _STATS.get(name)
        if st is None:
            st = _STATS[name] = KernelStoreStats()
        return st


def stats() -> Dict[str, dict]:
    """Per kernel name, every kernel that has met a store in this process
    (``timeline()["jax"]["kernel_store"]``, the engine dump, Prometheus)."""
    with _stats_lock:
        return {name: st.to_dict() for name, st in sorted(_STATS.items())}


def totals() -> dict:
    """:func:`stats` summed over the kernels."""
    out = KernelStoreStats().to_dict()
    for row in stats().values():
        for k, v in row.items():
            out[k] += v
    return out


_digest: Optional[str] = None
_digest_lock = threading.Lock()


def sources_digest(root: str = _PACKAGE) -> str:
    """SHA-256 over the names and bytes of :data:`TRACED_SOURCES` under
    ``root``; the package's own is computed once a process."""
    global _digest
    if root == _PACKAGE and _digest is not None:
        return _digest
    with _digest_lock:
        if root == _PACKAGE and _digest is not None:
            return _digest
        paths = []
        for entry in TRACED_SOURCES:
            full = os.path.join(root, entry)
            if os.path.isdir(full):
                paths += [
                    os.path.join(entry, n)
                    for n in os.listdir(full) if n.endswith(".py")
                ]
            else:
                paths.append(entry)
        h = hashlib.sha256()
        for rel in sorted(paths):
            with open(os.path.join(root, rel), "rb") as fh:
                body = fh.read()
            h.update(f"{rel}\0{len(body)}\0".encode())
            h.update(body)
        digest = h.hexdigest()
        if root == _PACKAGE:
            _digest = digest
        return digest


class Refused(Exception):
    """The load path met something it will not load from."""


def _private(st: os.stat_result, what: str) -> None:
    if st.st_uid != os.geteuid():
        raise Refused(f"{what} belongs to uid {st.st_uid}")
    if st.st_mode & (stat.S_IWGRP | stat.S_IWOTH):
        raise Refused(f"{what} is writable by group or others")


class KernelStore:
    """Serialized executables under ``directory``, one file an entry."""

    def __init__(self, directory: str):
        self.directory = directory

    def key(self, kernel: str, mode: str, avals, device) -> dict:
        """Everything a hit depends on, as plain JSON values, read without
        tracing ``kernel``.  ``avals``: ``(shape, dtype)`` an argument."""
        import jax
        import jaxlib
        import numpy

        return {
            "format": FORMAT,
            "kernel": kernel,
            "mode": mode,
            "args": [[list(shape), str(dtype)] for shape, dtype in avals],
            "platform": device.platform,
            "device_kind": device.device_kind,
            "device_id": device.id,
            "platform_version": device.client.platform_version,
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "numpy": numpy.__version__,
            "x64": bool(jax.config.jax_enable_x64),
            "xla_flags": os.environ.get("XLA_FLAGS", ""),
            "libtpu_init_args": os.environ.get("LIBTPU_INIT_ARGS", ""),
            "sources": sources_digest(),
        }

    def path(self, name: str, key: dict) -> str:
        mode = key["mode"]
        digest = hashlib.sha256(json.dumps(key, sort_keys=True).encode())
        return os.path.join(
            self.directory, f"{name}.{mode}.{digest.hexdigest()[:32]}"
        )

    def _usable_directory(self, create: bool) -> bool:
        """The directory is there, a real directory, ours alone (Refused
        if not); made 0700 when ``create`` and absent."""
        try:
            st = os.lstat(self.directory)
        except FileNotFoundError:
            if not create:
                return False
            os.makedirs(os.path.dirname(self.directory) or ".", exist_ok=True)
            try:
                os.mkdir(self.directory, 0o700)
            except FileExistsError:  # another process of this start
                pass
            st = os.lstat(self.directory)
        if not stat.S_ISDIR(st.st_mode):
            raise Refused(f"{self.directory} is not a directory")
        _private(st, self.directory)
        return True

    def load(self, name: str, key: dict, device):
        """-> the entry's ``jax.stages.Compiled`` on ``device`` and the
        outputs' ``[(shape, dtype)]`` as recorded when it was built, or
        None when there is no entry.  Raises on anything else."""
        from jax.experimental import serialize_executable

        st = stats_for(name)
        path = self.path(name, key)
        t0 = time.perf_counter()
        if not self._usable_directory(create=False):
            return None
        try:
            fd = os.open(path, os.O_RDONLY)
        except FileNotFoundError:
            return None
        with os.fdopen(fd, "rb") as fh:
            _private(os.fstat(fh.fileno()), path)
            blob = fh.read()
        st.bytes += len(blob)
        blob = zlib.decompress(blob)
        t1 = time.perf_counter()
        st.read_s += t1 - t0
        entry = pickle.loads(blob)
        if entry["key"] != key:
            raise Refused(f"{path} holds {entry['key'].get('kernel')!r}")
        compiled = serialize_executable.deserialize_and_load(
            entry["payload"], entry["in_tree"], entry["out_tree"],
            backend=device.client, execution_devices=[device],
        )
        st.deserialize_s += time.perf_counter() - t1
        return compiled, entry["out"]

    def save(self, name: str, key: dict, compiled) -> int:
        """Write ``compiled`` under ``key`` atomically -> bytes written."""
        from jax.experimental import serialize_executable

        payload, in_tree, out_tree = serialize_executable.serialize(compiled)
        blob = pickle.dumps(
            {
                "key": key, "payload": payload,
                "in_tree": in_tree, "out_tree": out_tree,
                "out": out_avals(compiled.out_info),
            },
            protocol=pickle.HIGHEST_PROTOCOL,
        )
        # an executable is ~40 MB of which seven eighths compress away at
        # level 1, for ~0.2 s on either side
        blob = zlib.compress(blob, 1)
        self._usable_directory(create=True)
        fd, tmp = tempfile.mkstemp(dir=self.directory, prefix=".tmp-")  # 0600
        try:
            with os.fdopen(fd, "wb") as fh:
                fh.write(blob)
            os.replace(tmp, self.path(name, key))
        except BaseException:
            os.unlink(tmp)
            raise
        return len(blob)

    def obtain(self, fn, key, args, build: bool = True):
        """The executable of kernel ``fn`` for ``key`` = (mode, avals,
        device), loaded, or built and written -> ``(Compiled, the result
        of its first call over args or None if not made yet)``;
        ``(None, None)`` when the store is switched off, or holds no
        entry for ``key`` and ``build`` is False (the key's first call
        builds it then)."""
        import jax

        if not jaxcache.switched_on():  # nothing read, nothing written
            return None, None
        mode, avals, device = key
        name = getattr(fn, "__name__", "kernel")
        st = stats_for(name)
        off_main = threading.current_thread() is not threading.main_thread()
        store_key = None
        t0 = time.perf_counter()
        try:
            store_key = self.key(
                f"{fn.__module__}.{fn.__qualname__}", mode, avals, device
            )
            st.digest_s += time.perf_counter() - t0
            found = self.load(name, store_key, device)
            if found is not None:
                compiled, want = found
                # waited for: what a bad executable does wrong it does
                # here, not in the caller's hands
                out = jax.block_until_ready(compiled(*args))
                if out_avals(out) != want:
                    raise Refused(f"{name} returned {out_avals(out)}")
                st.loads += 1
                st.off_main_loads += off_main
                st.load_s += time.perf_counter() - t0
                return compiled, out
            if not build:
                st.load_s += time.perf_counter() - t0
                return None, None
        except Exception:  # noqa: BLE001 - whatever it was: build, rewrite
            st.load_failures += 1
        st.load_s += time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(*args).compile()
        st.builds += 1
        st.off_main_builds += off_main
        st.build_s += time.perf_counter() - t0
        placed = {
            d for s in jax.tree.leaves(compiled.input_shardings)
            for d in s.device_set
        }
        if store_key is not None and placed == {device}:
            try:
                st.bytes += self.save(name, store_key, compiled)
            except Exception:  # noqa: BLE001 - served all the same
                st.save_failures += 1
        return compiled, None


def out_avals(tree) -> list:
    """``[(shape, dtype)]`` of the leaves of ``tree`` (arrays or
    ``Compiled.out_info``), as the entries record them."""
    import jax

    return [
        (tuple(leaf.shape), str(leaf.dtype)) for leaf in jax.tree.leaves(tree)
    ]


_default: Optional[KernelStore] = None
_default_known = False


def default_store() -> Optional[KernelStore]:
    """The process's store, or None where kernels keep the plain
    ``jax.jit`` path: on the CPU backend, whose loader of ahead-of-time
    results warns about machine features on every load, which cannot
    serialize again an executable that it retrieved from the compile cache
    (the copy fails when it runs; the TPU runtime can), whose kernels are
    the tests' tiny shapes, and which nobody serves from."""
    global _default, _default_known
    if not _default_known:
        import jax

        if jax.default_backend() != "cpu":
            _default = KernelStore(os.path.join(jaxcache.cache_dir(), SUBDIR))
        _default_known = True
    return _default
