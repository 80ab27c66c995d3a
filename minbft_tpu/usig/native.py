"""ctypes binding for the native C++ USIG module.

The shim layer of the reference is a cgo bridge that dlopens
``libusig_shim.so`` and calls through function pointers
(reference usig/sgx/usig-enclave.go:97-114, 337-347); here the bridge is
ctypes over ``minbft_tpu/native/libusig.so``.  The module is optional:
:func:`load` returns None when the library isn't built and callers fall
back to the pure-Python :class:`minbft_tpu.usig.software.EcdsaUSIG`.

``NativeEcdsaUSIG`` produces byte-identical UI certificates to
``EcdsaUSIG`` (cert = epoch8 || r32 || s32, ID = epoch8 || x32 || y32), so
its UIs verify on the TPU batch path (usig_verify_items) unchanged.  Unlike
the Python class it supports key **sealing**: ``seal()`` exports a blob
that ``from_sealed`` restores — the durable-state story of the reference
(sealed USIG key in keys.yaml, reference keymanager.go:299-328).  Only the
KEY is sealed: every init draws a fresh random epoch (reference
usig/sgx/enclave/usig.c:168-186), so a restored instance — whose counter
restarts at 1 — can never re-certify (epoch, cv) values issued by a
previous instance of the same key.  Verifiers learn the new epoch
trust-on-first-use (SampleAuthenticator epoch capture, reference
sample/authentication/crypto.go:204-218).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
from typing import Optional

from .usig import UI, USIG, UsigError

_EPOCH_LEN = 8

_NATIVE_DIR = os.path.join(os.path.dirname(__file__), "..", "native")
_LIB_PATH = os.path.abspath(os.path.join(_NATIVE_DIR, "libusig.so"))

USIG_OK = 0

_lib = None
_load_attempted = False


def build(quiet: bool = True) -> bool:
    """Build the native module in-tree (requires g++).  True on success."""
    try:
        # noqa: AH101 - one-shot native build at first load (gated by _load_attempted)
        res = subprocess.run(
            ["make", "libusig.so"],
            cwd=os.path.abspath(_NATIVE_DIR),
            capture_output=quiet,
            timeout=120,
        )
        return res.returncode == 0
    except Exception:
        return False


def load(auto_build: bool = False) -> Optional[ctypes.CDLL]:
    """Load (optionally building) the native library; None if unavailable."""
    global _lib, _load_attempted
    if _lib is not None:
        return _lib
    if _load_attempted and not auto_build:
        return None
    _load_attempted = True
    if not os.path.exists(_LIB_PATH) and auto_build:
        build()
    if not os.path.exists(_LIB_PATH):
        return None
    try:
        lib = ctypes.CDLL(_LIB_PATH)
    except OSError:
        return None
    if not hasattr(lib, "sigv_pool_start") and auto_build:
        # Stale build predating batch verification (or encrypted sealing,
        # v3, before it): rebuild + reload.
        # The Makefile links to a temp name and renames, so the rebuilt
        # file is a fresh inode and dlopen yields a new handle.
        if build():
            try:
                lib = ctypes.CDLL(_LIB_PATH)
            except OSError:
                return None
    # A stale-but-functional library (no compiler to rebuild with) still
    # serves everything except encrypted sealing (pre-v3) and batch
    # verification — bind what exists.
    _bind(lib)
    _lib = lib
    return _lib


def _bind(lib) -> None:
    u8p = ctypes.POINTER(ctypes.c_uint8)
    lib.usig_init.argtypes = [
        ctypes.POINTER(ctypes.c_void_p),
        ctypes.c_char_p,
        ctypes.c_size_t,
    ]
    lib.usig_destroy.argtypes = [ctypes.c_void_p]
    lib.usig_create_ui.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_uint64),
        u8p,
    ]
    lib.usig_get_epoch.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_uint64),
    ]
    lib.usig_get_pubkey.argtypes = [ctypes.c_void_p, u8p]
    lib.usig_sealed_size.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_size_t),
    ]
    lib.usig_seal.argtypes = [
        ctypes.c_void_p,
        u8p,
        ctypes.c_size_t,
        ctypes.POINTER(ctypes.c_size_t),
    ]
    lib.usig_verify_ui.argtypes = [
        ctypes.c_char_p,
        ctypes.c_uint64,
        ctypes.c_char_p,
        ctypes.c_uint64,
        ctypes.c_char_p,
    ]
    lib.usig_native_version.restype = ctypes.c_char_p
    if hasattr(lib, "sigv_pool_start"):
        lib.sigv_key_new.argtypes = [
            ctypes.c_int,
            ctypes.c_char_p,
            ctypes.c_size_t,
        ]
        lib.sigv_key_new.restype = ctypes.c_void_p
        lib.sigv_key_free.argtypes = [ctypes.c_void_p]
        lib.sigv_key_free.restype = None
        lib.sigv_verify_many.argtypes = [
            ctypes.c_int,
            ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_uint32),
            ctypes.c_char_p,
            ctypes.c_char_p,
        ]
        lib.sigv_pool_start.argtypes = [ctypes.c_int]
        lib.sigv_pool_stop.argtypes = []
        lib.sigv_pool_stop.restype = None
        lib.sigv_pool_threads.argtypes = []
    if hasattr(lib, "usig_init2"):
        lib.usig_init2.argtypes = [
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.c_char_p,
            ctypes.c_size_t,
            ctypes.c_char_p,
            ctypes.c_size_t,
        ]
        lib.usig_sealed_size2.argtypes = [
            ctypes.c_void_p,
            ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_size_t),
        ]
        lib.usig_seal2.argtypes = [
            ctypes.c_void_p,
            ctypes.c_char_p,
            ctypes.c_size_t,
            u8p,
            ctypes.c_size_t,
            ctypes.POINTER(ctypes.c_size_t),
        ]


def available(auto_build: bool = False) -> bool:
    return load(auto_build=auto_build) is not None


class NativeEcdsaUSIG(USIG):
    """USIG backed by the native module (reference SGXUSIG analogue,
    usig/sgx/sgx-usig.go:42-62)."""

    SCHEME = "ecdsa-p256"

    def __init__(
        self,
        sealed: Optional[bytes] = None,
        secret: Optional[bytes] = None,
        _lib_override=None,
    ):
        lib = _lib_override or load(auto_build=True)
        if lib is None:
            raise UsigError("native USIG module not available (build failed?)")
        self._lib = lib
        handle = ctypes.c_void_p()
        if hasattr(lib, "usig_init2"):
            rc = lib.usig_init2(
                ctypes.byref(handle),
                sealed if sealed is not None else None,
                len(sealed) if sealed is not None else 0,
                secret if secret else None,
                len(secret) if secret else 0,
            )
        elif secret or (sealed is not None and sealed[:4] == b"USG3"):
            raise UsigError(
                "this libusig.so predates encrypted sealing (v3); rebuild "
                "the native module to use a sealing secret"
            )
        else:
            rc = lib.usig_init(
                ctypes.byref(handle),
                sealed if sealed is not None else None,
                len(sealed) if sealed is not None else 0,
            )
        if rc != USIG_OK:
            raise UsigError(
                "usig_init failed: encrypted blob needs the sealing secret"
                if rc == 6
                else f"usig_init failed (rc={rc})"
            )
        self._h = handle
        epoch = ctypes.c_uint64()
        if lib.usig_get_epoch(self._h, ctypes.byref(epoch)) != USIG_OK:
            raise UsigError("usig_get_epoch failed")
        self._epoch = int(epoch.value).to_bytes(8, "big")
        pub = (ctypes.c_uint8 * 64)()
        if lib.usig_get_pubkey(self._h, pub) != USIG_OK:
            raise UsigError("usig_get_pubkey failed")
        self._pub = bytes(pub)

    def __del__(self):  # release the native instance
        h = getattr(self, "_h", None)
        if h:
            try:
                self._lib.usig_destroy(h)
            except Exception:
                pass
            self._h = None

    # -- USIG interface ------------------------------------------------------

    @property
    def epoch(self) -> bytes:
        return self._epoch

    @property
    def public_key(self):
        return (
            int.from_bytes(self._pub[:32], "big"),
            int.from_bytes(self._pub[32:], "big"),
        )

    def id(self) -> bytes:
        return self._epoch + self._pub

    def create_ui(self, message: bytes) -> UI:
        digest = hashlib.sha256(message).digest()
        counter = ctypes.c_uint64()
        sig = (ctypes.c_uint8 * 64)()
        rc = self._lib.usig_create_ui(self._h, digest, ctypes.byref(counter), sig)
        if rc != USIG_OK:
            raise UsigError(f"usig_create_ui failed (rc={rc})")
        return UI(counter=int(counter.value), cert=self._epoch + bytes(sig))

    def verify_ui(self, message: bytes, ui: UI, usig_id: bytes) -> None:
        if ui.counter == 0:
            raise UsigError("zero counter")
        if len(ui.cert) != _EPOCH_LEN + 64:
            raise UsigError("malformed certificate")
        cert_epoch, sig = ui.cert[:_EPOCH_LEN], ui.cert[_EPOCH_LEN:]
        if len(usig_id) != _EPOCH_LEN + 64:
            raise UsigError("malformed USIG ID")
        id_epoch, pub = usig_id[:_EPOCH_LEN], usig_id[_EPOCH_LEN:]
        if cert_epoch != id_epoch:
            raise UsigError("epoch mismatch")
        digest = hashlib.sha256(message).digest()
        rc = self._lib.usig_verify_ui(
            pub,
            int.from_bytes(id_epoch, "big"),
            digest,
            ui.counter,
            sig,
        )
        if rc != USIG_OK:
            raise UsigError("invalid UI certificate")

    # -- sealing (durable state) --------------------------------------------

    def seal(self, secret: Optional[bytes] = None) -> bytes:
        """Export the sealed key blob (reference SealedKey,
        usig/sgx/usig-enclave.go:254-268).  The epoch is volatile by
        design and is not part of the blob.  With ``secret`` the blob is
        AES-256-GCM encrypted inside the native module (v3 — the
        sgx_seal_data confidentiality analogue, reference
        usig/sgx/enclave/usig.c:107-116); without, the plaintext v2
        layout."""
        if not hasattr(self._lib, "usig_seal2"):
            if secret:
                raise UsigError(
                    "this libusig.so predates encrypted sealing (v3); "
                    "rebuild the native module to use a sealing secret"
                )
            need = ctypes.c_size_t()
            if self._lib.usig_sealed_size(self._h, ctypes.byref(need)) != USIG_OK:
                raise UsigError("usig_sealed_size failed")
            buf = (ctypes.c_uint8 * need.value)()
            out_len = ctypes.c_size_t()
            rc = self._lib.usig_seal(
                self._h, buf, need.value, ctypes.byref(out_len)
            )
            if rc != USIG_OK:
                raise UsigError(f"usig_seal failed (rc={rc})")
            return bytes(buf[: out_len.value])
        need = ctypes.c_size_t()
        if (
            self._lib.usig_sealed_size2(
                self._h, len(secret) if secret else 0, ctypes.byref(need)
            )
            != USIG_OK
        ):
            raise UsigError("usig_sealed_size failed")
        buf = (ctypes.c_uint8 * need.value)()
        out_len = ctypes.c_size_t()
        rc = self._lib.usig_seal2(
            self._h,
            secret if secret else None,
            len(secret) if secret else 0,
            buf,
            need.value,
            ctypes.byref(out_len),
        )
        if rc != USIG_OK:
            raise UsigError(f"usig_seal failed (rc={rc})")
        return bytes(buf[: out_len.value])

    @classmethod
    def from_sealed(
        cls, sealed: bytes, secret: Optional[bytes] = None
    ) -> "NativeEcdsaUSIG":
        """Restore an instance: same key, FRESH epoch, counter restarts
        at 1 (reference usig.c:168-186).  ``secret`` is required for v3
        (encrypted) blobs."""
        return cls(sealed=sealed, secret=secret)
