"""The clients' reply checks, a frame at a time and off the interpreter lock.

A client acknowledges a write on f+1 matching replies, each verified on
the host (OpenSSL).  Made one by one through ``cryptography`` that is the
largest single piece of work on a loop that carries many clients, and it
is made with the interpreter lock held, so nothing else of the process
moves meanwhile.  Here the replies that one transport frame carries are
verified by ONE native call (:func:`minbft_tpu.utils.hostcrypto.verify_many`):
the caller lets go of the lock for the whole batch, and the batch's items
are verified side by side on the native module's helper threads and the
caller's own.  The caller WAITS for the call: a verdict that came back a
loop turn later would cost the write more than its check does (PERF.md
section 6, PR 32: a turn of the benchmark's loop is tens of milliseconds,
a check well under one).

One :class:`ReplyChecker` a loop, alive while some client on that loop
holds it (:func:`acquire` / :func:`release`, from ``Client.start`` /
``Client.stop``); the helper threads live as long as some checker does.
The way in is the authenticator's: ``precheck_message_authen_tags`` puts a
frame's verdicts here, and the reply-by-reply ``verify_message_authen_tag``
that follows takes each of them out (:meth:`ReplyChecker.verdict`), so
whatever wraps a client's authenticator still sees every reply that counts
go through it exactly once, after its own verification.

What the behaviour depends on is only what can be seen here: whether the
native call loaded (else every check is made inline, as before this
module, and :class:`ReplyCheckStats` says so), and how many replies the
frame carried (:data:`MIN_BATCH`).
"""

from __future__ import annotations

import asyncio
import weakref
from typing import Optional

from . import hostcrypto as hc

# A frame with fewer replies than this is left to the inline path: one
# check gains nothing from a batch call's packing.
MIN_BATCH = 2
# Helper threads of the native module (the caller's thread works too).
HELPERS = 4


class ReplyCheckStats:
    """Counters of one client or of the process (:data:`TOTAL`)."""

    __slots__ = ("batches", "checked", "off_lock", "acked")

    def __init__(self):
        # native calls
        self.batches = 0
        # replies (and BUSY signals) authenticated
        self.checked = 0
        # signatures verified in those native calls, off the interpreter
        # lock; the rest of ``checked`` was verified inline, one by one
        self.off_lock = 0
        # requests whose quorum formed
        self.acked = 0

    def to_dict(self) -> dict:
        out = {name: getattr(self, name) for name in self.__slots__}
        out["inline"] = max(self.checked - self.off_lock, 0)
        out["checks_per_write"] = self.checked / self.acked if self.acked else None
        return out


TOTAL = ReplyCheckStats()


class ReplyChecker:
    def __init__(self):
        # None: the native module is not to be had, every check is inline.
        self._lib = hc.native_verifier()
        # verdicts of the frame in hand: (scheme, key, message, signature) -> bool
        self._ahead: dict = {}
        self._users = 0
        # Ends the helper threads (if this is the last checker to hold
        # them) once: from close(), or when a loop is dropped with its
        # clients still running.
        self._closer = None
        if self._lib is not None and self._lib.sigv_pool_start(HELPERS) == 0:
            self._closer = weakref.finalize(self, self._lib.sigv_pool_stop)

    @property
    def off_lock(self) -> bool:
        return self._lib is not None

    def precheck(self, scheme: str, items: list) -> int:
        """Verify ``items`` (``hostcrypto.verify_many``'s form) in one
        native call and keep the verdicts for :meth:`verdict`.  -> how many
        were verified: 0 without the native module."""
        self._ahead.clear()  # what nobody asked for of the last frame
        if self._lib is None or not items:
            return 0
        verdicts = hc.verify_many(scheme, items, self._lib)
        for item, ok in zip(items, verdicts):
            self._ahead[(scheme, *item)] = ok
        return len(items)

    @property
    def holding(self) -> bool:
        """Whether any verdict is held (asked before a caller goes to the
        length of hashing its message)."""
        return bool(self._ahead)

    def verdict(self, scheme: str, pub, msg: bytes, sig: bytes) -> Optional[bool]:
        """The verdict :meth:`precheck` holds for exactly this check, once;
        None where it holds none."""
        return self._ahead.pop((scheme, pub, msg, sig), None)

    def close(self) -> None:
        self._ahead.clear()
        self._lib = None
        if self._closer is not None:
            self._closer()


# loop -> its checker
_CHECKERS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def acquire() -> ReplyChecker:
    """The running loop's checker, made on first use; one release each."""
    loop = asyncio.get_running_loop()
    checker = _CHECKERS.get(loop)
    if checker is None:
        checker = _CHECKERS[loop] = ReplyChecker()
    checker._users += 1
    return checker


def release(checker: ReplyChecker) -> None:
    checker._users -= 1
    if checker._users > 0:
        return
    checker.close()
    loop = asyncio.get_running_loop()
    if _CHECKERS.get(loop) is checker:
        del _CHECKERS[loop]


def current() -> Optional[ReplyChecker]:
    """The running loop's checker while a client holds it, else None."""
    if not _CHECKERS:
        return None
    return _CHECKERS.get(asyncio.get_running_loop())
