"""The native batch verification call, its helper threads, and the reply
checker above them (utils/hostcrypto.py ``verify_many``,
native/usig.cc ``sigv_*``, utils/replycheck.py).

The native call must give OpenSSL's verdict through ``cryptography``
(``hostcrypto.ecdsa_verify`` / ``ed25519_verify``) item for item, and the
pure-Python oracles' wherever they are defined: a client whose checks
went native must never accept what an inline client refuses."""

import asyncio
import gc
import hashlib
import threading

import pytest

from minbft_tpu.utils import hostcrypto as hc
from minbft_tpu.utils import replycheck

pytestmark = pytest.mark.skipif(
    hc.native_verifier() is None, reason="native module did not build"
)


def _raw(r: int, s: int) -> bytes:
    return r.to_bytes(32, "big") + s.to_bytes(32, "big")


def _rs(sig: bytes):
    return int.from_bytes(sig[:32], "big"), int.from_bytes(sig[32:], "big")


def _flip(data: bytes, bit: int = 0) -> bytes:
    out = bytearray(data)
    out[bit // 8] ^= 1 << (bit % 8)
    return bytes(out)


_D, _Q = hc.keygen()
_DIGEST = hashlib.sha256(b"a reply").digest()
_R, _S = hc.ecdsa_sign_py(_D, _DIGEST)
_SEED, _PUB = hc.ed25519_keygen(bytes(range(32)))
_MSG = hashlib.sha256(b"another reply").digest()
_SIG = hc.ed25519_sign(_SEED, _MSG)

# Encodings of points of small order on edwards25519 (orders 1, 2, 4, 8, 8).
_SMALL_ORDER = [
    bytes([1]) + bytes(31),
    bytes.fromhex("ec" + "ff" * 30 + "7f"),
    bytes(32),
    bytes.fromhex("c7176a703d4dd84fba3c0b760d10670f2a2053fa2c39ccc64ec7fd7792ac037a"),
    bytes.fromhex("26e8958fc2b227b045c3f489f2ef98f0d5dfac05d3c63339b13802886d53fc05"),
]

# name -> (items of verify_many's form, whether the pure-Python oracle is
# defined for them: it takes any key, OpenSSL only a point of the curve)
_ECDSA_CASES = {
    "valid": ([(_Q, _DIGEST, _raw(_R, _S))], True),
    "message_bit": ([(_Q, _flip(_DIGEST, 77), _raw(_R, _S))], True),
    "signature_bit": ([(_Q, _DIGEST, _flip(_raw(_R, _S), 300))], True),
    "key_bit": ([((_Q[0], _Q[1] ^ 1), _DIGEST, _raw(_R, _S))], False),
    "other_key": ([(hc.keygen()[1], _DIGEST, _raw(_R, _S))], True),
    "r_zero": ([(_Q, _DIGEST, _raw(0, _S))], True),
    "s_zero": ([(_Q, _DIGEST, _raw(_R, 0))], True),
    "r_order": ([(_Q, _DIGEST, _raw(hc.N, _S))], True),
    "s_order": ([(_Q, _DIGEST, _raw(_R, hc.N))], True),
    "r_plus_order": ([(_Q, _DIGEST, _raw(_R + hc.N, _S))], True)
    if _R + hc.N < 1 << 256
    else ([(_Q, _DIGEST, _raw(hc.N + 1, _S))], True),
    "high_s": ([(_Q, _DIGEST, _raw(_R, hc.N - _S))], True),
    "off_curve_key": ([((5, 7), _DIGEST, _raw(_R, _S))], False),
    "key_out_of_range": ([((hc.P, 1 << 256), _DIGEST, _raw(_R, _S))], False),
    "mixed": (
        [
            (_Q, _DIGEST, _raw(_R, _S)),
            ((5, 7), _DIGEST, _raw(_R, _S)),
            (_Q, _flip(_DIGEST), _raw(_R, _S)),
            (_Q, _DIGEST, _raw(_R, _S)),
        ],
        False,
    ),
}

_ED_CASES = {
    "valid": [(_PUB, _MSG, _SIG)],
    "message_bit": [(_PUB, _flip(_MSG, 9), _SIG)],
    "signature_bit_R": [(_PUB, _MSG, _flip(_SIG, 3))],
    "signature_bit_S": [(_PUB, _MSG, _flip(_SIG, 300))],
    "key_bit": [(_flip(_PUB, 5), _MSG, _SIG)],
    "long_message": [(_PUB, b"m" * 1000, hc.ed25519_sign(_SEED, b"m" * 1000))],
    "empty_message": [(_PUB, b"", hc.ed25519_sign(_SEED, b""))],
    # S + L names the same scalar: a strict verifier refuses it
    "non_canonical_S": [
        (
            _PUB,
            _MSG,
            _SIG[:32]
            + (int.from_bytes(_SIG[32:], "little") + hc.ED_L).to_bytes(32, "little"),
        )
    ],
    "small_order_R": [(_PUB, _MSG, r + _SIG[32:]) for r in _SMALL_ORDER],
    # S = 0 and R, A of small order: cofactorless, valid only where R + kA
    # happens to be the identity; a cofactored verifier takes them all
    "small_order_A": [
        (a, hashlib.sha256(bytes([i])).digest(), r + bytes(32))
        for a in _SMALL_ORDER
        for r in _SMALL_ORDER
        for i in range(3)
    ],
    # y >= p: OpenSSL takes the encoding, the other verifiers do not
    "non_canonical_key": [(bytes.fromhex("ee" + "ff" * 30 + "7f"), _MSG, _SIG)],
    "mixed": [(_PUB, _MSG, _SIG), (_PUB, _flip(_MSG), _SIG), (_PUB, _MSG, _SIG)],
}


@pytest.mark.parametrize("name", sorted(_ECDSA_CASES))
def test_verify_many_ecdsa_agrees_with_openssl_and_the_oracle(name):
    items, oracle = _ECDSA_CASES[name]
    got = hc.verify_many("ecdsa-p256", items)
    assert got == [hc.ecdsa_verify(q, d, _rs(sig)) for q, d, sig in items]
    if oracle:
        assert got == [hc.ecdsa_verify_py(q, d, _rs(sig)) for q, d, sig in items]
    assert got[0] == (name in ("valid", "high_s", "mixed"))


@pytest.mark.parametrize("name", sorted(_ED_CASES))
def test_verify_many_ed25519_agrees_with_openssl_and_the_oracle(name):
    items = _ED_CASES[name]
    got = hc.verify_many("ed25519", items)
    assert got == [hc.ed25519_verify(*item) for item in items]
    assert got == [hc.ed25519_verify_py(*item) for item in items]
    if name != "small_order_A":
        assert got[0] == (
            name in ("valid", "long_message", "empty_message", "mixed")
        )


@pytest.mark.parametrize(
    "scheme, item",
    [
        ("ecdsa-p256", (_Q, _DIGEST, _raw(_R, _S)[:63])),
        ("ecdsa-p256", (_Q, _DIGEST, _raw(_R, _S) + b"\0")),
        ("ecdsa-p256", (_Q, _DIGEST, b"")),
        ("ecdsa-p256", (_Q, _DIGEST[:31], _raw(_R, _S))),
        ("ecdsa-p256", (_Q, _DIGEST + b"\0", _raw(_R, _S))),
        ("ed25519", (_PUB, _MSG, _SIG[:63])),
        ("ed25519", (_PUB, _MSG, _SIG + b"\0")),
        ("ed25519", (_PUB[:31], _MSG, _SIG)),
        ("ed25519", (_PUB + b"\0", _MSG, _SIG)),
        ("ed25519", (b"", _MSG, _SIG)),
    ],
)
def test_verify_many_wrong_lengths_read_false(scheme, item):
    good = (_Q, _DIGEST, _raw(_R, _S)) if scheme == "ecdsa-p256" else (_PUB, _MSG, _SIG)
    # among good ones, so that a wrong length cannot shift its neighbours
    assert hc.verify_many(scheme, [good, item, good]) == [True, False, True]


@pytest.mark.parametrize("scheme", ["ecdsa-p256", "ed25519"])
def test_verify_many_empty_batch(scheme):
    assert hc.verify_many(scheme, []) == []


@pytest.mark.parametrize("scheme", ["ecdsa-p256", "ed25519"])
def test_verify_many_batch_of_512(scheme):
    """Many keys, every seventh item broken in its own way."""
    items = []
    if scheme == "ecdsa-p256":
        keys = [hc.keygen() for _ in range(9)]
        for i in range(512):
            d, q = keys[i % 9]
            digest = hashlib.sha256(b"%d" % i).digest()
            sig = _raw(*hc.ecdsa_sign(d, digest))
            if i % 7 == 3:
                sig = _flip(sig, i)
            items.append((q, digest, sig))
        want = [hc.ecdsa_verify(q, d, _rs(sig)) for q, d, sig in items]
    else:
        keys = [hc.ed25519_keygen() for _ in range(9)]
        for i in range(512):
            seed, pub = keys[i % 9]
            msg = hashlib.sha256(b"%d" % i).digest()
            sig = hc.ed25519_sign(seed, msg)
            if i % 7 == 3:
                msg = _flip(msg, i % 256)
            items.append((pub, msg, sig))
        want = [hc.ed25519_verify(*item) for item in items]
    assert hc.verify_many(scheme, items) == want
    assert want.count(False) == len(range(3, 512, 7))


def test_verify_many_past_the_key_cache_parses_for_the_call(monkeypatch):
    """A key that finds the cache full is parsed, used and freed."""
    monkeypatch.setattr(hc, "_NATIVE_KEYS_MAX", 0)
    before = dict(hc._NATIVE_KEYS)
    _d, q = hc.keygen()
    digest = hashlib.sha256(b"x").digest()
    sig = _raw(*hc.ecdsa_sign(_d, digest))
    assert hc.verify_many("ecdsa-p256", [(q, digest, sig), ((5, 7), digest, sig)]) == [
        True,
        False,
    ]
    assert hc._NATIVE_KEYS == before


def test_verify_many_from_four_threads_at_once():
    """Two batches may be out at once over the same parsed keys."""
    items = [(_Q, _DIGEST, _raw(_R, _S)), (_Q, _flip(_DIGEST), _raw(_R, _S))] * 64
    out = []

    def work():
        out.append(hc.verify_many("ecdsa-p256", items))

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert out == [[True, False] * 64] * 4


# -- the helper threads -----------------------------------------------------


@pytest.fixture(autouse=True)
def _no_checker_left_over():
    """A loop that an earlier test dropped with its clients running lets
    go of the helper threads when it is collected."""
    gc.collect()


@pytest.fixture
def helpers():
    """Four helper threads for the native call, ended afterwards."""
    lib = hc.native_verifier()
    assert lib.sigv_pool_threads() == 0
    assert lib.sigv_pool_start(4) == 0
    try:
        yield lib
    finally:
        lib.sigv_pool_stop()
        assert lib.sigv_pool_threads() == 0


@pytest.mark.parametrize("scheme", ["ecdsa-p256", "ed25519"])
@pytest.mark.parametrize("size", [1, 2, 3, 5, 8, 64, 513])
def test_verify_many_on_helper_threads_gives_the_serial_verdicts(helpers, scheme, size):
    """Every size around the number of helpers: an item verified twice or
    not at all would show as a verdict out of place."""
    items = []
    for i in range(size):
        msg = hashlib.sha256(b"%d" % i).digest()
        if scheme == "ecdsa-p256":
            item = (_Q, msg, _raw(*hc.ecdsa_sign(_D, msg)))
        else:
            item = (_PUB, msg, hc.ed25519_sign(_SEED, msg))
        if i % 3 == 1:
            item = (item[0], _flip(msg, i % 256), item[2])
        items.append(item)
    want = [i % 3 != 1 for i in range(size)]
    assert hc.verify_many(scheme, items) == want


def test_verify_many_calls_from_four_threads_share_the_helpers(helpers):
    items = [(_Q, _DIGEST, _raw(_R, _S)), (_Q, _flip(_DIGEST), _raw(_R, _S))] * 32
    out = []

    def work():
        for _ in range(8):
            out.append(hc.verify_many("ecdsa-p256", items))

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert out == [[True, False] * 32] * 32


def test_helper_threads_are_shared_and_the_last_stop_ends_them():
    lib = hc.native_verifier()
    assert lib.sigv_pool_threads() == 0
    assert lib.sigv_pool_start(3) == 0
    assert lib.sigv_pool_start(5) == 0  # finds the three
    assert lib.sigv_pool_threads() == 3
    lib.sigv_pool_stop()
    assert lib.sigv_pool_threads() == 3
    lib.sigv_pool_stop()
    assert lib.sigv_pool_threads() == 0
    lib.sigv_pool_stop()  # one too many is nobody's
    assert lib.sigv_pool_threads() == 0
    assert lib.sigv_pool_start(0) != 0 and lib.sigv_pool_start(65) != 0
    assert hc.verify_many("ecdsa-p256", [(_Q, _DIGEST, _raw(_R, _S))] * 3) == [True] * 3


# -- the checker ------------------------------------------------------------


def _checks(n: int, bad=()):
    out = []
    for i in range(n):
        digest = hashlib.sha256(b"check %d" % i).digest()
        sig = _raw(*hc.ecdsa_sign(_D, digest))
        out.append((_Q, _flip(digest) if i in bad else digest, sig))
    return out


def test_checker_holds_a_frames_verdicts_once_each():
    async def run():
        lib = hc.native_verifier()
        checker = replycheck.acquire()
        try:
            assert replycheck.current() is checker and checker.off_lock
            assert lib.sigv_pool_threads() == replycheck.HELPERS
            items = _checks(5, bad={1, 4})
            assert checker.precheck("ecdsa-p256", items) == 5
            got = [checker.verdict("ecdsa-p256", *item) for item in items]
            assert got == [True, False, True, True, False]
            # once: a second ask is answered by the inline path
            assert checker.verdict("ecdsa-p256", *items[0]) is None
            # another scheme, key, message or signature is another check
            assert checker.precheck("ecdsa-p256", items[:1]) == 1
            q, digest, sig = items[0]
            assert checker.verdict("ed25519", q, digest, sig) is None
            assert checker.verdict("ecdsa-p256", q, digest, _flip(sig)) is None
            assert checker.verdict("ecdsa-p256", q, _flip(digest), sig) is None
            assert checker.verdict("ecdsa-p256", q, digest, sig) is True
            # what nobody asked for goes with the next frame
            checker.precheck("ecdsa-p256", items)
            assert checker.precheck("ecdsa-p256", items[:2]) == 2
            assert checker.verdict("ecdsa-p256", *items[3]) is None
            assert checker.precheck("ecdsa-p256", []) == 0
        finally:
            replycheck.release(checker)
        assert replycheck.current() is None and lib.sigv_pool_threads() == 0

    asyncio.run(run())


def test_checker_without_the_native_module_leaves_the_inline_path(monkeypatch):
    """The loader's failure: nothing is verified ahead, nothing is held."""
    monkeypatch.setattr(hc, "native_verifier", lambda: None)

    async def run():
        checker = replycheck.acquire()
        try:
            assert not checker.off_lock
            items = _checks(3)
            assert checker.precheck("ecdsa-p256", items) == 0
            assert checker.verdict("ecdsa-p256", *items[0]) is None
        finally:
            replycheck.release(checker)

    asyncio.run(run())
    with pytest.raises(RuntimeError):
        hc.verify_many("ecdsa-p256", _checks(1))


def test_two_loops_checkers_share_the_helpers():
    """A checker a loop; the helper threads end with the last of them."""
    lib = hc.native_verifier()
    inner_done = threading.Event()
    go_on = threading.Event()

    async def inner():
        checker = replycheck.acquire()
        inner_done.set()
        await asyncio.get_running_loop().run_in_executor(None, go_on.wait, 10)
        assert lib.sigv_pool_threads() == replycheck.HELPERS
        replycheck.release(checker)

    async def outer():
        checker = replycheck.acquire()
        other = threading.Thread(target=asyncio.run, args=(inner(),))
        other.start()
        await asyncio.get_running_loop().run_in_executor(None, inner_done.wait, 10)
        replycheck.release(checker)
        assert lib.sigv_pool_threads() == replycheck.HELPERS  # the other loop's
        go_on.set()
        await asyncio.get_running_loop().run_in_executor(None, other.join, 10)
        assert lib.sigv_pool_threads() == 0

    asyncio.run(outer())
