"""The comparison that decides ``correct``, and its plain reference.

The reference is the service's semantics written down plainly, with nothing
of the program in it: a hash chain replayed serially over the committed
order (``sha256(height || previous digest || payload)`` per block, the
digest being the write's result), and OpenSSL's ECDSA over the reply bytes
that the clients accepted.  Every number compared is a count of breaches
of a guarantee that the configuration states, so every limit is 0.
"""

from __future__ import annotations

import asyncio
import hashlib
import struct
import time
from typing import Dict, Iterable, List, Sequence

from .manifest import BenchmarkError

LIMITS = {
    "never_answered": 0,
    "wrong_results": 0,
    "ledgers_off_reference": 0,
    "executed_twice": 0,
    "forged_executed": 0,
    "unrequested_executed": 0,
    "acks_short_of_quorum": 0,
    "device_path_faults": 0,
    "view_changes": 0,
}

_REPLY_HEAD = struct.Struct(">IIQBB32s")  # after b"REPLY": messages/authen.py


def replay(order: Sequence[bytes]) -> tuple:
    """The reference: -> (head digest, {payload: result})."""
    prev = hashlib.sha256(struct.pack(">Q", 0) + bytes(32) + b"genesis").digest()
    results: Dict[bytes, bytes] = {}
    for height, payload in enumerate(order, start=1):
        prev = hashlib.sha256(struct.pack(">Q", height) + prev + payload).digest()
        results[payload] = prev
    return prev, results


class ReplyVerifier:
    """OpenSSL's verdict on a reply's signature, by the configuration's
    scheme and the replicas' public keys."""

    def __init__(self, scheme: str, replica_pubs: dict):
        if scheme != "ecdsa-p256":
            raise BenchmarkError(f"no reference verifier for scheme {scheme!r} yet")
        from cryptography.hazmat.primitives.asymmetric import ec

        self._keys = {
            rid: ec.EllipticCurvePublicNumbers(x, y, ec.SECP256R1()).public_key()
            for rid, (x, y) in replica_pubs.items()
        }

    def valid(self, replica_id: int, msg: bytes, signature: bytes) -> bool:
        from cryptography.exceptions import InvalidSignature
        from cryptography.hazmat.primitives import hashes
        from cryptography.hazmat.primitives.asymmetric import ec, utils

        key = self._keys.get(replica_id)
        if key is None or len(signature) != 64:
            return False
        der = utils.encode_dss_signature(
            int.from_bytes(signature[:32], "big"), int.from_bytes(signature[32:], "big")
        )
        try:
            key.verify(der, msg, ec.ECDSA(hashes.SHA256()))
        except InvalidSignature:
            return False
        return True


def quorum_shortfalls(issued: Iterable, accepted_by_client: Sequence[Sequence[tuple]],
                      verifier: ReplyVerifier, f: int) -> int:
    """Acknowledged writes with fewer than f+1 replies that the client had
    accepted by the time of the ack, each from a distinct replica, each
    naming this client and this result, each validly signed."""
    by_digest: List[Dict[bytes, list]] = []
    for accepted in accepted_by_client:
        index: Dict[bytes, list] = {}
        for pos, (peer_id, msg, sig) in enumerate(accepted):
            if len(msg) != 5 + _REPLY_HEAD.size or msg[:5] != b"REPLY":
                continue
            rid, cid, _seq, read_only, error, digest = _REPLY_HEAD.unpack_from(msg, 5)
            if rid == peer_id and not read_only and not error:
                index.setdefault(digest, []).append((pos, rid, cid, msg, sig))
        by_digest.append(index)
    short = 0
    for rec in issued:
        if rec.acked is None:
            continue
        voters = set()
        want = hashlib.sha256(rec.result).digest()
        for pos, rid, cid, msg, sig in by_digest[rec.client].get(want, ()):
            if pos < rec.mark and cid == rec.client and rid not in voters:
                if verifier.valid(rid, msg, sig):
                    voters.add(rid)
        if len(voters) < f + 1:
            short += 1
    return short


def engine_counts(engine, queue: str) -> dict:
    """One engine's counters of ``queue`` (verify and sign side), through
    the engine's public ``stats``/``sign_stats``/``written_off``."""
    v = engine.stats.get(queue)
    s = engine.sign_stats.get(queue)
    return {
        "verify_items": v.items if v else 0,
        "verify_batches": v.batches if v else 0,
        "verify_padded": v.padded_lanes if v else 0,
        "verify_prep_s": v.host_prep_time_s if v else 0.0,
        "verify_timeouts": v.dispatch_timeouts if v else 0,
        "verify_wait_buckets": list(v.queue_wait.buckets) if v else [],
        "sign_items": s.items if s else 0,
        "sign_batches": s.batches if s else 0,
        "sign_padded": s.padded_lanes if s else 0,
        "sign_prep_s": s.host_prep_time_s if s else 0.0,
        "sign_timeouts": s.dispatch_timeouts if s else 0,
        "sign_fallback": s.host_fallback_items if s else 0,
        "written_off": len(engine.written_off()),
    }


def counts_delta(after: dict, before: dict) -> dict:
    out = {}
    for k, v in after.items():
        if isinstance(v, list):
            b = before.get(k) or [0] * len(v)
            out[k] = [x - y for x, y in zip(v, b)]
        else:
            out[k] = v - before.get(k, 0)
    return out


def device_path_faults(deltas: Sequence[dict], after: Sequence[dict]) -> int:
    """Engines that did no device work in the window, plus every dispatch
    timeout, host-fallback item and written-off queue the process has seen."""
    faults = 0
    for d, now in zip(deltas, after):
        faults += d["verify_items"] <= 0
        faults += d["sign_items"] <= 0
        faults += now["verify_timeouts"] + now["sign_timeouts"]
        faults += now["sign_fallback"] + now["written_off"]
    return faults


async def converged(ledgers, timeout: float = 60.0) -> None:
    """Wait until no ledger is behind the longest (late is late, not wrong)."""
    deadline = time.monotonic() + timeout
    stable = 0
    while time.monotonic() < deadline and stable < 3:
        lengths = {lg.length for lg in ledgers}
        stable = stable + 1 if len(lengths) == 1 else 0
        await asyncio.sleep(0.05)


def ledger_payloads(ledger) -> List[bytes]:
    return [ledger.block(h).payload for h in range(1, ledger.length + 1)]


def compare(
    system,
    issued: Sequence,
    forged: set,
    deltas: Sequence[dict],
    after: Sequence[dict],
) -> Dict[str, int]:
    """-> {number compared: value}; ``correct`` is every value at its limit
    in :data:`LIMITS`.  ``forged`` holds this window's forged payloads;
    those of earlier windows (only a control process has any) are the
    verdict of their own window."""
    config = system.config
    chains = [ledger_payloads(lg) for lg in system.cluster.ledgers]
    order = max(chains, key=len)
    head, results = replay(order)
    off = sum(
        chain != order or lg.state_digest() != head
        for chain, lg in zip(chains, system.cluster.ledgers)
    )
    executed = set()
    for chain in chains:
        executed.update(chain)
    verifier = ReplyVerifier(config["scheme"], system.store.replica_pubs())
    acked = [r for r in issued if r.acked is not None]
    return {
        "never_answered": len(issued) - len(acked),
        "wrong_results": sum(results.get(r.op) != r.result for r in acked),
        "ledgers_off_reference": off,
        "executed_twice": len(order) - len(set(order)),
        "forged_executed": len(executed & forged),
        "unrequested_executed": len(
            executed - system.requested - forged - system.forged_sent),
        "acks_short_of_quorum": quorum_shortfalls(
            issued, [r.accepted for r in system.recorders], verifier, config["f"]
        ),
        "device_path_faults": device_path_faults(deltas, after),
        "view_changes": sum(int(r.metrics.current_view) for r in system.cluster.replicas),
    }


def verdict(numbers: Dict[str, int]) -> bool:
    return all(numbers[k] <= LIMITS[k] for k in LIMITS)


def compared_lines(numbers: Dict[str, int]) -> Dict[str, dict]:
    return {k: {"value": numbers[k], "limit": LIMITS[k]} for k in LIMITS}
