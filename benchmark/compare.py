"""The comparison that decides ``correct``, and its plain reference.

The reference is the service's semantics written down plainly, with nothing
of the program in it: a hash chain replayed serially over the committed
order (``sha256(height || previous digest || payload)`` per block, the
digest being the write's result), and OpenSSL's verdict on the signature of
every reply that the clients accepted, by the file of the configuration's
scheme (``benchmark/verifiers/<scheme>.py``).  Every number compared is a
count of breaches of a guarantee that the configuration states, so every
limit is 0.
"""

from __future__ import annotations

import asyncio
import hashlib
import itertools
import struct
import time
from typing import Callable, Dict, Iterable, List, Sequence, Tuple

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


def quorum_shortfalls(issued: Iterable, accepted_by_client: Sequence[Sequence[tuple]],
                      valid: Callable[[int, bytes, bytes], bool], f: int) -> int:
    """Acknowledged writes with fewer than f+1 replies that the client had
    accepted by the time of the ack, each from a distinct replica, each
    naming this client and this result, each validly signed
    (``valid(replica id, bytes signed, signature)``: the reference's verdict)."""
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
                if valid(rid, msg, sig):
                    voters.add(rid)
        if len(voters) < f + 1:
            short += 1
    return short


# key here -> attribute of the engine's VerifyStats / SignStats
_COUNTED = {"items": "items", "batches": "batches", "padded": "padded_lanes",
            "prep_s": "host_prep_time_s", "timeouts": "dispatch_timeouts"}


def engine_counts(engine, queues: Sequence[str]) -> dict:
    """One engine's counters, summed over its device ``queues`` (the keys
    of the engine's public ``stats`` / ``sign_stats``; histogram buckets
    added bucket by bucket), and under ``items`` and ``batches`` those of
    each side ``(queue, "verify" | "sign")`` by itself."""
    out: dict = {f"{kind}_{key}": 0 for kind in ("verify", "sign") for key in _COUNTED}
    out.update(verify_wait_buckets=[], sign_fallback=0,
               written_off=len(engine.written_off()), items={}, batches={})
    for queue in queues:
        for kind, stats in (("verify", engine.stats.get(queue)),
                            ("sign", engine.sign_stats.get(queue))):
            out["items"][queue, kind] = out["batches"][queue, kind] = 0
            if stats is None:  # a side the engine has not been asked for
                continue
            for key, attribute in _COUNTED.items():
                out[f"{kind}_{key}"] += getattr(stats, attribute)
            out["items"][queue, kind] = stats.items
            out["batches"][queue, kind] = stats.batches
            if kind == "sign":
                out["sign_fallback"] += stats.host_fallback_items
            else:
                out["verify_wait_buckets"] = [a + b for a, b in itertools.zip_longest(
                    out["verify_wait_buckets"], stats.queue_wait.buckets, fillvalue=0)]
    return out


def counts_delta(after: dict, before: dict) -> dict:
    out = {}
    for k, v in after.items():
        if isinstance(v, list):
            b = before.get(k) or [0] * len(v)
            out[k] = [x - y for x, y in zip(v, b)]
        elif isinstance(v, dict):
            out[k] = {side: n - before.get(k, {}).get(side, 0) for side, n in v.items()}
        else:
            out[k] = v - before.get(k, 0)
    return out


def device_path_faults(deltas: Sequence[dict], after: Sequence[dict],
                       sides: Iterable[Tuple[str, str]]) -> int:
    """Engines that did no device work in the window on a side ``(queue,
    kind)`` for which the configuration names a kernel, plus every dispatch
    timeout, host-fallback item and written-off queue the process has seen."""
    sides = set(sides)
    faults = 0
    for d, now in zip(deltas, after):
        faults += sum(d["items"][side] <= 0 for side in sides)
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
    valid = system.verifier.make(system.store.replica_pubs())
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
            issued, [r.accepted for r in system.recorders], valid, config["f"]
        ),
        "device_path_faults": device_path_faults(
            deltas, after, ((m.QUEUE, m.KIND) for m in system.kernels.values())),
        "view_changes": sum(int(r.metrics.current_view) for r in system.cluster.replicas),
    }


def verdict(numbers: Dict[str, int]) -> bool:
    return all(numbers[k] <= LIMITS[k] for k in LIMITS)


def compared_lines(numbers: Dict[str, int]) -> Dict[str, dict]:
    return {k: {"value": numbers[k], "limit": LIMITS[k]} for k in LIMITS}
