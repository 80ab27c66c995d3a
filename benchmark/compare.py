"""The comparison that decides ``correct``, and its plain reference.

The reference is the service's semantics written down plainly, with nothing
of the program in it: a hash chain replayed serially over the committed
order (``sha256(height || previous digest || payload)`` per block, the
digest being the write's result), and OpenSSL's verdict on the signature of
every reply that the clients accepted, by the file of the configuration's
scheme (``benchmark/verifiers/<scheme>.py``).  Every number compared is a
count of breaches of a guarantee that the configuration states, so every
limit is 0.

A deployment with a failure in it is held to the same guarantees.  The
replicas that the traffic's schedule crashed (``system.faults_applied``, up
to f of them) are down: nothing waits for them, the running replicas agree
among themselves, what a crashed replica executed is a prefix of what they
agreed on, and the running replicas stand in the view that the crashes
explain and no other (:func:`view_explained`).  With nobody down every rule
reads as it did before there were schedules.
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

# The fault kinds (files of benchmark/faults/) that leave a replica down, and
# with that the kinds a window can be judged under: Mix.against refuses others.
CRASHES = frozenset({"crash"})

_REPLY_HEAD = struct.Struct(">IIQBB32s")  # after b"REPLY": messages/authen.py


def crashed_by_schedule(system) -> List[int]:
    """The replicas that the schedules crashed, in the order they went, over
    all of the process's windows."""
    return [a.replica for a in system.faults_applied if a.kind in CRASHES]


def down(system) -> List[int]:
    """The replicas that are down: those a schedule crashed, and then those a
    control took down behind the schedule's back (``system.down_unexplained``)."""
    return list(dict.fromkeys(crashed_by_schedule(system) + list(system.down_unexplained)))


def running(system) -> List[int]:
    gone = set(down(system))
    return [r for r in range(system.config["n"]) if r not in gone]


def view_explained(n: int, crashed: Sequence[int]) -> int:
    """The view a cluster of ``n`` that started in view 0 has to stand in
    once the replicas ``crashed`` went down in that order: a view's primary
    is ``view mod n``, a view whose primary is down is left for the next,
    and nothing else moves it."""
    view, gone = 0, set()
    for replica in crashed:
        gone.add(replica)
        while view % n in gone and len(gone) < n:
            view += 1
    return view


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
                       sides: Iterable[Tuple[str, str]], gone: Iterable[int] = ()) -> int:
    """Engines that did no device work in the window on a side ``(queue,
    kind)`` for which the configuration names a kernel, plus every dispatch
    timeout, host-fallback item and written-off queue the process has seen.
    The engine of a replica that is down (``gone``: positions in the lists)
    is held to the second half alone: it owes the window no work."""
    sides, gone = set(sides), set(gone)
    faults = 0
    for k, (d, now) in enumerate(zip(deltas, after)):
        if k not in gone:
            faults += sum(d["items"][side] <= 0 for side in sides)
        faults += now["verify_timeouts"] + now["sign_timeouts"]
        faults += now["sign_fallback"] + now["written_off"]
    return faults


async def converged(ledgers, timeout: float = 60.0) -> None:
    """Wait until none of ``ledgers`` (the running replicas') is behind the
    longest (late is late, not wrong)."""
    deadline = time.monotonic() + timeout
    stable = 0
    while time.monotonic() < deadline and stable < 3:
        lengths = {lg.length for lg in ledgers}
        stable = stable + 1 if len(lengths) == 1 else 0
        await asyncio.sleep(0.05)


def ledger_payloads(ledger) -> List[bytes]:
    return [ledger.block(h).payload for h in range(1, ledger.length + 1)]


def ledgers_off_reference(chains: Sequence[Sequence[bytes]], digests: Sequence[bytes],
                          gone: Iterable[int] = ()) -> Tuple[int, List[bytes]]:
    """-> (ledgers off the reference, the reference order).  The order is the
    longest chain of a running replica.  A running replica is off it when
    its chain or its state digest differs from the order and its replay; a
    replica that is down (``gone``) when its chain is no prefix of the
    order, or its digest not the replay of that prefix: what it executed
    before it went, it executed in the agreed order."""
    gone = set(gone)
    order = list(max((c for k, c in enumerate(chains) if k not in gone), key=len))
    head = replay(order)[0]
    off = 0
    for k, (chain, digest) in enumerate(zip(chains, digests)):
        if k not in gone:
            off += list(chain) != order or digest != head
        else:
            off += list(chain) != order[:len(chain)] or digest != replay(chain)[0]
    return off, order


def views_unexplained(views: Sequence[int], crashed: Sequence[int],
                      unexplained: Iterable[int] = ()) -> int:
    """The number compared as ``view_changes``.  With nobody down: the views
    of all replicas summed, as before there were schedules.  Else the
    running replicas that stand in another view than the one the schedule's
    crashes explain, ``crashed`` in their order (:func:`view_explained`):
    more view changes than the faults explain are as wrong as fewer.
    ``unexplained`` are down as well and explain nothing (a control's)."""
    gone = set(crashed) | set(unexplained)
    if not gone:
        return sum(views)
    want = view_explained(len(views), crashed)
    return sum(view != want for r, view in enumerate(views) if r not in gone)


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
    gone = down(system)
    chains = [ledger_payloads(lg) for lg in system.cluster.ledgers]
    off, order = ledgers_off_reference(
        chains, [lg.state_digest() for lg in system.cluster.ledgers], gone)
    results = replay(order)[1]
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
            deltas, after, ((m.QUEUE, m.KIND) for m in system.kernels.values()), gone),
        "view_changes": views_unexplained(
            [int(r.metrics.current_view) for r in system.cluster.replicas],
            crashed_by_schedule(system), system.down_unexplained),
    }


def verdict(numbers: Dict[str, int]) -> bool:
    return all(numbers[k] <= LIMITS[k] for k in LIMITS)


def compared_lines(numbers: Dict[str, int]) -> Dict[str, dict]:
    return {k: {"value": numbers[k], "limit": LIMITS[k]} for k in LIMITS}
