"""Multi-device engine pool: one batching engine per home chip.

PR 8 proved the coalescing claim on ONE device: G consensus groups
sharing a single :class:`~minbft_tpu.parallel.engine.BatchVerifier`
raise verify batch fill with G (2.0 → 32.0 across G=1..16) because every
group's authenticator lands checks in the same scheme queues.  The chip
ceiling, though, is per *device* — ~164k ECDSA verifies/s on one chip
while the other seven idle (ROADMAP item 1, the MULTICHIP dryruns).

:class:`EnginePool` replicates the PR-8 win **per chip** instead of
diluting it globally:

- one :class:`BatchVerifier` per home chip — its own verify/sign
  queues, staging pool, and dedup memo, pinned to its device
  (``BatchVerifier(device=...)``);
- a **placement policy** mapping each consensus group to exactly one
  home chip (static round-robin ``group % chips``), so all groups homed
  on a chip keep coalescing into that chip's queues exactly as PR 8
  measured — cross-chip traffic never splits a batch;
- a **rebalance hook** fed by the PR-9 ledger's per-chip
  ``busy × fill`` score: :meth:`rebalance` migrates groups off the
  hottest chip, but NEVER a group with in-flight dispatches (a migrated
  group's outstanding futures must all resolve on the engine that owns
  their memo/staging state);
- a **striping path** for oversized explicit batches: a ``verify_*_many``
  call larger than ``stripe_threshold`` routes through a mesh-routed
  engine (the existing ``mesh.sharded_*`` kernels partition the batch
  axis over all chips), because a batch that already fills several
  chips' buckets gains nothing from home-chip affinity.

Degenerate honesty: ``chips=1`` (or one visible device) builds exactly
ONE unpinned ``BatchVerifier`` and every facade call forwards to it —
the C=1 pool is byte-identical to the pre-pool engine (results, stats
accounting, flush decisions), which the differential fuzz in
tests/test_pool.py pins.

Concurrency: the placement map, per-group in-flight counters, and the
facade cache are event-loop confined (every mutation is a sync method or
a loop-atomic update around an await — LD-spec'd in
tools/analyze/project.py).  Scrape threads only read (GIL-atomic), the
same contract as the engine stats.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from .engine import BatchVerifier


class _GroupEngine:
    """One group's BatchVerifier-compatible facade over the pool.

    Forwards the engine's public verify/sign surface to the group's
    CURRENT home-chip engine (placement is read per call, so a rebalance
    takes effect on the next submission), counting in-flight calls per
    group — the witness :meth:`EnginePool.rebalance` consults before
    migrating.  Attribute reads (``stats``, ``queue_depths``, ...) fall
    through to the home engine, so existing engine-shaped consumers keep
    working unchanged.
    """

    __slots__ = ("_pool", "group")

    def __init__(self, pool: "EnginePool", group: int):
        self._pool = pool
        self.group = int(group)

    @property
    def home(self) -> BatchVerifier:
        return self._pool._engines[self._pool.home_chip(self.group)]

    async def _call(self, name: str, *args):
        pool = self._pool
        g = self.group
        eng = pool._engines[pool.home_chip(g)]
        # Loop-atomic bump (sync before the await, decrement after):
        # rebalance reads this between awaits on the same loop, so a
        # group is only ever migrated with zero outstanding futures.
        pool._inflight[g] = pool._inflight.get(g, 0) + 1
        try:
            return await getattr(eng, name)(*args)
        finally:
            pool._inflight[g] -= 1

    async def _call_many(self, name: str, items):
        pool = self._pool
        g = self.group
        eng = pool._route_many(g, len(items))
        pool._inflight[g] = pool._inflight.get(g, 0) + 1
        try:
            return await getattr(eng, name)(items)
        finally:
            pool._inflight[g] -= 1

    # -- verify surface (mirrors BatchVerifier's public API) ---------------

    def verify_ecdsa_p256(self, pubkey, digest, sig):
        return self._call("verify_ecdsa_p256", pubkey, digest, sig)

    def verify_ecdsa_p256_host(self, pubkey, digest, sig):
        return self._call("verify_ecdsa_p256_host", pubkey, digest, sig)

    def verify_hmac_sha256(self, key, msg32, mac):
        return self._call("verify_hmac_sha256", key, msg32, mac)

    def verify_hmac_sha256_host(self, key, msg32, mac):
        return self._call("verify_hmac_sha256_host", key, msg32, mac)

    def verify_ed25519(self, pub, msg, sig):
        return self._call("verify_ed25519", pub, msg, sig)

    def verify_ed25519_host(self, pub, msg, sig):
        return self._call("verify_ed25519_host", pub, msg, sig)

    def verify_nist_host(self, curve, pub, msg, sig):
        return self._call("verify_nist_host", curve, pub, msg, sig)

    # Device _many entry points may stripe (oversized batches span the
    # mesh); the host _many variants never do — host queues have no
    # device to stripe over, and splitting their dedup memo would only
    # re-verify items the home chip already knows.

    def verify_ecdsa_p256_many(self, items):
        return self._call_many("verify_ecdsa_p256_many", items)

    def verify_ecdsa_p256_host_many(self, items):
        return self._call("verify_ecdsa_p256_host_many", items)

    def verify_ed25519_many(self, items):
        return self._call_many("verify_ed25519_many", items)

    def verify_ed25519_host_many(self, items):
        return self._call("verify_ed25519_host_many", items)

    # -- sign surface -------------------------------------------------------

    def sign_ecdsa_p256(self, d, digest):
        return self._call("sign_ecdsa_p256", d, digest)

    def sign_ed25519(self, seed, msg):
        return self._call("sign_ed25519", seed, msg)

    def __getattr__(self, name):
        # stats / queue_depths / buckets / ... — read-side
        # passthrough to the current home engine.
        return getattr(self._pool._engines[self._pool.home_chip(self.group)],
                       name)


class EnginePool:
    """One :class:`BatchVerifier` per home chip, with group placement.

    ``chips`` requests the pool width; it clamps to the number of
    visible jax devices (``requested_chips`` keeps the ask).  With one
    chip the pool never touches jax at construction and owns exactly one
    unpinned engine — the degenerate path this CPU container runs.

    ``stripe_threshold`` (default: the engines' ``max_batch``) sets the
    explicit-batch size above which ``verify_*_many`` routes through the
    mesh-striped engine instead of the home chip; ``None``/a 1-chip pool
    disables striping.  All remaining keyword arguments construct each
    per-chip :class:`BatchVerifier` identically.
    """

    def __init__(
        self,
        chips: int = 1,
        *,
        devices: Optional[list] = None,
        stripe_threshold: Optional[int] = None,
        **engine_kwargs,
    ):
        if chips < 1:
            raise ValueError(f"chips must be >= 1, got {chips}")
        if "mesh" in engine_kwargs or "device" in engine_kwargs:
            raise ValueError(
                "the pool owns device/mesh placement; pass chips=/devices="
            )
        self.requested_chips = int(chips)
        if chips > 1 and devices is None:
            import jax

            devices = list(jax.devices())
        if devices is not None and chips > len(devices):
            # Honest degeneracy (the CPU container): fewer devices than
            # asked → a narrower pool, never an oversubscribed one.
            chips = max(len(devices), 1)
        self.chips = int(chips)
        self._devices = list(devices[:chips]) if devices is not None else None
        self._engine_kwargs = dict(engine_kwargs)
        if chips == 1:
            engines = [BatchVerifier(**engine_kwargs)]
        else:
            engines = [
                BatchVerifier(device=self._devices[c], **engine_kwargs)
                for c in range(chips)
            ]
        self._engines: Tuple[BatchVerifier, ...] = tuple(engines)
        # Striped engine: mesh over the pool's chips for oversized
        # explicit batches.  Only built for a real multi-chip pool (a
        # 1-device mesh degenerates inside BatchVerifier anyway).
        self._striped: Optional[BatchVerifier] = None
        self.stripe_threshold: Optional[int] = None
        if self.chips > 1:
            from . import mesh as mesh_mod

            self._striped = BatchVerifier(
                mesh=mesh_mod.make_mesh(self._devices), **engine_kwargs
            )
            self.stripe_threshold = (
                int(stripe_threshold)
                if stripe_threshold is not None
                else int(self._engines[0].max_batch)
            )
        # group -> home chip; facade cache; per-group in-flight counters.
        # All loop-confined (see module docstring).
        self._placement: Dict[int, int] = {}
        self._facades: Dict[int, _GroupEngine] = {}
        self._inflight: Dict[int, int] = {}
        # Rolling per-chip utilization windows (chip_utilization):
        # DeviceLedger baselines captured at the previous call.
        self._util_ledgers: Optional[list] = None
        # Ceilings re-applied to every rolling window (set_ceiling).
        self._ceilings: Dict[str, Tuple[float, str]] = {}

    # -- placement -----------------------------------------------------------

    @property
    def engines(self) -> Tuple[BatchVerifier, ...]:
        return self._engines

    @property
    def striped_engine(self) -> Optional[BatchVerifier]:
        return self._striped

    def home_chip(self, group: int) -> int:
        """The group's home chip, assigning static round-robin
        (``group % chips``) on first touch.  Every group maps to exactly
        one chip — the placement invariant tests pin."""
        chip = self._placement.get(group)
        if chip is None:
            chip = group % self.chips
            self._placement[group] = chip
        return chip

    def engine_for(self, group: int) -> _GroupEngine:
        """The group's engine facade (cached — one identity per group)."""
        fac = self._facades.get(group)
        if fac is None:
            self.home_chip(group)  # place eagerly
            fac = _GroupEngine(self, group)
            self._facades[group] = fac
        return fac

    def placement(self) -> Dict[int, int]:
        return dict(self._placement)

    def groups_on(self, chip: int) -> List[int]:
        return sorted(g for g, c in self._placement.items() if c == chip)

    def group_inflight(self, group: int) -> int:
        return self._inflight.get(group, 0)

    def _route_many(self, group: int, n_items: int) -> BatchVerifier:
        if (
            self._striped is not None
            and self.stripe_threshold is not None
            and n_items > self.stripe_threshold
        ):
            return self._striped
        return self._engines[self.home_chip(group)]

    def rebalance(
        self,
        scores: Optional[List[float]] = None,
        min_gap: float = 0.25,
    ) -> Dict[int, Tuple[int, int]]:
        """Migrate groups off the hottest chip when the per-chip
        ``busy × fill`` scores diverge.

        ``scores[c]`` is chip ``c``'s load score (higher = busier) — the
        PR-9 ledger product; defaults to :meth:`chip_scores`.  When the
        hottest chip exceeds the coolest by more than ``min_gap``
        (absolute score gap), ONE group homed on the hottest chip moves
        to the coolest.  A group with in-flight dispatches is never
        migrated: its outstanding futures resolve on the engine whose
        memo/staging own them, so migration under load would split a
        group's verification state across chips mid-await.  Returns
        ``{group: (old_chip, new_chip)}`` (empty when balanced).
        """
        if self.chips < 2:
            return {}
        if scores is None:
            scores = self.chip_scores()
        if len(scores) != self.chips:
            raise ValueError(
                f"{len(scores)} scores for a {self.chips}-chip pool"
            )
        hot = max(range(self.chips), key=lambda c: scores[c])
        cool = min(range(self.chips), key=lambda c: scores[c])
        if hot == cool or scores[hot] - scores[cool] <= min_gap:
            return {}
        movable = [
            g for g in self.groups_on(hot) if self._inflight.get(g, 0) == 0
        ]
        if not movable:
            return {}
        # Deterministic choice: the highest-numbered idle group moves
        # (later groups are the round-robin overflow that made the chip
        # hot in the first place).
        g = movable[-1]
        self._placement[g] = cool
        return {g: (hot, cool)}

    # -- utilization (the busy × fill feed) ----------------------------------

    def set_ceiling(self, queue: str, lanes_per_sec: float, source: str) -> None:
        """Calibrated per-chip full-batch lane rate for ``queue`` with
        provenance, applied to every rolling utilization window (and
        re-applied after each window reset)."""
        if lanes_per_sec <= 0:
            raise ValueError("ceiling must be positive")
        self._ceilings[queue] = (float(lanes_per_sec), source)

    def _fresh_ledgers(self, now=None) -> list:
        from ..obs.ledger import DeviceLedger

        leds = [DeviceLedger(e, now=now) for e in self._engines]
        for led in leds:
            for q, (rate, source) in self._ceilings.items():
                led.set_ceiling(q, rate, source)
        return leds

    def chip_utilization(self, now=None) -> List[dict]:
        """Per-chip rows over the window since the previous call: busy
        fraction, fill efficiency (lane-weighted across that chip's
        active queues; 1.0 under a self ceiling), the ``busy × fill``
        placement score, current total queue depth, and the groups homed
        there.  The first call establishes baselines and reads all-idle
        rows — by design (there was no window yet)."""
        prev = self._util_ledgers
        self._util_ledgers = self._fresh_ledgers(now=now)
        rows: List[dict] = []
        for c, eng in enumerate(self._engines):
            busy = 0.0
            fill = 1.0
            if prev is not None:
                wins = prev[c].snapshot(now=now)
                if wins:
                    wall = max(w.wall_s for w in wins.values())
                    busy = min(
                        sum(w.busy_s for w in wins.values()) / max(wall, 1e-9),
                        1.0,
                    )
                    lanes = sum(w.dispatched_lanes for w in wins.values())
                    if lanes > 0:
                        fill = sum(
                            prev[c].decompose(w).fill_efficiency
                            * w.dispatched_lanes
                            for w in wins.values()
                        ) / lanes
            depth = sum(eng.queue_depths().values()) + sum(
                eng.sign_queue_depths().values()
            )
            rows.append(
                {
                    "chip": c,
                    "device": (
                        str(self._devices[c])
                        if self._devices is not None
                        else "default"
                    ),
                    "busy": round(busy, 4),
                    "fill": round(fill, 4),
                    "score": round(busy * fill, 4),
                    "depth": depth,
                    "groups": self.groups_on(c),
                }
            )
        return rows

    def chip_up(self, chip: int) -> bool:
        """False when EVERY instantiated queue on the chip's engine has
        written its device off (the hung-dispatch liveness net demoted
        them all to host fallback) — the ``peer top`` DOWN row.  A chip
        with no queues yet is up (nothing has disproved it)."""
        eng = self._engines[chip]
        qs = list(dict(eng._queues).values()) + list(
            dict(eng._sign_queues).values()
        )
        if not qs:
            return True
        return any(not q._device_written_off for q in qs)

    def chip_scores(self, now=None) -> List[float]:
        """The per-chip ``busy × fill`` placement scores (PR-9 product)
        over the window since the last :meth:`chip_utilization` call."""
        return [row["score"] for row in self.chip_utilization(now=now)]

    # -- merged read-side surfaces (prom / timeseries compatibility) ---------
    #
    # Shaped exactly like one BatchVerifier's maps so existing consumers
    # (register_engine_series, _collect_engine) take a pool unchanged.
    # A 1-chip pool uses the bare queue names (indistinguishable from
    # the single engine); a multi-chip pool prefixes "c{chip}:" for
    # per-chip attribution, with the striped engine's traffic under
    # "stripe:".

    def _merged(self, getter) -> Dict[str, object]:
        if self.chips == 1 and self._striped is None:
            return getter(self._engines[0])
        out: Dict[str, object] = {}
        for c, eng in enumerate(self._engines):
            for name, v in getter(eng).items():
                out[f"c{c}:{name}"] = v
        if self._striped is not None:
            for name, v in getter(self._striped).items():
                out[f"stripe:{name}"] = v
        return out

    @property
    def stats(self) -> Dict[str, object]:
        return self._merged(lambda e: e.stats)

    @property
    def sign_stats(self) -> Dict[str, object]:
        return self._merged(lambda e: e.sign_stats)

    def queue_depths(self) -> Dict[str, int]:
        return self._merged(lambda e: e.queue_depths())

    def sign_queue_depths(self) -> Dict[str, int]:
        return self._merged(lambda e: e.sign_queue_depths())

    def queue_depth_peaks(self, reset: bool = True) -> Dict[str, int]:
        return self._merged(lambda e: e.queue_depth_peaks(reset=reset))

    def sign_queue_depth_peaks(self, reset: bool = True) -> Dict[str, int]:
        return self._merged(lambda e: e.sign_queue_depth_peaks(reset=reset))
