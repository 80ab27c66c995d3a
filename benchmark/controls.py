"""The controls: windows over a deliberately broken system, which the
comparison has to fail, beside sound windows, which it has to pass.

    python3 -m benchmark.controls --workload <cell> --seed <first seed> --seconds 5

One process, one set-up: a dozen sound windows on seeds of their own, then
each sabotage on three.  (A cell whose traffic has a fault schedule gets a
cluster of its own for every window: a crashed replica stays down, and the
guarantees cover f of them.)  A benchmark run never comes here.  Each
sabotage breaks one guarantee the configuration states, where a later PR
might be tempted to save the work:

- ``replies_unverified``: the clients take replies on trust;
- ``acks_on_f``: the clients take f matching replies for a quorum;
- ``answer_altered``: every replica's state machine returns an altered result;
- ``state_unchanged``: every replica's state machine returns its state unchanged;
- ``verify_skipped``: every verify kernel of the configuration answers
  "valid" in every lane (``skip()`` of its ``benchmark/kernels/`` file);

and, where replicas go down (:data:`WITH_FAULTS`; neither can be mended, so
each ends its cluster's use):

- ``crashed_diverges``: the ledger of every replica that is or goes down
  gets one block the others never had;
- ``view_unexplained``: the primary goes down behind the schedule's back:
  the running replicas change view and no scheduled fault explains it.

They reach the program through its public surfaces only (the client's
constructor, the ledger's ``deliver``, a kernel's module-level entry, the
fault kinds' own files).
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import itertools
import json
import logging
import sys
import types

from . import compare as cmp
from . import manifest, run, tracing
from . import system as sut
from .generator import target_replica

SABOTAGES = ("replies_unverified", "acks_on_f", "answer_altered",
             "state_unchanged", "verify_skipped")
WITH_FAULTS = ("crashed_diverges", "view_unexplained")


def _crash(system):
    """The crash kind's file, as the system's windows use it."""
    if "crash" not in system.fault_kinds:
        system.fault_kinds["crash"] = manifest.by_name(system.root, "faults", "crash", "fault kind")
    return system.fault_kinds["crash"]


@contextlib.asynccontextmanager
async def sabotaged(system, name: str):
    """The system with one guarantee broken, and mended again on the way out."""
    if name == "sound":
        yield
    elif name == "replies_unverified":
        await sut.attach_clients(system, verify_replies=False)
        try:
            yield
        finally:
            await sut.attach_clients(system)
    elif name == "acks_on_f":
        await sut.attach_clients(system, client_f=system.config["f"] - 1)
        try:
            yield
        finally:
            await sut.attach_clients(system)
    elif name in ("answer_altered", "state_unchanged"):
        ledgers = system.cluster.ledgers
        sound = [lg.deliver for lg in ledgers]

        def broken(ledger, deliver):
            async def altered(operation: bytes) -> bytes:
                digest = await deliver(operation)
                return bytes([digest[0] ^ 1]) + digest[1:]

            async def unchanged(operation: bytes) -> bytes:
                return ledger.state_digest()

            return altered if name == "answer_altered" else unchanged

        for lg, deliver in zip(ledgers, sound):
            lg.deliver = broken(lg, deliver)
        try:
            yield
        finally:
            for lg in ledgers:
                del lg.deliver
    elif name == "verify_skipped":
        with contextlib.ExitStack() as skipped:
            for module in system.kernels.values():
                if module.KIND == "verify":
                    skipped.enter_context(module.skip())
            yield
    elif name == "crashed_diverges":
        async def alone(replica: int) -> None:
            op = b"executed by replica %d alone" % replica
            system.requested.add(op)  # agreement is what it breaks, not who may ask
            await system.cluster.ledgers[replica].deliver(op)

        for replica in cmp.down(system):
            await alone(replica)
        crash = _crash(system)

        async def and_diverge(system, replica: int) -> None:
            await crash.apply(system, replica)
            await alone(replica)

        system.fault_kinds["crash"] = types.SimpleNamespace(apply=and_diverge)
        try:
            yield
        finally:
            system.fault_kinds["crash"] = crash
    elif name == "view_unexplained":
        primary = target_replica(system, "primary")
        system.down_unexplained.append(primary)
        await _crash(system).apply(system, primary)
        yield
    else:
        raise manifest.BenchmarkError(f"no sabotage {name!r}")


async def windows(system, mix, plan, seconds: float, emit) -> list:
    """Run ``plan`` = [(sabotage or "sound", seed)] over one system.  -> the
    lines emitted: step, seed, correct, attempted, the numbers compared."""
    lines = []
    for k, (name, seed) in enumerate(plan):
        async with sabotaged(system, name):
            got = await run.one_window(system, mix, seed, seconds, tag=b"c%d." % k)
            # mend nothing while a replica still executes this window's tail
            await tracing.quiet(system)
        line = {"step": name, "seed": seed, "correct": cmp.verdict(got["numbers"]),
                "attempted": len(got["window"].issued), "numbers": got["numbers"]}
        emit(line)
        lines.append(line)
    return lines


def plan_for(first_seed: int, sound: int = 12, each: int = 3,
             with_faults: bool = False) -> list:
    seeds = itertools.count(first_seed, 7919)
    plan = [("sound", next(seeds)) for _ in range(sound)]
    for name in SABOTAGES + (WITH_FAULTS if with_faults else ()):
        plan += [(name, next(seeds)) for _ in range(each)]
    return plan


async def _main(cell, device, first_seed: int, seconds: float) -> int:
    config, mix = run.sized(cell, device)

    async def on_one_cluster(steps: list) -> list:
        system = await sut.build(cell, config, mix.clients, on_cpu=device["rehearsal"])
        try:
            return await windows(system, mix, steps, seconds,
                                 lambda line: print(json.dumps(line), flush=True))
        finally:
            await system.stop()

    plan = plan_for(first_seed, with_faults=bool(mix.faults))
    if mix.faults:
        lines = [line for step in plan for line in await on_one_cluster([step])]
    else:
        lines = await on_one_cluster(plan)
    unexpected = [ln for ln in lines if ln["correct"] != (ln["step"] == "sound")]
    print(json.dumps({"device": {k: device[k] for k in ("platform", "kind", "count")},
                      "windows": len(lines), "unexpected": unexpected}), flush=True)
    return 1 if unexpected else 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=5.0)
    args = parser.parse_args(argv)
    logging.disable(logging.WARNING)
    try:
        cell = manifest.load_cell(args.workload)
        device = run.start_jax(cell.chips)
        if device["rehearsal"]:
            raise manifest.BenchmarkError(
                "the controls at the cell's own size need the chip; "
                "tests/benchmark/ runs them tiny on the CPU backend"
            )
        return asyncio.run(_main(cell, device, args.seed, args.seconds))
    except manifest.BenchmarkError as e:
        print(f"benchmark controls: {e}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())
