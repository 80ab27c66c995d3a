"""A replica's process dies: every stream through its stub ends and no new
one starts, and the replica stops (as ``tests/test_chaos.py`` takes a
primary down).  Its ledger stays as it was at that instant, for the
comparison to read, and its engine is left alone: its queues drain or idle.
Nothing restarts it.
"""


async def apply(system, replica_id: int) -> None:
    cluster = system.cluster
    cluster.stubs[replica_id].crash()
    await cluster.replicas[replica_id].stop()
