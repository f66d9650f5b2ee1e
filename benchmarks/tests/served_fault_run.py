"""Support for ``test_served.py``: break the served write path underneath
the harness, then drive a whole rehearsal run of the served cell.
``python served_fault_run.py <fault> <run.py arguments>``.

Each fault is planted in the program's own classes; ``correct`` has to come
out false for every one of them.
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]

NTH = 9  # the command the fault hits: past the warm-up, inside the window


def subscription_never_fires():
    """One peer's invalidation frames lose their first key, every time."""
    from stl_fusion_tpu.rpc.outbox import PeerOutbox

    post = PeerOutbox.post_invalidations

    def broken(self, entries):
        entries = list(entries)
        if self.peer.ref.endswith("c0"):
            entries = entries[1:]
        return post(self, entries)

    PeerOutbox.post_invalidations = broken


def unjournaled_ack():
    """The op-log drops every fifth record; the command is acknowledged
    all the same."""
    from stl_fusion_tpu.oplog import InMemoryOperationLog

    append, seen = InMemoryOperationLog.append, {"n": 0}

    def broken(self, record):
        seen["n"] += 1
        if seen["n"] % 5 == 0:
            return record
        return append(self, record)

    InMemoryOperationLog.append = broken


def lost_write():
    """One command is taken for a replay of an applied operation: it is
    acknowledged and never applied."""
    from stl_fusion_tpu.commands import ClusterCommander

    execute, seen = ClusterCommander.execute_local, {"n": 0}

    async def broken(self, command, operation_id):
        seen["n"] += 1
        if seen["n"] == NTH:
            self._memo.try_add(operation_id, (None,))
        return await execute(self, command, operation_id)

    ClusterCommander.execute_local = broken


def doubled_write():
    """One command's handler chain runs a second time, past the dedup."""
    from stl_fusion_tpu.commands import ClusterCommander

    execute, seen = ClusterCommander.execute_local, {"n": 0}

    async def broken(self, command, operation_id):
        seen["n"] += 1
        result = await execute(self, command, operation_id)
        if seen["n"] == NTH:
            await self.commander.call(command)
        return result

    ClusterCommander.execute_local = broken


FAULTS = {f.__name__: f for f in (
    subscription_never_fires, unjournaled_ack, lost_write, doubled_write,
)}

if __name__ == "__main__":
    os.environ["JAX_PLATFORMS"] = "cpu"
    FAULTS[sys.argv[1]]()
    import run

    sys.exit(run.main(sys.argv[2:]))
