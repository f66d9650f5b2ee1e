"""Support for ``tests/test_multihost_cell.py``: break the multi-host write
path underneath the harness, then drive a whole rehearsal run of the
multi-host cell. ``python mh_fault_run.py <fault> <run.py arguments>``.

Each fault is planted in the program's own classes; ``correct`` has to come
out false for every one of them.
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]

NTH = 12  # the command the fault hits: past the warm-up, inside the window


def reader_skips_a_record():
    """One member's reader takes up one external record, advances past it
    and never replays it: that member's watchers never hear of the write."""
    from stl_fusion_tpu.operations.pipeline import OperationsHost

    notify, seen = OperationsHost.notify_completed, {"n": 0}

    async def broken(self, operation, is_local=True):
        if not is_local:
            seen["n"] += 1
            if seen["n"] == 3 * NTH:  # three replicas replay each command
                return False
        return await notify(self, operation, is_local)

    OperationsHost.notify_completed = broken


def all_on_device_0():
    """Every member's backend is built with no device: four graphs on the
    default device. Every answer is still right; only the layout says so."""
    from stl_fusion_tpu.graph import TpuGraphBackend

    init = TpuGraphBackend.__init__

    def broken(self, hub, node_capacity=4096, edge_capacity=16384, device=None):
        init(self, hub, node_capacity, edge_capacity)

    TpuGraphBackend.__init__ = broken


def _misroute_one():
    """The router sends one command to the member next to its key's owner."""
    from stl_fusion_tpu.cluster.router import ShardMapRouter

    route, seen = ShardMapRouter.route, {"n": 0}

    def broken(self, service, method, args):
        peer, headers = route(self, service, method, args)
        if service == "$commander":
            seen["n"] += 1
            if seen["n"] == NTH:
                members = self.shard_map.members
                peer = members[(members.index(peer) + 1) % len(members)]
        return peer, headers

    ShardMapRouter.route = broken


def non_owner_executes():
    """One command is misrouted and no member re-checks ownership: it is
    applied by the wrong member, journaled under the wrong agent, and
    replayed by its rightful owner."""
    from stl_fusion_tpu.commands import ClusterCommander

    _misroute_one()
    ClusterCommander._shard_map = lambda self: None


def misrouted_is_bounced():
    """The same misrouting with the owner-side re-check in place: the member
    bounces the command, the writer retries it, and the retry is counted.
    Nothing is applied twice or by the wrong member."""
    _misroute_one()


FAULTS = {f.__name__: f for f in (
    reader_skips_a_record, all_on_device_0, non_owner_executes, misrouted_is_bounced,
)}

if __name__ == "__main__":
    os.environ["JAX_PLATFORMS"] = "cpu"
    FAULTS[sys.argv[1]]()
    import run

    sys.exit(run.main(sys.argv[2:]))
