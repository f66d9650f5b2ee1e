"""Support for ``tests/test_hellocart_cell.py``: break the hot-table path
underneath the harness, then drive a whole rehearsal run of the HelloCart
cell. ``python cart_fault_run.py <fault> <run.py arguments>``.

Each fault is planted in the program's own classes; ``correct`` has to come
out false for every one of them (``no_declaration``: the run exits 3).
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]

NTH = 9  # the command the fault hits: past the warm-up, inside the window


def refresh_skipped():
    """The pipeline applies its waves and never refreshes the hot tables."""
    from stl_fusion_tpu.graph import TpuGraphBackend

    TpuGraphBackend.refresh_hot = lambda self, newly: 0


def wrong_block_order():
    """The hot blocks are refreshed derived rows first: a total is computed
    from the price row as it was before the edit."""
    from stl_fusion_tpu.graph import TpuGraphBackend

    ordered = TpuGraphBackend._ordered_hot_blocks
    TpuGraphBackend._ordered_hot_blocks = (
        lambda self, block_edges: ordered(self, block_edges)[::-1]
    )


def stale_total_served():
    """From the NTH recompute of a total on, every fourth one serves the
    value that key had one recompute earlier."""
    from stl_fusion_tpu.core.function import ComputeMethodFunction

    produce, last, seen = ComputeMethodFunction.produce_value, {}, {"n": 0}

    async def broken(self, input, computed):
        value = await produce(self, input, computed)
        if self.method_def.name.endswith(".total"):
            seen["n"] += 1
            stale = last.get(input.args, value)
            last[input.args] = value
            if seen["n"] >= NTH and seen["n"] % 4 == 0:
                return stale
        return value

    ComputeMethodFunction.produce_value = broken


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


def no_declaration():
    """A program whose ``TableBacking`` has no ``hot`` declaration (the
    parent of PR 38): the deployment has to exit 3 at once."""
    from stl_fusion_tpu.core import TableBacking

    TableBacking.__slots__ = tuple(s for s in TableBacking.__slots__ if s != "hot")


FAULTS = {f.__name__: f for f in (
    refresh_skipped, wrong_block_order, stale_total_served, lost_write,
    no_declaration,
)}

if __name__ == "__main__":
    os.environ["JAX_PLATFORMS"] = "cpu"
    FAULTS[sys.argv[1]]()
    import run

    sys.exit(run.main(sys.argv[2:]))
