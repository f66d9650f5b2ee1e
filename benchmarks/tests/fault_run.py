"""Support for ``test_faults.py``: break the timed path underneath the
harness, then drive a whole rehearsal run (the look for a chip is the only
step a rehearsal skips). ``python fault_run.py <fault> <run.py arguments>``.

Each fault is planted in the program's own classes, where the answer is
produced; ``correct`` has to come out false for every one of them.
"""
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]


def burst_answer_altered():
    """One lane count of every harvested round is off by one."""
    from stl_fusion_tpu.graph.superround import SuperRoundTicket

    harvest = SuperRoundTicket.harvest

    def broken(self):
        out = [np.array(c, copy=True) for c in harvest(self)]
        for c in out[:1]:
            c += 1
        return out

    SuperRoundTicket.harvest = broken


def burst_half_batch():
    """The second half of the lane groups is left out of every round."""
    from stl_fusion_tpu.graph.superround import SuperRoundTicket

    harvest = SuperRoundTicket.harvest

    def broken(self):
        out = [np.array(c, copy=True) for c in harvest(self)]
        for c in out:
            c[len(c) // 2:] = 0
        return out

    SuperRoundTicket.harvest = broken


def burst_state_unchanged():
    """The step returns its state unchanged: nothing was invalidated."""
    from stl_fusion_tpu.graph.superround import SuperRoundTicket

    harvest = SuperRoundTicket.harvest
    SuperRoundTicket.harvest = lambda self: [np.zeros_like(c) for c in harvest(self)]


def lone_answer_altered():
    from stl_fusion_tpu.graph import TpuGraphBackend

    cascade = TpuGraphBackend.cascade_rows_batch
    TpuGraphBackend.cascade_rows_batch = (
        lambda self, block, rows, *a, **k: cascade(self, block, rows, *a, **k) + 1
    )


def lone_state_unchanged():
    """The edit returns without cascading: the state is as it was."""
    from stl_fusion_tpu.graph import TpuGraphBackend

    calls = {"n": 0}
    cascade = TpuGraphBackend.cascade_rows_batch

    def broken(self, block, rows, *a, **k):
        calls["n"] += 1
        if calls["n"] <= 2:  # the two warm waves compile the programs
            return cascade(self, block, rows, *a, **k)
        return 0

    TpuGraphBackend.cascade_rows_batch = broken


FAULTS = {f.__name__: f for f in (
    burst_answer_altered, burst_half_batch, burst_state_unchanged,
    lone_answer_altered, lone_state_unchanged,
)}

if __name__ == "__main__":
    os.environ["JAX_PLATFORMS"] = "cpu"
    FAULTS[sys.argv[1]]()
    import run

    sys.exit(run.main(sys.argv[2:]))
