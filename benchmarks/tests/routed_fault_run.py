"""Support for ``tests/test_routed_cell.py``: break the routed mesh path
underneath the harness, then drive a whole rehearsal run of the routed cell.
``python routed_fault_run.py <fault> <run.py arguments>``.

Each fault is planted in the program's own classes; ``correct`` has to come
out false for every one of them.
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]


def one_device():
    """Every resident array of the routed graph is handed to the device as
    a plain array, which lands whole on the first chip: the waves still
    answer right (each call deals the arguments out again), and only the
    layout says so."""
    import jax.numpy as jnp

    from stl_fusion_tpu.parallel.routed_wave import RoutedShardedGraph

    RoutedShardedGraph._put = lambda self, a, sharding: jnp.asarray(a)


def dropped_cross_edges():
    """The shards lose the in-edges of one node whose sources all live on
    other members: the frontier never crosses over to it."""
    import numpy as np

    from stl_fusion_tpu.parallel import routed_wave

    init = routed_wave.RoutedShardedGraph.__init__

    def broken(self, edges_src, edges_dst, n_nodes, placement, *args, **kwargs):
        src, dst = np.asarray(edges_src), np.asarray(edges_dst)
        perm, _inv = placement.permutation()
        dev = perm // placement.n_local
        local_in = np.bincount(dst[dev[src] == dev[dst]], minlength=n_nodes)
        has_in = np.bincount(dst, minlength=n_nodes) > 0
        # past the seeds' id range, so that only its in-edges reach it
        victims = np.flatnonzero(has_in & (local_in == 0))
        victim = int(victims[victims >= n_nodes // 2][0])
        keep = dst != victim
        if kwargs.get("edge_dst_epoch") is not None:
            kwargs["edge_dst_epoch"] = np.asarray(kwargs["edge_dst_epoch"])[keep]
        init(self, src[keep], dst[keep], n_nodes, placement, *args, **kwargs)

    routed_wave.RoutedShardedGraph.__init__ = broken


def one_level_early():
    """The level loop hands back the state it had before its last level
    that lit anything: the wave stops one level early."""
    from jax import lax

    from stl_fusion_tpu.parallel import routed_wave

    class Lax:
        def __getattr__(self, name):
            return getattr(lax, name)

        @staticmethod
        def while_loop(cond, body, init):
            def lagging(carry):
                _older, old, new = carry
                return old, new, body(new)

            older, _old, _new = lax.while_loop(
                lambda carry: cond(carry[2]), lagging, (init, init, init)
            )
            return older

    routed_wave.lax = Lax()


FAULTS = {f.__name__: f for f in (one_device, dropped_cross_edges, one_level_early)}

if __name__ == "__main__":
    os.environ["JAX_PLATFORMS"] = "cpu"
    FAULTS[sys.argv[1]]()
    import run

    sys.exit(run.main(sys.argv[2:]))
