"""Shared bit-pack primitives for the byte-thin transfer paths.

One definition of the little-endian bool→uint32 pack that burst epilogues,
overflow readbacks, and table validity bits all use (three modules had
drifted their own copies of it); the host-side twin lives in
graph/device_graph.py::_pack_mask_host next to its unpack kernel.
"""
from __future__ import annotations

import functools

__all__ = [
    "fused_pair_scatter",
    "fused_quad_scatter",
    "pack_bool_bits",
    "pack_bool_bits_jit",
]


def pack_bool_bits(mask):
    """bool[n] → uint32[ceil(n/32)] little-endian pack (traceable — use
    inside larger jitted programs; ships 1 bit/node over PCIe instead
    of 1 byte)."""
    import jax.numpy as jnp

    n = mask.shape[0]
    pad = (-n) % 32
    m = jnp.pad(mask, (0, pad)).reshape(-1, 32).astype(jnp.uint32)
    return (m << jnp.arange(32, dtype=jnp.uint32)).sum(axis=1, dtype=jnp.uint32)


@functools.lru_cache(maxsize=1)
def pack_bool_bits_jit():
    """Standalone jitted pack for eager callers."""
    import jax

    return jax.jit(pack_bool_bits)


@functools.lru_cache(maxsize=1)
def fused_pair_scatter():
    """One jitted row scatter updating a mirror's paired tables (ids +
    epochs) IN PLACE: the two tables are donated, so the program writes the
    changed rows into the buffers it was given and the old handles are
    deleted (any later use of one raises). The caller rebinds its handles to
    the outputs at once and holds the tables nowhere else. Undonated, XLA
    copies both whole tables to change a 1,024-row bucket (0.7 GB as laid
    out at 11 M rows). The row indices and values are not donated. One
    program (and compile) for the pair, cached per (table shapes x width
    bucket) by jit itself. Shared by the single-chip topo/lat mirrors and
    the packed mesh mirror."""
    import jax

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def scat(t1, t2, rows, v1, v2):
        return t1.at[rows].set(v1), t2.at[rows].set(v2)

    return scat


@functools.lru_cache(maxsize=1)
def fused_quad_scatter():
    """One jitted row scatter updating TWO paired-table mirrors at once
    (topo in-rows + lat out-rows of a patch application), all four tables
    donated and patched in place as in :func:`fused_pair_scatter` (a copy
    of the four is 1.4 GB, 7.8 ms of device time, at 11 M rows: PERF.md §6,
    PR 29). The row batches are independent scatters; fusing them saves one
    dispatch."""
    import jax

    @functools.partial(jax.jit, donate_argnums=(0, 1, 5, 6))
    def scat(a1, a2, rows_a, va1, va2, b1, b2, rows_b, vb1, vb2):
        return (
            a1.at[rows_a].set(va1),
            a2.at[rows_a].set(va2),
            b1.at[rows_b].set(vb1),
            b2.at[rows_b].set(vb2),
        )

    return scat
