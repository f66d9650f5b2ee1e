"""The hand-written Pallas TPU kernel of the invalidation hot path.

The rest of the wave pipeline deliberately stays in XLA — its
gathers/scatters fuse well.

:func:`or_popcount` is the wave FINALIZER: merge a new invalidation bit
vector into the accumulated one and count newly-lit bits, in ONE pass over
the words (XLA materializes ``new & ~old`` as an intermediate before the
reduce unless it fuses; here merge + delta-popcount + scalar accumulation
share a single VMEM-resident tile walk). It compiles through Mosaic by
default; a CPU test asks for the interpreter itself (``interpret=True``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["or_popcount"]

_LANES = 128
_BLOCK_ROWS = 256  # 256x128 int32 = 128 KiB per buffer — 3 buffers well under VMEM


# ---------------------------------------------------------------- or+popcount
def _or_popcount_kernel(new_ref, old_ref, merged_ref, count_ref):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _():
        count_ref[0, 0] = 0

    new = new_ref[...]
    old = old_ref[...]
    merged_ref[...] = new | old
    delta = lax.population_count(new & ~old)
    count_ref[0, 0] += jnp.sum(delta)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _or_popcount_2d(new2d, old2d, interpret: bool):
    rows = new2d.shape[0]
    grid = rows // _BLOCK_ROWS
    block = pl.BlockSpec(
        (_BLOCK_ROWS, _LANES), lambda i: (i, 0), memory_space=pltpu.VMEM
    )
    merged, count = pl.pallas_call(
        _or_popcount_kernel,
        grid=(grid,),
        in_specs=[block, block],
        out_specs=[
            block,
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(new2d.shape, jnp.int32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        interpret=interpret,
    )(new2d, old2d)
    return merged, count[0, 0]


def or_popcount(new_bits, old_bits, interpret: bool = False):
    """``(old | new, popcount(new & ~old))`` over int32 bit-vector words.

    1-D int32 inputs of equal length; zero-pads internally to the kernel
    tile. Returns (merged 1-D array, newly-lit bit count as 0-d int32).
    Compiled by Mosaic unless the caller asks for ``interpret=True``.
    """
    n = new_bits.shape[0]
    tile = _BLOCK_ROWS * _LANES
    n_pad = (n + tile - 1) // tile * tile
    new2d = jnp.zeros(n_pad, jnp.int32).at[:n].set(new_bits).reshape(-1, _LANES)
    old2d = jnp.zeros(n_pad, jnp.int32).at[:n].set(old_bits).reshape(-1, _LANES)
    merged, count = _or_popcount_2d(new2d, old2d, interpret)
    return merged.reshape(-1)[:n], count
