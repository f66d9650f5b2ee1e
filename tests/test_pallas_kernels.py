"""Pallas kernel tests: the or+popcount wave finalizer, run in interpreter
mode because THIS suite runs on the CPU (``interpret=True`` said here, not
guessed by the kernel); chip_smoke.py compiles the same kernel through
Mosaic on the chip and checks it against numpy there."""
import numpy as np
import pytest

import jax.numpy as jnp

from stl_fusion_tpu.ops.pallas_kernels import or_popcount


@pytest.mark.parametrize("n", [7, 128, 32768, 40000])
def test_or_popcount_matches_numpy(n):
    rng = np.random.default_rng(n)
    new = rng.integers(-(2**31), 2**31, size=n, dtype=np.int32)
    old = rng.integers(-(2**31), 2**31, size=n, dtype=np.int32)
    merged, count = or_popcount(jnp.asarray(new), jnp.asarray(old), interpret=True)
    np.testing.assert_array_equal(np.asarray(merged), new | old)
    expect = int(np.bitwise_count((new & ~old).astype(np.uint32)).sum())
    assert int(count) == expect


def test_or_popcount_zero_delta():
    x = jnp.asarray(np.full(1000, 0x0F0F0F0F, dtype=np.int32))
    merged, count = or_popcount(x, x, interpret=True)
    assert int(count) == 0
    np.testing.assert_array_equal(np.asarray(merged), np.asarray(x))
