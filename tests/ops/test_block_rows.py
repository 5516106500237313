"""A step's blocks of rows into the slot cache (`ops.block_rows`): the
Pallas kernel through the interpreter against `decoder.write_rows` a
block at a time, which is what it is off the TPU, to the bit: blocks of
a slot one behind the other and at one start (the later one's rows
stay), in one tile and across two, at a region's first and last rows,
and nothing else of a stack touched."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import decoder
from ray_tpu.ops import block_rows

LAYERS, SPAN, WIDTH, LENGTH = 3, 64, 256, 4
# A slot each: the starts of its two blocks.
STARTS = {
    "behind each other in a tile": [[0, 4], [16, 20], [36, 40]],
    "across two tiles": [[12, 16], [28, 32], [44, 48]],
    "at one start": [[0, 0], [12, 12], [60, 60]],
    "the region's last rows": [[56, 60], [60, 60], [52, 56]],
    "mixed": [[8, 8], [12, 16], [60, 60], [56, 60], [0, 4], [20, 20]],
}


@pytest.mark.parametrize("starts", STARTS.values(), ids=STARTS.keys())
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_kernel_writes_what_write_rows_writes(starts, dtype):
    starts = jnp.asarray(starts, jnp.int32)
    slots = starts.shape[0]
    rng = np.random.default_rng(slots)
    k, v = (jnp.asarray(rng.normal(size=(LAYERS, slots, SPAN, WIDTH)), dtype)
            for _ in range(2))
    new = [jnp.asarray(rng.normal(size=(slots, 2 * LENGTH, WIDTH)),
                       jnp.float32) for _ in range(2)]
    layer = jnp.int32(1)
    want = [k, v]
    for i in range(2):
        at = slice(i * LENGTH, (i + 1) * LENGTH)
        want = [decoder.write_rows(x, layer, rows[:, at], starts[:, i])
                for x, rows in zip(want, new)]
    plain = block_rows.write_blocks((k, v), layer, new, starts)
    got = jax.jit(block_rows.write_blocks, static_argnames="interpret")(
        (k, v), layer, new, starts, interpret=True)
    for x, y, z, old in zip(got, plain, want, (k, v)):
        assert x.dtype == dtype and x.shape == old.shape
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(z, np.float32))
        np.testing.assert_array_equal(np.asarray(y, np.float32),
                                      np.asarray(z, np.float32))
        assert (np.asarray(x[1], np.float32)
                != np.asarray(old[1], np.float32)).any()
        np.testing.assert_array_equal(np.asarray(x[0::2], np.float32),
                                      np.asarray(old[0::2], np.float32))
