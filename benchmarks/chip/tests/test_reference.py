"""The plain reference agrees with the program's model at a small size.

In float32 both compute the same step, so they agree to float32 round-off
(the program accumulates its microbatches and W passes in another order):
losses, the first gradient per leaf and the change of every leaf after the
checked steps, on the one-device and the four-stage ZB-V path.
"""

import dataclasses

import jax
import pytest

import compare
import harness
from small import small_cell

F32_GAP = 1e-4  # float32 round-off over a few thousand-term sums, with room


@pytest.mark.parametrize("pipe", [1, 4])
def test_reference_matches_program_in_float32(pipe):
    c = small_cell(pipe)
    c = dataclasses.replace(c, config=dict(c.config, torch_dtype="float32"))
    b = harness.Bench(c, jax.devices(), 5)
    state, prog = b.check_steps()
    harness.free(state)
    nums = compare.numbers(prog, b.reference())
    assert all(v < F32_GAP for v, _ in nums.values()), nums


def test_state_reads_back_per_layer():
    """Written into the ZB-V layout and read back per layer, the program's
    initial weights are the plain ones, layer by layer."""
    import numpy as np

    from weights import plain_weights

    b = harness.Bench(small_cell(4), jax.devices(), 9)
    state = b.init_state()
    got = jax.jit(b.layout.leaf_norms)(state["params"], state["shared"])
    for name, x in plain_weights(b.cell.arch, jax.numpy.asarray(b.key_words), b.cell.config).items():
        x = np.asarray(x, np.float64)
        axes = tuple(range(1, x.ndim)) if name in b.layout.rows else None
        want = np.sqrt(np.sum(x * x, axis=axes))
        np.testing.assert_allclose(np.asarray(got[name]), want, rtol=1e-5)
