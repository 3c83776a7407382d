"""Property test: the two step forms of every policy are interchangeable.

The lane-vectorized refactor's safety net — for every registered policy,
random ``(carry, arrive, params, dt)`` blocks must give identical outputs
from the scalar ``lax.switch`` step and the branchless lane-vectorized
step (the registry asserts a fixed random block at registration; this
sweeps the space). Follows the repo's importorskip guard pattern:
hypothesis is optional, the module skips cleanly without it.
"""
import numpy as np
import pytest

pytest.importorskip("hypothesis")
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from hypothesis import given, settings  # noqa: E402
from hypothesis import strategies as st  # noqa: E402

from repro.core.twin import (CARRY_DIM, PARAM_DIM,  # noqa: E402
                             lane_policy_step, policy_branches,
                             policy_names, policy_onehot, policy_spec)

LANES = 4

finite = dict(allow_nan=False, allow_infinity=False, width=32)
carry_vals = st.floats(0.0, 1e5, **finite)
arrive_vals = st.floats(0.0, 1e5, **finite)
param_vals = st.floats(float(np.float32(1e-3)), 1e3, **finite)
dts = st.sampled_from([1.0, 0.25, 1.0 / 60.0, 1.0 / 3600.0])


def _block(draw_list, shape):
    return np.asarray(draw_list, np.float32).reshape(shape)


@st.composite
def lane_blocks(draw):
    carry = _block(draw(st.lists(carry_vals, min_size=LANES * CARRY_DIM,
                                 max_size=LANES * CARRY_DIM)),
                   (LANES, CARRY_DIM))
    arrive = _block(draw(st.lists(arrive_vals, min_size=LANES,
                                  max_size=LANES)), (LANES,))
    params = _block(draw(st.lists(param_vals, min_size=LANES * PARAM_DIM,
                                  max_size=LANES * PARAM_DIM)),
                    (LANES, PARAM_DIM))
    dt = draw(dts)
    return carry, arrive, params, dt


@pytest.mark.parametrize("policy", policy_names())
@given(block=lane_blocks())
@settings(max_examples=25, deadline=None)
def test_scalar_and_lane_steps_agree(policy, block):
    carry, arrive, params, dt = block
    spec = policy_spec(policy)
    dt = jnp.float32(dt)
    c_lane, o_lane = spec.lane_step(jnp.asarray(carry), jnp.asarray(arrive),
                                    jnp.asarray(params), dt)
    for lane in range(LANES):
        # the scalar form exactly as the XLA kernel dispatches it
        c_s, o_s = jax.lax.switch(spec.index, policy_branches(),
                                  jnp.asarray(carry[lane]),
                                  jnp.asarray(arrive[lane]),
                                  jnp.asarray(params[lane]), dt)
        np.testing.assert_allclose(np.asarray(c_lane[lane]),
                                   np.asarray(c_s), rtol=1e-6, atol=1e-6)
        for a, b in zip(o_lane, o_s):
            np.testing.assert_allclose(np.asarray(a[lane]), np.asarray(b),
                                       rtol=1e-6, atol=1e-6)


@given(block=lane_blocks(),
       idx=st.lists(st.integers(0, len(policy_names()) - 1),
                    min_size=LANES, max_size=LANES))
@settings(max_examples=25, deadline=None)
def test_masked_blend_matches_switch(block, idx):
    """lane_policy_step (the Pallas kernel's bin-step) == per-lane switch."""
    carry, arrive, params, dt = block
    idx = np.asarray(idx, np.int32)
    dt = jnp.float32(dt)
    c_lane, o_lane = lane_policy_step(
        jnp.asarray(carry), jnp.asarray(arrive), jnp.asarray(params),
        jnp.asarray(policy_onehot(idx)), dt)
    for lane in range(LANES):
        c_s, o_s = jax.lax.switch(int(idx[lane]), policy_branches(),
                                  jnp.asarray(carry[lane]),
                                  jnp.asarray(arrive[lane]),
                                  jnp.asarray(params[lane]), dt)
        np.testing.assert_allclose(np.asarray(c_lane[lane]),
                                   np.asarray(c_s), rtol=1e-6, atol=1e-6)
        for a, b in zip(o_lane, o_s):
            np.testing.assert_allclose(np.asarray(a[lane]), np.asarray(b),
                                       rtol=1e-6, atol=1e-6)
