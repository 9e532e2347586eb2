"""The chunked state-space scan (``ops/ssd.ssd_scan``, Pallas interpreter on
the CPU) against the recurrence a position at a time: lengths that are and
are not multiples of the chunk, several chunks with a slow decay (so that a
dropped carry shows), heads met singly and side by side, grouped ``B`` and
``C``, the ``D`` term, bfloat16 inputs, and the shapes it refuses."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.ops.ssd import ssd_reference, ssd_scan


def _inputs(seed, length, heads=4, width=8, groups=2, state=16, dtype=jnp.float32, slow=False, batch=2):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(batch, length, heads, width)), dtype)
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.3), size=(batch, length, heads))), jnp.float32)
    # slow: a state still holds exp(-0.3 x 0.05 x 200) = 5% of what it held 200 positions ago at the least
    A = -jnp.asarray(rng.uniform(0.01, 0.05, size=heads) if slow else rng.uniform(1, 16, size=heads), jnp.float32)
    B, C = (jnp.asarray(rng.normal(size=(batch, length, groups, state)), dtype) for _ in range(2))
    D = jnp.asarray(rng.uniform(0.5, 1.5, size=heads), jnp.float32)
    return x, dt, A, B, C, D


def _scan(*a, chunk=16):
    return jax.jit(lambda *a: ssd_scan(*a, chunk=chunk, interpret=True))(*a)


def _gap(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.abs(got - want).max() / np.abs(want).max()


@pytest.mark.parametrize("length", [16, 64, 128, 1, 15, 17, 50, 200])
@pytest.mark.parametrize("slow", [False, True], ids=["fast_decay", "slow_decay"])
def test_chunked_scan_equals_the_recurrence(length, slow):
    """Multiples of the chunk (1, 4 and 8 chunks: 8 is two grid steps of
    four) and lengths that are not (padded behind)."""
    a = _inputs(length, length, slow=slow)
    got = _scan(*a)
    assert got.shape == a[0].shape and got.dtype == a[0].dtype
    assert _gap(got, ssd_reference(*a)) < 2e-6


def test_a_dropped_carry_fails_where_the_decay_is_slow():
    """Over 13 chunks with a slow decay most of ``y`` comes from earlier
    chunks: the scan with its state dropped at every chunk boundary (each
    chunk run as a row of its own) is far from the recurrence, and the scan
    is not."""
    x, dt, A, B, C, D = a = _inputs(3, 208, slow=True)
    want = ssd_reference(*a)
    assert _gap(_scan(*a), want) < 2e-6
    alone = lambda v: v.reshape((2 * 13, 16) + v.shape[2:])  # every chunk a row
    dropped = _scan(alone(x), alone(dt), A, alone(B), alone(C), D).reshape(x.shape)
    assert _gap(dropped, want) > 0.3
    assert _gap(dropped[:, :16], want[:, :16]) < 2e-6  # the first chunk carries nothing in


@pytest.mark.parametrize("heads,width,groups", [(8, 8, 2), (6, 8, 2), (4, 64, 2), (3, 200, 1), (4, 8, 4)],
                         ids=["four_side_by_side", "three_side_by_side", "two_fill_the_lanes", "wide_heads_singly",
                              "a_group_a_head"])
def test_heads_side_by_side_or_singly_read_their_own_group(heads, width, groups):
    a = _inputs(heads + width, 40, heads=heads, width=width, groups=groups, batch=1)
    assert _gap(_scan(*a), ssd_reference(*a)) < 2e-6
    # a head reads its group's B and C: with the groups' B swapped the result is another
    x, dt, A, B, C, D = a
    if groups > 1:
        assert _gap(_scan(x, dt, A, B[:, :, ::-1], C, D), ssd_reference(*a)) > 0.05


def test_the_d_term_and_the_state_are_separate_sums():
    x, dt, A, B, C, D = _inputs(7, 50)
    state_only = _scan(x, dt, A, B, C, jnp.zeros_like(D))
    np.testing.assert_allclose(_scan(x, dt, A, B, C, D) - state_only, D[:, None] * x, rtol=1e-4, atol=1e-5)
    # a step of zero passes the state unchanged and adds nothing
    assert np.abs(np.asarray(_scan(x, jnp.zeros_like(dt), A, B, C, jnp.zeros_like(D)))).max() == 0


def test_bfloat16_inputs_at_the_published_head_and_state_widths():
    """Products take bfloat16 inputs and sum in float32; decays and the
    carried state stay float32. 300 positions at the published chunk of 128."""
    a = _inputs(5, 300, heads=4, width=64, groups=2, state=128, dtype=jnp.bfloat16, slow=True, batch=1)
    got = _scan(*a, chunk=128)
    assert got.dtype == jnp.bfloat16
    assert _gap(got, ssd_reference(*a)) < 0.01


def test_the_reference_is_the_recurrence_written_out():
    x, dt, A, B, C, D = (np.asarray(v, np.float64) for v in _inputs(9, 12, heads=2, width=3, groups=1, state=4, batch=1))
    state, want = np.zeros((2, 3, 4)), np.zeros((12, 2, 3))
    for t in range(12):
        for h in range(2):
            state[h] = np.exp(dt[0, t, h] * A[h]) * state[h] + dt[0, t, h] * np.outer(x[0, t, h], B[0, t, 0])
            want[t, h] = state[h] @ C[0, t, 0] + D[h] * x[0, t, h]
    got = ssd_reference(*(jnp.asarray(v, jnp.float32) for v in (x, dt, A, B, C, D)))
    np.testing.assert_allclose(got[0], want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("alter", [
    lambda x, dt, A, B, C, D: (x, dt, A, B[:, :, :1].repeat(3, axis=2), C[:, :, :1].repeat(3, axis=2), D),  # 4 heads, 3 groups
    lambda x, dt, A, B, C, D: (x, dt, A, B, C[:, :-1], D),
    lambda x, dt, A, B, C, D: (x, dt[:, :, :2], A, B, C, D),
], ids=["heads_not_in_groups", "c_of_another_length", "dt_of_other_heads"])
def test_shapes_that_are_not_heads_in_groups_over_one_length_are_refused(alter):
    with pytest.raises(ValueError, match="not heads in groups over one length"):
        ssd_scan(*alter(*_inputs(0, 20)), chunk=16, interpret=True)
