"""The routed experts' combine (``ops/expert_parallel._combine``, the Pallas
kernel ``moe_combine``, in the Pallas interpreter on the CPU): each token's
``k`` rows of ``ys`` read where they lie and weighed into one, slot by slot
in float32, and ``moe_topk`` around it on both of its product paths."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.models.moe_decoder import relu2
from mmlspark_tpu.ops import expert_parallel as ep


def _routing(rng, T, k, E, adjacent):
    """A top-``k`` choice of ``E`` experts a token, as ``moe_topk`` sorts it:
    -> (``back``, (T*k,) int32: where assignment (t, j) sits among the rows
    sorted by expert, token order kept within an expert). Expert 1 gets no
    token; with ``adjacent`` token 0's experts are ``E - k .. E - 1``, one
    group after another."""
    chosen = np.stack([rng.choice([e for e in range(E) if e != 1], size=k, replace=False) for _ in range(T)])
    if adjacent:
        chosen[0] = np.arange(E - k, E)
    order = np.argsort(chosen.reshape(T * k), kind="stable")
    return np.argsort(order).astype(np.int32), chosen


def _parent(ys, back, weights):
    """The expression the kernel took the place of: every row gathered into
    token order, laid out ``(T, k, D)`` and reduced over ``k`` by XLA."""
    T, k = weights.shape
    return (ys[back].reshape(T, k, -1) * weights[:, :, None]).sum(axis=1)


@pytest.mark.parametrize("D", [16, 128, 2 * 128 + 128])
@pytest.mark.parametrize("k", [1, 2, 4, 6, 8])
def test_combine_is_the_slot_order_sum_of_the_weighed_rows(monkeypatch, k, D):
    """75 tokens in blocks of 32: no whole number of blocks, the block halved
    from 64 because VMEM is made to hold the buffers of 32 and no more, and
    the copies of one block in flight while another is summed."""
    T, E = 75, 12
    monkeypatch.setattr(ep, "_COMBINE_BLOCK", 64)
    monkeypatch.setattr(ep, "_COMBINE_VMEM", 2 * (ep._combine_tiles(32, k, E) * 8 + 32) * D * 4)
    assert ep._combine_plan(T, k, D, E) == 32
    rng = np.random.default_rng(k * 1000 + D)
    back, chosen = _routing(rng, T, k, E, adjacent=True)
    expert_of, sizes = chosen.reshape(T * k), np.bincount(chosen.ravel(), minlength=E)
    ys = rng.normal(size=(T * k, D)).astype(np.float32)
    weights = rng.uniform(0.05, 1.0, size=(T, k)).astype(np.float32)

    got = np.asarray(jax.jit(lambda *a: ep._combine(*a, True))(ys, back, expert_of, sizes, weights))
    terms = [ys[back[j::k]] * weights[:, j:j + 1] for j in range(k)]
    slot_order = terms[0]
    for t in terms[1:]:
        slot_order = slot_order + t

    def same_order_compiled(ys, back, weights):
        acc = ys[back[0::k]] * weights[:, :1]
        for j in range(1, k):
            acc = acc + ys[back[j::k]] * weights[:, j:j + 1]
        return acc

    assert got.shape == (T, D) and got.dtype == np.float32
    # the same adds in the same order, compiled for the same backend: bit-equal
    np.testing.assert_array_equal(got, jax.jit(same_order_compiled)(ys, back, weights))
    # numpy rounds each product on its own where the backend may fuse it into
    # the add: one rounding a step apart, each within an ulp of the terms' sum
    spread = np.spacing(np.sum(np.abs(terms), axis=0, dtype=np.float32))
    assert (np.abs(got - slot_order) <= max(k - 1, 1) * spread).all()
    want = np.asarray(_parent(ys, back, weights))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6 * np.abs(want).max())


@pytest.mark.parametrize("shape, tb", [((32768, 4, 2048, 32), 256), ((16384, 6, 2688, 128), 128),
                                       ((32768, 8, 2048, 128), 256), ((16384, 8, 2048, 256), 64),
                                       ((75, 4, 2048, 32), 75)],
                         ids=["short_convolution", "hybrid", "afmoe", "latent", "short_batch"])
def test_the_plan_halves_256_tokens_until_the_whole_width_fits(shape, tb):
    """The four routed cells' shapes: blocks of 256 tokens, halved until two
    buffers of tiles (at most ``tb * k / 8 + 2 E`` a block) and two output
    blocks, all of the whole width, fit the VMEM budget; a short batch is
    one block (PERF.md)."""
    T, k, D, E = shape
    assert ep._combine_plan(T, k, D, E) == tb
    assert 2 * (ep._combine_tiles(tb, k, E) * 8 + tb) * D * 4 <= ep._COMBINE_VMEM
    if tb < min(T, ep._COMBINE_BLOCK):  # the block twice as long would not have fit
        assert 2 * (ep._combine_tiles(2 * tb, k, E) * 8 + 2 * tb) * D * 4 > ep._COMBINE_VMEM


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_each_block_lists_the_tiles_its_rows_lie_in_and_no_more_than_its_buffer_holds(seed):
    """Three blocks of 32 tokens, 16 experts, some far fuller than others:
    a block lists each tile that holds one of its rows once, ascending, never
    more than ``_combine_tiles`` of them, and a row's place among them is its
    tile's rank there and its row within the tile."""
    rng = np.random.default_rng(seed)
    T, k, E, tb = 96, 2, 16, 32
    popular = rng.permutation(np.linspace(3.0, -3.0, E))
    chosen = np.argsort(-(rng.normal(size=(T, E)) + popular), axis=1)[:, :k]
    back = np.argsort(np.argsort(chosen.reshape(T * k), kind="stable")).astype(np.int32)
    sizes = np.bincount(chosen.ravel(), minlength=E)
    most = ep._combine_tiles(tb, k, E)
    tiles, pos = (np.asarray(a)[:, 0] for a in ep._tile_tables(back, chosen.reshape(T * k), sizes, k, tb, most))
    for b in range(T // tb):
        rows = back.reshape(T // tb, tb * k)[b]
        count, listed = tiles[b, 0], tiles[b, 1:1 + tiles[b, 0]]
        assert count <= most and (np.diff(listed) > 0).all() and set(listed) == set(rows // 8)
        np.testing.assert_array_equal(listed[pos[b] // 8] * 8 + pos[b] % 8, rows)


def _moe_topk_with_the_parents_combine(monkeypatch):
    monkeypatch.setattr(ep, "_combine", lambda ys, back, expert_of, sizes, weights, interpret: _parent(ys, back, weights))


@pytest.mark.parametrize("path", ["E_groups", "a_stack_in_place"])
@pytest.mark.parametrize("k", [2, 6])
def test_moe_topk_returns_what_the_parents_combine_did(monkeypatch, path, k):
    """Both product paths feed the same rows to the same kernel: ``E``
    groups through ``lax.ragged_dot``, and a stack of three layers' experts
    read in place through the Pallas grouped matmul told the layer's first
    group. ``y`` is the parent's within rtol 1e-6 (of the output's scale
    where a token's rows cancel), and the choice and the loads are the
    parent's. Expert 3 gets no token."""
    rng = np.random.default_rng(k)
    T, D, F, E, U = 72, 128, 16, 8, 3
    x = jnp.asarray(rng.normal(size=(T, D)), jnp.bfloat16)
    weigh = jnp.asarray(rng.uniform(0.1, 0.9, size=(T, E)), jnp.float32)
    choose = weigh.at[:, 3].add(-10.0)
    shapes = {"up": (E * U, D, F), "down": (E * U, F, D)}
    stack = {n: jnp.asarray(rng.normal(size=s) / 4, jnp.bfloat16) for n, s in shapes.items()}
    if path == "E_groups":
        experts, first = {n: a[E:2 * E] for n, a in stack.items()}, 0
    else:
        experts, first = stack, E

    def run():
        return jax.jit(lambda *a: ep.moe_topk(*a, k, 2.5, relu2, first, True))(x, choose, weigh, experts)

    y, chosen = run()
    with monkeypatch.context() as m:
        _moe_topk_with_the_parents_combine(m)
        want, want_chosen = run()
    np.testing.assert_array_equal(chosen, want_chosen)
    load = np.bincount(np.asarray(chosen).ravel(), minlength=E)
    np.testing.assert_array_equal(load, np.bincount(np.asarray(want_chosen).ravel(), minlength=E))
    assert load[3] == 0 and load.sum() == T * k
    assert y.dtype == jnp.float32 and np.isfinite(np.asarray(y)).all()
    # XLA's reduce adds a token's k rows in another order: where they cancel,
    # a gap is relative to the rows' size, not to what is left of them
    np.testing.assert_allclose(y, want, rtol=1e-6, atol=1e-6 * np.abs(want).max())
