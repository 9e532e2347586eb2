"""Learned sparse attention inside a model: an indexer scores every key a
query sees, the query keeps its ``topk`` best keys, and attention reads the
kept keys only. Two Pallas kernels, ``dsa_index`` and ``dsa_attn``.

The indexer's score of key ``s`` for query ``t`` (``s <= t``) is
``I[t, s] = sum_j w[t, j] relu(qi[t, j] . ki[s])`` over the indexer's heads
``j``, with one indexer key a position shared by every head; ``w`` arrives
with every constant scale folded in. ``t`` keeps the ``min(t + 1, topk)``
keys of the largest score, a tie going to the lower position (the rule of
``lax.top_k``). Every attention head of a query shares that selection.

``dsa_index``: a grid step holds one block of queries and its indexer key
sequence in VMEM, writes the block's scores over every key it sees into a
VMEM scratch as integers of the same order (:func:`order_keys`), and finds
each query's cut there by a search over the bits (a count of the keys at or
above a candidate, a pass over the scratch a bit, until exactly ``topk``
lie above it; a tie at the cut is then split by a search over positions).
Scores never reach HBM and nothing is sorted. A count crosses lanes once a
pass, not once a key block: it is added 128 lanes wide through the pass and
summed across the lanes at its end. What it writes is each query's kept set
as a bitmask (:func:`unpack_keys`): a word column holds 32 bit planes of
128 keys, so a row of 32,768 keys is 1,024 int32 words.

``dsa_attn``: :mod:`ops.attention`'s blocked loop (a block of queries of the
heads of one key/value head, the head's whole key and value sequence in
VMEM, an online softmax over key blocks up to the diagonal) whose scores
meet the bitmask: a key outside a query's set weighs nothing, and a key
block in which no query of the block keeps a key is not computed at all.
Where kept sets have no locality between neighbouring queries (random
weights) nearly every tile holds a kept key, and the loop does the dense
work (PERF.md section 6 gives the designs timed on the chip).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_Q, BLOCK_K = 128, 512
_LANES, _PLANES = 128, 32  # a word column: 32 bit planes of 128 keys
_NONE = np.iinfo(np.int32).min  # the order key of a key a query does not see
_LOW = -1e30
_VMEM = 100 * 2**20


def _round_up(n: int, to: int) -> int:
    return -(-n // to) * to


def words_for(length: int) -> int:
    """Words of a query's bitmask over ``length`` keys (padded to a key block)."""
    return _LANES * -(-_round_up(length, BLOCK_K) // (_LANES * _PLANES))


def order_keys(scores):
    """float32 -> int32 of the same order (minus zero as zero), so that a
    count of the keys at or above a candidate is a comparison of integers
    and a search over a score's bits is a search over an integer's."""
    bits = lax.bitcast_convert_type(scores, jnp.int32)
    keys = jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits)
    return jnp.where(keys == -1, 0, keys)


def unpack_keys(words, length: int):
    """A bitmask of :func:`dsa_index` (..., words) -> bool (..., length):
    key ``s`` is bit ``(s // 128) % 32`` of word ``(s // 4096) * 128 + s %
    128``. numpy in, numpy out."""
    words = np.asarray(words).astype(np.uint32)
    s = np.arange(length)
    column = (s // (_LANES * _PLANES)) * _LANES + s % _LANES
    plane = (s // _LANES) % _PLANES
    return ((words[..., column] >> plane.astype(np.uint32)) & 1).astype(bool)


def _scores(qi, w, ki):
    """One tile of indexer scores, float32: qi [(bq, d)] and w [(bq, 1)] a
    head, ki (d, bk) -> (bq, bk)."""
    acc = None
    for q, wj in zip(qi, w):
        term = wj * jnp.maximum(jnp.dot(q, ki, preferred_element_type=jnp.float32), 0.0)
        acc = term if acc is None else acc + term
    return acc


def _index_kernel(qi_ref, w_ref, ki_ref, words_ref, keys_ref, *, topk, bq, bk):
    """One block of ``bq`` queries: scores over every key the block sees,
    each query's cut, its kept set as bits."""
    i = pl.program_id(1)
    t = lax.broadcasted_iota(jnp.int32, (bq, 1), 0) + i * bq  # the block's positions
    blocks = (i * bq + bq - 1) // bk + 1  # key blocks that hold a key the block sees
    lanes = lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    heads = w_ref.shape[1]
    d = qi_ref.shape[1] // heads
    qi = [qi_ref[:, j * d:(j + 1) * d] for j in range(heads)]
    w = [w_ref[:, j:j + 1] for j in range(heads)]

    def fill(j, carry):
        start = pl.multiple_of(j * bk, bk)
        keys = order_keys(_scores(qi, w, ki_ref[:, pl.ds(start, bk)]))
        keys_ref[:, pl.ds(start, bk)] = jnp.where(lanes + start <= t, keys, _NONE)
        return carry

    lax.fori_loop(0, blocks, fill, 0)

    def count(at):
        """(bq, 1) float32: keys of each query for which ``at(keys, positions)``,
        added lane by lane into a (bq, 128) carry over the sweep (exact: a
        count is at most S), then summed across the lanes once."""
        def step(j, acc):
            start = pl.multiple_of(j * bk, bk)
            hit = jnp.where(at(keys_ref[:, pl.ds(start, bk)], lanes + start), 1.0, 0.0)
            return functools.reduce(lambda a, c: a + hit[:, c:c + _LANES], range(0, bk, _LANES), acc)

        wide = lax.fori_loop(0, blocks, step, jnp.zeros((bq, _LANES), jnp.float32))
        return jnp.sum(wide, axis=1, keepdims=True)

    def any_row(rows):
        return jnp.max(jnp.where(rows, 1.0, 0.0)) > 0.0

    # the cut: the largest key ``lo`` with at least ``topk`` keys at or above
    # it; ``hi`` is exclusive, ``at_lo`` and ``at_hi`` the counts there
    k = float(topk)
    seen = (t + 1).astype(jnp.float32)
    cut = seen > k  # a query that sees no more than topk keys keeps them all
    lo = jnp.where(cut, _NONE + 1, _NONE)
    hi = jnp.where(cut, np.iinfo(np.int32).max, _NONE + 1)
    at_lo, at_hi = jnp.where(cut, seen, k), jnp.zeros_like(seen)

    def open_rows(lo, hi):
        return lo < hi - 1

    def halve(carry):
        lo, hi, at_lo, at_hi = carry
        active = open_rows(lo, hi)
        mid = (lo & hi) + ((lo ^ hi) >> 1)  # the floor of the mean, with no overflow
        n = count(lambda keys, _: keys >= mid)
        up, down = active & (n >= k), active & (n < k)
        lo, at_lo = jnp.where(up, mid, lo), jnp.where(up, n, at_lo)
        hi, at_hi = jnp.where(down, mid, hi), jnp.where(down, n, at_hi)
        return lo, jnp.where(up & (n == k), mid + 1, hi), at_lo, at_hi  # exactly topk: done

    lo, _, at_lo, at_hi = lax.while_loop(lambda c: any_row(open_rows(c[0], c[1])), halve, (lo, hi, at_lo, at_hi))

    # more than topk keys at or above the cut: of the keys AT it the lowest
    # positions are kept, as many as are wanted: the last such position
    tie = at_lo > k
    wanted = k - at_hi

    def halve_positions(carry):
        p_lo, p_hi = carry
        active = tie & open_rows(p_lo, p_hi)
        mid = (p_lo + p_hi) >> 1
        n = count(lambda keys, pos: (keys == lo) & (pos <= mid))
        up = active & (n >= wanted)
        return jnp.where(active & ~up, mid, p_lo), jnp.where(up, mid, p_hi)

    _, last = lax.while_loop(lambda c: any_row(tie & open_rows(c[0], c[1])), halve_positions,
                             (jnp.full_like(t, -1), t))

    per = bk // _LANES  # 128-key chunks of a key block
    words_ref[...] = jnp.zeros_like(words_ref)

    def pack(j, carry):
        start = pl.multiple_of(j * bk, bk)
        keys, pos = keys_ref[:, pl.ds(start, bk)], lanes + start
        kept = (pos <= t) & ((keys > lo) | ((keys == lo) & (pos <= last)))
        bits = jnp.where(kept, 1, 0)
        plane = (j * per) % _PLANES
        word = functools.reduce(lambda a, b: a | b, [
            bits[:, c * _LANES:(c + 1) * _LANES] << (plane + c) for c in range(per)])
        column = pl.multiple_of((j * per) // _PLANES * _LANES, _LANES)
        words_ref[:, pl.ds(column, _LANES)] = words_ref[:, pl.ds(column, _LANES)] | word
        return carry

    lax.fori_loop(0, blocks, pack, 0)


def dsa_index(qi, w, ki, topk: int, *, interpret: bool = False):
    """Each query's kept keys, as a bitmask.

    ``qi`` is ``(batch, S, heads, d)`` and ``ki`` ``(batch, S, d)`` (the
    indexer's queries and its one key a position, bfloat16 as stated), ``w``
    ``(batch, S, heads)`` float32, every constant scale folded in. Scores are
    float32 sums of bfloat16 products. Returns ``(batch, S, words)`` int32
    (:func:`words_for`, :func:`unpack_keys`): query ``t`` keeps the
    ``min(t + 1, topk)`` keys ``s <= t`` of the largest score, a tie to the
    lower position. ``S`` is padded to a key block (a padded key lies after
    every real query). ``interpret`` runs the kernel in the Pallas
    interpreter, for a backend that is no TPU; it is never chosen here."""
    B, S, heads, d = qi.shape
    if ki.shape != (B, S, d) or w.shape != (B, S, heads):
        raise ValueError(f"qi {qi.shape}, w {w.shape}, ki {ki.shape}: not one indexer of one length")
    padded = _round_up(S, BLOCK_K)
    pad = ((0, 0), (0, padded - S), (0, 0))
    qi = jnp.pad(qi.reshape(B, S, heads * d), pad)  # a head's query at lanes j*d..(j+1)*d
    w = jnp.pad(w, pad)
    ki = jnp.pad(ki, pad).transpose(0, 2, 1)
    width = words_for(padded)
    words = pl.pallas_call(
        functools.partial(_index_kernel, topk=int(topk), bq=BLOCK_Q, bk=BLOCK_K),
        grid=(B, padded // BLOCK_Q),
        in_specs=[pl.BlockSpec((None, BLOCK_Q, heads * d), lambda b, i: (b, i, 0)),
                  pl.BlockSpec((None, BLOCK_Q, heads), lambda b, i: (b, i, 0)),
                  pl.BlockSpec((None, d, padded), lambda b, i: (b, 0, 0))],
        out_specs=pl.BlockSpec((None, BLOCK_Q, width), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, padded, width), jnp.int32),
        scratch_shapes=[pltpu.VMEM((BLOCK_Q, padded), jnp.int32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"),
                                             vmem_limit_bytes=_VMEM),
        interpret=interpret,
        name="dsa_index",
    )(qi, w, ki)
    return words[:, :S]


def _key_blocks(words, bq: int, bk: int):
    """The key blocks that each block of ``bq`` queries meets, those that hold
    a key one of its queries keeps first and in order: -> (``(batch x
    query blocks x key blocks,)`` int32, how many of each list hold one
    ``(batch x query blocks,)`` int32). A block above the diagonal holds none."""
    B, S, width = words.shape
    nq, nk, per = S // bq, S // bk, bk // _LANES
    union = lax.reduce(words.reshape(B, nq, bq, width), np.int32(0), lax.bitwise_or, (2,))
    # a word column holds the planes of _PLANES // per key blocks, ``per`` each
    shifts = jnp.arange(_PLANES // per, dtype=jnp.int32) * per
    planes = union.reshape(B, nq, width // _LANES, _LANES, 1) >> shifts
    held = ((planes & ((1 << per) - 1)) != 0).any(axis=3).reshape(B, nq, -1)[..., :nk]
    order = jnp.argsort(~held, axis=-1, stable=True)
    return order.astype(jnp.int32).reshape(-1), held.sum(axis=-1).astype(jnp.int32).reshape(-1)


def _attn_kernel(blocks_ref, counts_ref, q_ref, k_ref, v_ref, words_ref, o_ref, *, scale, bq, bk):
    """One block of ``bq`` queries of every head of a group, against the
    key/value blocks its list names (:func:`_key_blocks`): straight-line
    tiles, no branch (a ``lax.cond`` a tile cost 48% on the chip, PERF.md)."""
    b, i, nq = pl.program_id(0), pl.program_id(2), pl.num_programs(2)
    G, _, d = q_ref.shape
    d_v = v_ref.shape[-1]
    per, nk = bk // _LANES, k_ref.shape[0] // bk
    q = q_ref[...].reshape(G * bq, d)
    row = (b * nq + i) * nk

    def meet(n, carry):
        acc, top, denom = carry
        j = blocks_ref[row + n]
        column = pl.multiple_of((j * per) // _PLANES * _LANES, _LANES)
        planes = (words_ref[:, pl.ds(column, _LANES)] >> ((j * per) % _PLANES)) & ((1 << per) - 1)
        kept = jnp.concatenate([(planes >> c) & 1 for c in range(per)], axis=1) > 0  # (bq, bk)
        start = pl.multiple_of(j * bk, bk)
        k = k_ref[pl.ds(start, bk), :]
        v = v_ref[pl.ds(start, bk), :]
        scores = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32).reshape(G, bq, bk) * scale
        scores = jnp.where(kept[None], scores, _LOW)
        new_top = jnp.maximum(top, scores.max(axis=-1, keepdims=True))
        shrink = jnp.exp(top - new_top)
        weights = jnp.where(kept[None], jnp.exp(scores - new_top), 0.0)
        denom = denom * shrink + weights.sum(axis=-1, keepdims=True)
        step = jnp.dot(weights.reshape(G * bq, bk).astype(v.dtype), v, preferred_element_type=jnp.float32)
        return acc * shrink + step.reshape(G, bq, d_v), new_top, denom

    start = (jnp.zeros((G, bq, d_v), jnp.float32), jnp.full((G, bq, 1), _LOW, jnp.float32),
             jnp.zeros((G, bq, 1), jnp.float32))
    acc, _, denom = lax.fori_loop(0, counts_ref[b * nq + i], meet, start)
    o_ref[...] = (acc / denom).astype(o_ref.dtype)


def dsa_attn(q, k, v, words, *, interpret: bool = False):
    """Causal attention over each query's kept keys (``words``, from
    :func:`dsa_index`): softmax of ``q . k /
    sqrt(d)`` over the kept keys alone, grouped heads as
    :func:`ops.attention.blocked_attention` (query head ``h`` reads
    key/value head ``h // (heads // kv_heads)``), products in the inputs'
    dtype summed in float32. Returns ``(batch, S, heads, d_v)`` in ``q``'s
    dtype."""
    B, S, H, d = q.shape
    KV, d_v = k.shape[2], v.shape[-1]
    if H % KV or k.shape != (B, S, KV, d) or v.shape != (B, S, KV, d_v):
        raise ValueError(f"q {q.shape}, k {k.shape}, v {v.shape}: not grouped heads of one length")
    G = H // KV
    padded = _round_up(S, BLOCK_K)
    width = words_for(padded)
    if words.shape != (B, S, width):
        raise ValueError(f"words {words.shape}: not a bitmask of {S} queries over {padded} keys")
    pad = ((0, 0), (0, padded - S))
    q, k, v = (jnp.pad(a, pad + ((0, 0), (0, 0))) for a in (q, k, v))
    words = jnp.pad(words, pad + ((0, 0),))

    def whole_sequence(width):
        return pl.BlockSpec((None, None, padded, width), lambda b, h, i, *_: (b, h, 0, 0))

    def per_queries(width):
        return pl.BlockSpec((None, None, G, BLOCK_Q, width), lambda b, h, i, *_: (b, h, 0, i, 0))

    out = pl.pallas_call(
        functools.partial(_attn_kernel, scale=d ** -0.5, bq=BLOCK_Q, bk=BLOCK_K),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, KV, padded // BLOCK_Q),
            in_specs=[per_queries(d), whole_sequence(d), whole_sequence(d_v),
                      pl.BlockSpec((None, BLOCK_Q, width), lambda b, h, i, *_: (b, i, 0))],
            out_specs=per_queries(d_v)),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, padded, d_v), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"),
                                             vmem_limit_bytes=_VMEM),
        interpret=interpret,
        name="dsa_attn",
    )(
        *_key_blocks(words, BLOCK_Q, BLOCK_K),
        q.reshape(B, padded, KV, G, d).transpose(0, 2, 3, 1, 4),
        k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), words,
    )
    return out.transpose(0, 3, 1, 2, 4).reshape(B, padded, H, d_v)[:, :S]
