"""Plain reference for the hybrid decoder of the ``nemotron_h`` family
(NVIDIA Nemotron-H / Nemotron 3, as NVIDIA-Nemotron-3-Nano-30B-A3B
publishes it): whole token rows in, the last position's normalised hidden
state and logits out, with each expert block's load a row.

Imports nothing of the program and uses no trick of its: float32 arithmetic
at ``highest`` matrix precision; **the state-space scan is the recurrence
itself, a position at a time in float32**, not a second chunked algorithm;
the convolution is four shifted multiply-adds; attention is dense masked
scores, a few heads and a block of queries at a time, so that they fit
beside the weights; the experts are looped over, each given the tokens that
chose it (found on the host and handed over 1,024 at a time, as
``reference/mla_moe.py`` does). It computes at the precision the
configuration STATES, no finer: the residual stream, every matrix product's
inputs, ``z``, ``xBC`` after its activation and the scan's ``y`` are rounded
to bfloat16 (``_bf``: ``lax.reduce_precision``, which no compiler folds
away), while norms, softmax, router scores and the choice of experts, the
convolution, ``dt``, every decay and the carried state stay float32. The
parameter tree is the program's (widened here a block, and an expert, at a
time); everything else is written from the equations the configuration's
file gives:

- ``h = E[token]``; every block is ``h <- h + f(RMSNorm(h))`` with ``f`` by
  the block's letter in ``hybrid_override_pattern[:layers]``;
- ``M``: ``[z | xBC | dt] = x W_in``; ``xBC <- silu(conv(xBC))``, depthwise,
  causal, kernel 4, with bias, three zeros in front of the row; ``xBC -> x
  (heads x head width) | B | C (groups x state)``, head ``h`` reading group
  ``h // (heads / groups)``; ``dt <- softplus(dt + dt_bias)``, ``A =
  -exp(A_log)``; ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T`` from ``S =
  0``, ``y_t = S_t C_t + D x_t``; ``u = y * silu(z)``, RMS-normalised inside
  each group of channels, times a learned scale; ``u W_out``;
- ``*``: ``q, k, v = x W_q, x W_k, x W_v``, grouped heads, causal softmax at
  ``1/sqrt(head_dim)``, no positions, ``W_o``;
- ``E``: sigmoid router scores ``s``, the ``k`` largest of ``s + b`` chosen,
  weights ``s / sum(s) * routed_scaling_factor`` over the chosen; an expert
  is ``W_down relu(W_up x)^2``; plus the shared expert, the same form.

Departures from the published model, both the program's too: the one-token
recurrent step and any cache across calls are not built (nothing here
generates), and the ``1e-20`` of the published routing denominator is left
out (sigmoid scores cannot sum to zero).

``fault`` plants one departure, for the tests and for ``calibrate``: see
``FAULTS``; ``state_reset_every_chunk`` drops the carried state at every
multiple of ``chunk_size`` (what a chunked scan without its pass over the
chunk states computes), ``five_experts_of_six`` gives the least of a
token's chosen experts weight 0 (one short of ``num_experts_per_tok``,
whatever that is), ``key_value_heads_swapped`` gives each group of query
heads the last key/value head where it reads the first and so on (a wrong
group mapping), ``head_inputs_3_mantissa_bits`` rounds the head's product
alone to what float8 e4m3 keeps of a mantissa (nothing before the head
moves, so only the following check, ``head_of`` over the job's own hidden
state, sees it).
"""

from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench.reference.afmoe import load_gaps, relative_gaps  # noqa: F401  (the comparison's numbers)
from chipbench.reference.mla_moe import _head, head_of  # noqa: F401  (the final norm and the head: one form)

FAULTS = ("conv_skipped", "no_dt_bias", "no_d_term", "gate_after_norm", "one_norm_group",
          "state_reset_every_chunk", "relu_not_squared", "five_experts_of_six", "no_shared_expert",
          "rotary_in_attention", "attention_skipped", "key_value_heads_swapped", "head_inputs_3_mantissa_bits")
HEADS_AT_A_TIME = 4
QUERIES_AT_A_TIME = 1024
TOKENS_AT_A_TIME = 1024  # of those that chose one expert
_STACK = {"M": "mixer", "*": "attention", "E": "experts"}


def blocks(config: dict):
    """[(letter, index in its kind's stack)] of the blocks that are run: the
    first ``layers`` letters of the published pattern."""
    held = config["hybrid_override_pattern"][: config["layers"]]
    if not re.fullmatch(r"(M\*?E)*", held):
        raise ValueError(f"blocks {held!r}: not whole units of M, an optional *, E")
    seen, out = {}, []
    for letter in held:
        out.append((letter, seen.get(letter, 0)))
        seen[letter] = seen.get(letter, 0) + 1
    return out


def _bf(x):
    """Round to bfloat16 (8 exponent bits, 7 of mantissa), stay float32."""
    return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _block(stack, index):
    """Block ``index`` of a stack, widened."""
    return {n: lax.dynamic_index_in_dim(a, index, keepdims=False).astype(jnp.float32) for n, a in stack.items()}


def scan(x, dt, A, B, C, D, reset_every=None):
    """The recurrence, a position at a time: x (S, heads, width), dt (S,
    heads), A and D (heads,), B and C (S, groups, state). -> y (S, heads,
    width). ``reset_every`` plants the fault: the state is dropped before
    every position that is a multiple of it."""
    S, H, P = x.shape
    G = B.shape[1]

    def step(state, at):
        t, x_t, dt_t, b_t, c_t = at
        b_t, c_t = (jnp.repeat(a, H // G, axis=0) for a in (b_t, c_t))  # (heads, state)
        if reset_every:
            state = jnp.where(t % reset_every == 0, 0.0, state)
        state = jnp.exp(dt_t * A)[:, None, None] * state + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, (state * c_t[:, None, :]).sum(axis=-1) + D[:, None] * x_t

    _, y = lax.scan(step, jnp.zeros((H, P, B.shape[2]), jnp.float32), (jnp.arange(S), x, dt, B, C))
    return y


def conv(x, taps, bias):
    """Depthwise and causal over time: x (S, channels), taps (channels, K);
    tap ``K - 1`` meets the position itself, tap 0 the one ``K - 1`` before,
    and what lies before the row is zero."""
    S, K = x.shape[0], taps.shape[1]
    out = bias + taps[:, K - 1] * x
    for back in range(1, K):
        out = out + taps[:, K - 1 - back] * jnp.concatenate([jnp.zeros_like(x[:back]), x[: S - back]])
    return out


@functools.partial(jax.jit, static_argnames=("config", "fault"))
def _mix(stack, index, h, config, fault):
    """``h + Mixer(N(h))`` over one row, rounded."""
    c = dict(config)
    with jax.default_matmul_precision("highest"):
        p = _block(stack, index)
        S = h.shape[0]
        H, P, G, N = c["mamba_num_heads"], c["mamba_head_dim"], c["n_groups"], c["ssm_state_size"]
        inner, eps = H * P, c["layer_norm_epsilon"]
        mixed = _bf(_norm(h, p["norm"], eps)) @ p["in_proj"]
        z, xbc, dt = _bf(mixed[:, :inner]), mixed[:, inner:-H], mixed[:, -H:]
        if fault != "conv_skipped":
            xbc = conv(xbc, p["conv_w"], p["conv_b"])
        xbc = _bf(jax.nn.silu(xbc))
        dt = jax.nn.softplus(dt if fault == "no_dt_bias" else dt + p["dt_bias"])
        x = xbc[:, :inner].reshape(S, H, P)
        y = scan(x, dt, -jnp.exp(p["A_log"]), xbc[:, inner:inner + G * N].reshape(S, G, N),
                 xbc[:, inner + G * N:].reshape(S, G, N),
                 jnp.zeros_like(p["D"]) if fault == "no_d_term" else p["D"],
                 c["chunk_size"] if fault == "state_reset_every_chunk" else None)
        y, gate = _bf(y).reshape(S, inner), jax.nn.silu(z)
        groups = 1 if fault == "one_norm_group" else G
        grouped = lambda a: _norm(a.reshape(S, groups, inner // groups), 1.0, eps).reshape(S, inner)
        u = grouped(y) * gate if fault == "gate_after_norm" else grouped(y * gate)
        return _bf(h + _bf(u * p["gate_norm"]) @ p["out_proj"])


def rope(x, theta):
    """x: (S, heads, d). Pairs ``(i, i + d/2)`` turned by ``position x
    theta^(-2i/d)``: what the planted fault adds, and the model does not."""
    S, _, d = x.shape
    freq = theta ** (-jnp.arange(d // 2, dtype=jnp.float32) / (d // 2))
    turn = jnp.exp(1j * (jnp.arange(S, dtype=jnp.float32)[:, None] * freq))[:, None, :]
    z = lax.complex(x[..., : d // 2], x[..., d // 2:]) * turn
    return jnp.concatenate([z.real, z.imag], axis=-1)


@functools.partial(jax.jit, static_argnames=("config", "fault"))
def _attend(stack, index, h, config, fault):
    """``h + Attn(N(h))`` over one row, rounded."""
    c = dict(config)
    with jax.default_matmul_precision("highest"):
        p = _block(stack, index)
        S = h.shape[0]
        H, KV, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
        x = _bf(_norm(h, p["norm"], c["layer_norm_epsilon"]))
        q, k, v = ((x @ p[w]).reshape(S, n, hd) for w, n in (("wq", H), ("wk", KV), ("wv", KV)))
        if fault == "rotary_in_attention":
            q, k = rope(q, c["rope_theta"]), rope(k, c["rope_theta"])
        q, k, v = _bf(q), _bf(k), _bf(v)
        block = QUERIES_AT_A_TIME if S % QUERIES_AT_A_TIME == 0 else S
        j = jnp.arange(S)[None, :]

        def some_heads(heads):  # (n,) query heads -> (blocks, n, block, hd)
            group = heads // (H // KV)
            if fault == "key_value_heads_swapped":
                group = KV - 1 - group
            mine_k, mine_v = k[:, group], v[:, group]

            def some_queries(start):
                i = start + jnp.arange(block)[:, None]
                mine = lax.dynamic_slice_in_dim(q, start, block, axis=0)[:, heads]
                scores = jnp.einsum("shd,thd->hst", mine, mine_k) / np.sqrt(hd)
                scores = jnp.where(j <= i, scores, -jnp.inf)
                # rounded before they are normalised: an online softmax has no
                # denominator yet when its weights meet the values
                weights = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
                total = weights.sum(axis=-1, keepdims=True)
                return jnp.einsum("hst,thd->hsd", _bf(weights), mine_v) / total

            return lax.map(some_queries, jnp.arange(0, S, block))

        n = min(HEADS_AT_A_TIME, H)
        out = lax.map(some_heads, jnp.arange(H).reshape(H // n, n))  # (H/n, blocks, n, block, hd)
        out = out.transpose(1, 3, 0, 2, 4).reshape(S, H * hd)
        return _bf(h + _bf(out) @ p["wo"])


def _expert(x, up, down, squared=True):
    inner = jax.nn.relu(x @ up)
    return _bf(inner * inner if squared else inner) @ down


@functools.partial(jax.jit, static_argnames=("k", "scale", "eps", "fault"))
def _route(stack, index, h, k, scale, eps, fault):
    """-> (the experts' input ``N(h)`` rounded, chosen experts (S, k), their weights (S, k))."""
    with jax.default_matmul_precision("highest"):
        norm, router, bias = (lax.dynamic_index_in_dim(stack[n], index, keepdims=False).astype(jnp.float32)
                              for n in ("norm", "router", "router_bias"))
        x = _bf(_norm(h, norm, eps))
        scores = jax.nn.sigmoid(x @ router)
        _, chosen = lax.top_k(scores + bias, k)
        picked = jnp.take_along_axis(scores, chosen, axis=1)
        weights = picked / picked.sum(axis=1, keepdims=True) * scale
        if fault == "five_experts_of_six":
            weights = jnp.where(picked == picked.min(axis=1, keepdims=True), 0.0, weights)
        return x, chosen, weights


@functools.partial(jax.jit, donate_argnums=0, static_argnames=("squared",))
def _add_expert(y, x, tokens, weights, stack, index, expert, squared):
    """``y[tokens] += weights * Expert(x[tokens])`` for one expert of block
    ``index``; a padded slot names token ``S`` at weight 0 and is dropped."""
    with jax.default_matmul_precision("highest"):
        up, down = (lax.dynamic_index_in_dim(lax.dynamic_index_in_dim(stack[n], index, keepdims=False),
                                             expert, keepdims=False).astype(jnp.float32)
                    for n in ("e_up", "e_down"))
        mine = x[jnp.minimum(tokens, x.shape[0] - 1)]
        return y.at[tokens].add(weights[:, None] * _expert(mine, up, down, squared), mode="drop")


@functools.partial(jax.jit, static_argnames=("shared", "squared"))
def _close(stack, index, h, x, y, shared, squared):
    with jax.default_matmul_precision("highest"):
        if shared:
            y = y + _expert(x, *(lax.dynamic_index_in_dim(stack[n], index, keepdims=False).astype(jnp.float32)
                                 for n in ("s_up", "s_down")), squared)
        return _bf(h + y)


def _experts(stack, index, h, config, fault):
    """-> (``h + Experts(N(h))`` (S, hidden), tokens an expert received (experts,))."""
    E, k, S = config["n_routed_experts"], config["num_experts_per_tok"], h.shape[0]
    x, chosen, weights = _route(stack, index, h, k, config["routed_scaling_factor"],
                                config["layer_norm_epsilon"], fault)
    chosen, weights = np.asarray(chosen), np.asarray(weights)
    squared = fault != "relu_not_squared"
    y = jnp.zeros_like(x)
    for e in range(E):
        tokens, slots = np.nonzero(chosen == e)
        for start in range(0, len(tokens), TOKENS_AT_A_TIME):
            some = slice(start, start + TOKENS_AT_A_TIME)
            padded = np.full(TOKENS_AT_A_TIME, S, np.int32)
            padded[: len(tokens[some])] = tokens[some]
            w = np.zeros(TOKENS_AT_A_TIME, np.float32)
            w[: len(tokens[some])] = weights[tokens[some], slots[some]]
            y = _add_expert(y, x, padded, w, stack, index, e, squared)
    load = np.bincount(chosen.ravel(), minlength=E).astype(np.int32)
    return _close(stack, index, h, x, y, fault != "no_shared_expert", squared), load


def _hashable(config: dict):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v) for k, v in config.items()))


def forward(params, tokens, config: dict, fault=None) -> dict:
    """tokens: (rows, S) int. -> ``hidden`` (rows, hidden) and ``logits``
    (rows, vocabulary) of each row's last position, float32, and
    ``expert_load`` (rows, expert blocks, experts) int32."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    frozen = _hashable(config)
    hidden, logits, loads = [], [], []
    for row in np.asarray(tokens):
        h = params["embed"][jnp.asarray(row)].astype(jnp.float32)
        row_loads = []
        for letter, index in blocks(config):
            stack = params[_STACK[letter]]
            if letter == "M":
                h = _mix(stack, index, h, frozen, fault)
            elif letter == "*":
                if fault != "attention_skipped":
                    h = _attend(stack, index, h, frozen, fault)
            else:
                h, load = _experts(stack, index, h, config, fault)
                row_loads.append(load)
        hid, log = _head(params["final_norm"], params["head"], h[-1], config["layer_norm_epsilon"],
                         3 if fault == "head_inputs_3_mantissa_bits" else 7)
        hidden.append(np.asarray(hid))
        logits.append(np.asarray(log))
        loads.append(np.asarray(row_loads, np.int32).reshape(-1, config["n_routed_experts"]))
    return {"hidden": np.stack(hidden), "logits": np.stack(logits),
            "expert_load": np.stack(loads)}
