"""Shared VW learner machinery: params + the jitted adagrad-SGD train loop.

Re-design of ``vw/VowpalWabbitBase.scala:238-442``: the native
``VowpalWabbitNative.learn()`` per-example hot loop becomes a ``lax.scan``
over padded minibatches (gather weights → margin → loss gradient →
scatter-add adagrad update), and the spanning-tree allreduce
(``trainInternalDistributed`` ``:337-365``) becomes ``lax.pmean`` weight
averaging at each pass boundary inside one ``shard_map`` over the mesh
``data`` axis — VW's ``endPass`` synchronization, ICI-native.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import numpy as np

from mmlspark_tpu.core.params import (
    HasFeaturesCol,
    HasLabelCol,
    HasPredictionCol,
    HasWeightCol,
    Param,
    Params,
    ge,
    gt,
    in_range,
    to_bool,
    to_float,
    to_int,
    to_str,
)
from mmlspark_tpu.core.pipeline import Estimator, Model
from mmlspark_tpu.core.utils import StopWatch
from mmlspark_tpu.data.sparse import SparseBatch, column_to_batch, dense_to_batch
from mmlspark_tpu.data.table import Table
from mmlspark_tpu.ops.hashing import mask_bits, murmur32_bytes

#: VW's implicit constant (bias) feature, hashed from the literal "Constant".
CONSTANT_FEATURE = b"Constant"


def _loss_grad(loss: str, margin, y, quantile_tau: float):
    """d loss / d margin. Labels: classifier y in {-1, +1}; regressor real."""
    import jax
    import jax.numpy as jnp

    if loss == "logistic":
        return -y * jax.nn.sigmoid(-y * margin)
    if loss == "squared":
        return margin - y
    if loss == "hinge":
        return jnp.where(y * margin < 1.0, -y, 0.0)
    if loss == "quantile":
        return jnp.where(margin > y, 1.0 - quantile_tau, -quantile_tau)
    raise ValueError(f"unknown loss {loss!r}")


@dataclasses.dataclass
class VWTrainResult:
    weights: np.ndarray
    stats: dict


class VowpalWabbitBaseParams(
    HasFeaturesCol, HasLabelCol, HasPredictionCol, HasWeightCol, Params
):
    numPasses = Param("Training passes over the data", default=1, converter=to_int, validator=gt(0))
    learningRate = Param("Base learning rate", default=0.5, converter=to_float, validator=gt(0))
    powerT = Param("Learning-rate decay exponent", default=0.5, converter=to_float, validator=ge(0))
    l1 = Param("L1 regularization (lazy, applied at pass end)", default=0.0, converter=to_float, validator=ge(0))
    l2 = Param("L2 regularization", default=0.0, converter=to_float, validator=ge(0))
    numBits = Param("log2 feature-space size (when features are dense)", default=18, converter=to_int, validator=in_range(1, 30))
    batchSize = Param("Rows per SGD minibatch", default=64, converter=to_int, validator=gt(0))
    hashSeed = Param("Hash seed for the constant feature", default=0, converter=to_int)
    passThroughArgs = Param("VW-style CLI arg string (parsed for known flags)", default="", converter=to_str)
    useBarrierExecutionMode = Param("Accepted for API parity (SPMD is always synchronous)", default=True, converter=to_bool)
    initialModel = Param("Warm-start weights", is_complex=True)
    interactions = Param("Namespace interaction pairs (handled by VowpalWabbitInteractions)", default=[], is_complex=False)

    # flag -> (out key, converter); None converter = boolean switch
    _ARG_SPEC = {
        "--loss_function": ("loss", str),
        "--learning_rate": ("learning_rate", float),
        "-l": ("learning_rate", float),
        "--passes": ("passes", int),
        "--l1": ("l1", float),
        "--l2": ("l2", float),
        "--power_t": ("power_t", float),
        "-b": ("num_bits", int),
        "--bit_precision": ("num_bits", int),
        "--quantile_tau": ("quantile_tau", float),
        "--ftrl": ("ftrl", None),
        "--ftrl_alpha": ("ftrl_alpha", float),
        "--ftrl_beta": ("ftrl_beta", float),
        "--link": ("link", str),
        "--noconstant": ("noconstant", None),
        # NOTE: hashing happens in the (separate) VowpalWabbitFeaturizer
        # stage in this runtime, so --hash_seed here governs LEARNER-side
        # hashing only (the constant feature / un-featurized spaces). To
        # move the whole feature space, set hashSeed on the featurizer —
        # unlike native VW, where the learner owns all hashing.
        "--hash_seed": ("hash_seed", int),
    }

    #: Diagnostic / IO flags that do not change the trained model: accepted
    #: for pipeline compatibility (the reference forwards them to native VW
    #: where they are no-ops for training math) and skipped with a warning.
    #: Maps flag -> True if it consumes a value token.
    _NOOP_ARGS = {
        "--quiet": False,
        "--no_stdin": False,
        "--holdout_off": False,
        "-p": True,
        "--predictions": True,
        "--progress": True,
        "-P": True,
        "--cache": False,
        "-c": False,
        "--cache_file": True,
        "-k": False,
        "--kill_cache": False,
        "--save_resume": False,
        "--preserve_performance_counters": False,
        "--readable_model": True,
        "--invert_hash": True,
        "--audit": False,
        "-a": False,
    }

    def _parse_args(self) -> dict:
        """Parse the VW CLI flags this runtime implements
        (``appendParamIfNotThere`` analogue, VowpalWabbitBase.scala:140-159).
        Unknown MODEL-CHANGING flags RAISE: the reference hands the full
        string to native VW where every reduction works — silently dropping
        one here would train a different model than the user asked for.
        Known diagnostic/IO flags (``_NOOP_ARGS``) are skipped with a
        warning so existing pipelines that pass e.g. ``--quiet`` keep
        working."""
        from mmlspark_tpu.core.profiling import get_logger

        out = {}
        toks = self.getPassThroughArgs().split()
        i = 0
        while i < len(toks):
            t = toks[i]
            inline = None
            if t.startswith("--") and "=" in t:
                t, _, inline = t.partition("=")
            if t in self._NOOP_ARGS:
                get_logger("mmlspark_tpu.vw").warning(
                    "passThroughArgs: ignoring diagnostic VW flag %r "
                    "(no effect on the trained model in this runtime)", t
                )
                i += 1 + (1 if self._NOOP_ARGS[t] and inline is None else 0)
                continue
            if t not in self._ARG_SPEC:
                raise ValueError(
                    f"passThroughArgs: unsupported VW flag {t!r}. This "
                    "runtime implements: "
                    + " ".join(sorted(self._ARG_SPEC))
                    + ". Other VW reductions/flags are not silently ignored "
                    "— they would change the trained model."
                )
            key, conv = self._ARG_SPEC[t]
            if conv is None:  # boolean switch
                if inline is not None:
                    raise ValueError(f"passThroughArgs flag {t!r} takes no value")
                out[key] = True
                i += 1
                continue
            if inline is None:
                if i + 1 >= len(toks):
                    raise ValueError(f"passThroughArgs flag {t!r} expects a value")
                inline = toks[i + 1]
                i += 2
            else:
                i += 1
            out[key] = conv(inline)
        if out.get("link") not in (None, "identity", "logistic"):
            raise ValueError(
                f"--link {out['link']!r} not supported (identity | logistic)"
            )
        return out


class VowpalWabbitBase(VowpalWabbitBaseParams, Estimator):
    _default_loss = "squared"

    def _label_transform(self, y: np.ndarray) -> np.ndarray:
        return y.astype(np.float32)

    def _get_batch(self, table: Table, num_bits=None) -> Tuple[SparseBatch, bool]:
        """Returns (batch, is_hashed_space). ``num_bits`` overrides the
        param (the ``-b``/``--bit_precision`` pass-through flag); a
        pre-featurized column's ``sparse_dim`` metadata wins over both
        (the space was fixed upstream by VowpalWabbitFeaturizer)."""
        col = table.column(self.getFeaturesCol())
        if col.dtype == object:
            dim = table.metadata(self.getFeaturesCol()).get("sparse_dim")
            if dim is None:
                dim = 1 << (num_bits or self.getNumBits())
            return column_to_batch(col, dim), True
        # dense vector column: positions are the features; slot f is the bias
        dense = np.asarray(col, dtype=np.float32)
        return dense_to_batch(dense, dense.shape[1] + 1), False

    def _train_setup(self, table: Table):
        """Everything ``_fit`` resolves BEFORE the numeric train loop:
        (args, batch, y, w, const_idx, init). Factored so the many-models
        plane (``sweep/batched.py``) can prepare rows once per bucket and
        route K candidates through :func:`train_linear_many` while this
        estimator's single-fit path stays the reference semantics."""
        args = self._parse_args()
        batch, is_hashed = self._get_batch(table, num_bits=args.get("num_bits"))
        y = self._label_transform(
            np.asarray(table.column(self.getLabelCol()), dtype=np.float64)
        )
        w = (
            np.asarray(table.column(self.getWeightCol()), dtype=np.float32)
            if self.isSet("weightCol")
            else np.ones(batch.num_rows, dtype=np.float32)
        )
        hash_seed = args.get("hash_seed", self.getHashSeed())
        if args.get("noconstant"):
            const_idx = -1  # --noconstant: no bias feature anywhere
        elif is_hashed:
            # hashed feature space: the constant feature hashes like any other
            const_idx = int(
                mask_bits(
                    np.asarray([murmur32_bytes(CONSTANT_FEATURE, hash_seed)]),
                    int(np.log2(batch.dim)),
                )[0]
            )
        else:
            # dense feature space: the reserved last slot is the bias
            const_idx = batch.dim - 1

        init = None
        if self.isSet("initialModel"):
            init = np.asarray(self.getInitialModel(), dtype=np.float32)
        return args, batch, y, w, const_idx, init

    def _fit(self, table: Table) -> "VowpalWabbitModelBase":
        args, batch, y, w, const_idx, init = self._train_setup(table)

        result = train_linear(
            batch,
            y,
            w,
            loss=args.get("loss", self._default_loss),
            num_passes=args.get("passes", self.getNumPasses()),
            learning_rate=args.get("learning_rate", self.getLearningRate()),
            power_t=args.get("power_t", self.getPowerT()),
            l1=args.get("l1", self.getL1()),
            l2=args.get("l2", self.getL2()),
            batch_size=self.getBatchSize(),
            constant_index=const_idx,
            initial_weights=init,
            quantile_tau=args.get("quantile_tau", 0.5),
            optimizer="ftrl" if args.get("ftrl") else "adagrad",
            ftrl_alpha=args.get("ftrl_alpha", 0.005),
            ftrl_beta=args.get("ftrl_beta", 0.1),
            mesh=self._select_mesh(),
        )
        self._link = args.get("link", "identity")
        model = self._make_model(result, batch.dim, const_idx)
        model.set("linkFunction", self._link)
        model.parent = self
        return model

    def _select_mesh(self):
        import jax

        if len(jax.devices()) <= 1:
            return None
        from mmlspark_tpu.parallel.mesh import best_mesh

        return best_mesh()

    def _make_model(self, result: VWTrainResult, dim: int, const_idx: int):
        raise NotImplementedError


def _prep_rows(
    batch: SparseBatch,
    y: np.ndarray,
    sample_weight: np.ndarray,
    constant_index: int,
    batch_size: int,
    n_shards: int,
):
    """Row layout shared by the single-fit and many-models paths: append
    the constant feature, pad rows to ``n_shards * num_batches *
    batch_size``. Padding rides with zero value/weight so it never moves
    the weights. Returns (idx, val, y, sample_weight, k, num_batches)."""
    n, k = batch.indices.shape

    if constant_index >= 0:
        # append the constant feature to every row
        idx = np.concatenate(
            [batch.indices, np.full((n, 1), constant_index, dtype=np.int32)], axis=1
        )
        val = np.concatenate([batch.values, np.ones((n, 1), dtype=np.float32)], axis=1)
        k += 1
    else:
        idx, val = batch.indices, batch.values

    rows_per_shard = -(-n // n_shards)  # ceil
    num_batches = -(-rows_per_shard // batch_size)
    padded = n_shards * num_batches * batch_size
    pad = padded - n
    if pad:
        idx = np.concatenate([idx, np.zeros((pad, k), dtype=np.int32)])
        val = np.concatenate([val, np.zeros((pad, k), dtype=np.float32)])
        y = np.concatenate([y.astype(np.float32), np.zeros(pad, dtype=np.float32)])
        sample_weight = np.concatenate(
            [sample_weight, np.zeros(pad, dtype=np.float32)]
        )
    else:
        y = y.astype(np.float32)
    return idx, val, y, sample_weight, k, num_batches


def train_linear(
    batch: SparseBatch,
    y: np.ndarray,
    sample_weight: np.ndarray,
    *,
    loss: str,
    num_passes: int,
    learning_rate: float,
    power_t: float,
    l1: float,
    l2: float,
    batch_size: int,
    constant_index: int,
    initial_weights: Optional[np.ndarray] = None,
    quantile_tau: float = 0.5,
    optimizer: str = "adagrad",
    ftrl_alpha: float = 0.005,
    ftrl_beta: float = 0.1,
    mesh: Optional[Any] = None,
) -> VWTrainResult:
    """Adagrad SGD (or FTRL-Proximal, VW ``--ftrl``) over padded
    minibatches; per-pass pmean state averaging across mesh shards (VW
    endPass allreduce). ``constant_index < 0`` = ``--noconstant``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    sw = StopWatch()
    dim = batch.dim
    n = batch.num_rows

    n_shards = int(mesh.shape["data"]) if mesh is not None else 1
    idx, val, y, sample_weight, k, num_batches = _prep_rows(
        batch, y, sample_weight, constant_index, batch_size, n_shards
    )

    w0 = (
        initial_weights.copy()
        if initial_weights is not None
        else np.zeros(dim, dtype=np.float32)
    )

    lr = float(learning_rate)

    def run_pass(weights, acc, bidx, bval, by, bw, t0):
        """One pass over this shard's minibatches. Shapes:
        bidx/bval (num_batches, B, K); by/bw (num_batches, B)."""

        def step(carry, xs):
            weights, acc, t = carry
            bi, bv, yy, ww = xs
            wi = weights[bi]  # (B, K) gather
            margin = jnp.sum(wi * bv, axis=1)
            g_row = _loss_grad(loss, margin, yy, quantile_tau) * ww
            g = g_row[:, None] * bv  # (B, K)
            if l2:
                g = g + l2 * wi * (bv != 0)
            flat_i = bi.reshape(-1)
            flat_g = g.reshape(-1)
            acc = acc.at[flat_i].add(flat_g * flat_g)
            denom = jnp.sqrt(acc[flat_i]) + 1e-6
            step_t = lr if power_t == 0.0 else lr / ((1.0 + t) ** power_t)
            weights = weights.at[flat_i].add(-step_t * flat_g / denom)
            return (weights, acc, t + 1.0), None

        (weights, acc, t0), _ = jax.lax.scan(
            step, (weights, acc, t0), (bidx, bval, by, bw)
        )
        return weights, acc, t0

    def ftrl_w(z, nacc):
        """FTRL-Proximal closed-form weights from the (z, n) accumulators."""
        w = -(z - jnp.sign(z) * l1) / (
            (ftrl_beta + jnp.sqrt(nacc)) / ftrl_alpha + l2
        )
        return jnp.where(jnp.abs(z) > l1, w, 0.0)

    def run_pass_ftrl(z, nacc, bidx, bval, by, bw, t0):
        """FTRL-Proximal (VW --ftrl; McMahan et al.): per-coordinate (z, n)
        state, weights materialized lazily on the touched coordinates."""

        def step(carry, xs):
            z, nacc, t = carry
            bi, bv, yy, ww = xs
            zi, ni = z[bi], nacc[bi]  # (B, K) gathers
            wi = ftrl_w(zi, ni)
            margin = jnp.sum(wi * bv, axis=1)
            g = (_loss_grad(loss, margin, yy, quantile_tau) * ww)[:, None] * bv
            sigma = (jnp.sqrt(ni + g * g) - jnp.sqrt(ni)) / ftrl_alpha
            flat_i = bi.reshape(-1)
            z = z.at[flat_i].add((g - sigma * wi).reshape(-1))
            nacc = nacc.at[flat_i].add((g * g).reshape(-1))
            return (z, nacc, t + 1.0), None

        (z, nacc, t0), _ = jax.lax.scan(step, (z, nacc, t0), (bidx, bval, by, bw))
        return z, nacc, t0

    def fit_fn(idx_s, val_s, y_s, w_s, weights, acc):
        # idx_s etc are this shard's rows: (num_batches*B, K)
        bidx = idx_s.reshape(num_batches, batch_size, k)
        bval = val_s.reshape(num_batches, batch_size, k)
        by = y_s.reshape(num_batches, batch_size)
        bw = w_s.reshape(num_batches, batch_size)
        t = jnp.zeros(())
        if optimizer == "ftrl":
            # warm start: invert the closed form at n=0 (ignoring l1)
            z = -weights * (ftrl_beta / ftrl_alpha + l2)
            nacc = acc
            for _ in range(num_passes):
                z, nacc, t = run_pass_ftrl(z, nacc, bidx, bval, by, bw, t)
                if mesh is not None:
                    z = jax.lax.pmean(z, "data")
                    nacc = jax.lax.pmean(nacc, "data")
            # l1 lives inside the closed form — no extra lazy shrink
            return ftrl_w(z, nacc), nacc
        for _ in range(num_passes):
            weights, acc, t = run_pass(weights, acc, bidx, bval, by, bw, t)
            if mesh is not None:
                weights = jax.lax.pmean(weights, "data")
                acc = jax.lax.pmean(acc, "data")
        if l1:
            weights = jnp.sign(weights) * jnp.maximum(jnp.abs(weights) - l1, 0.0)
        return weights, acc

    with sw.measure():
        if mesh is None:
            fitted, _ = jax.jit(fit_fn)(
                jnp.asarray(idx), jnp.asarray(val), jnp.asarray(y),
                jnp.asarray(sample_weight), jnp.asarray(w0),
                jnp.zeros(dim, dtype=jnp.float32),
            )
        else:
            shard = jax.shard_map(
                fit_fn,
                mesh=mesh,
                in_specs=(P("data"), P("data"), P("data"), P("data"), P(), P()),
                out_specs=(P(), P()),
                check_vma=False,
            )
            fitted, _ = jax.jit(shard)(
                jnp.asarray(idx), jnp.asarray(val), jnp.asarray(y),
                jnp.asarray(sample_weight), jnp.asarray(w0),
                jnp.zeros(dim, dtype=jnp.float32),
            )
        fitted = np.asarray(jax.block_until_ready(fitted))

    stats = {
        "rows": int(n),
        "passes": int(num_passes),
        "learn_time_s": sw.elapsed_s,
        "shards": n_shards,
        "ipass_loss": None,
    }
    return VWTrainResult(weights=fitted, stats=stats)


#: compiled many-models fit programs, keyed on the trace-shaping statics
#: (everything else — shapes, lr/power_t/l1/l2 — is traced data)
_MANY_FIT_CACHE: dict = {}


def _make_fit_many(loss, num_passes, optimizer, quantile_tau, ftrl_alpha,
                   ftrl_beta):
    """The vmapped VW fit: one candidate's whole SGD run as a function of
    TRACED (lr, power_t, l1, l2) scalars, vmapped over a leading candidate
    axis. The minibatch stream (bidx/bval/by/bw) is shared across
    candidates (in_axes=None — one device copy). The regularization terms
    are applied UNCONDITIONALLY (the sequential path branches on Python
    truthiness): at 0.0 each form is the exact identity — ``g + 0*...``,
    ``lr/(1+t)**0 == lr``, ``sign(w)*max(|w|-0, 0) == w`` — so a batched
    candidate matches its :func:`train_linear` fit."""
    import jax
    import jax.numpy as jnp

    def fit_one(bidx, bval, by, bw, weights, acc, lr, power_t, l1, l2):
        def step(carry, xs):
            weights, acc, t = carry
            bi, bv, yy, ww = xs
            wi = weights[bi]  # (B, K) gather
            margin = jnp.sum(wi * bv, axis=1)
            g_row = _loss_grad(loss, margin, yy, quantile_tau) * ww
            g = g_row[:, None] * bv  # (B, K)
            g = g + l2 * wi * (bv != 0)
            flat_i = bi.reshape(-1)
            flat_g = g.reshape(-1)
            acc = acc.at[flat_i].add(flat_g * flat_g)
            denom = jnp.sqrt(acc[flat_i]) + 1e-6
            step_t = lr / ((1.0 + t) ** power_t)
            weights = weights.at[flat_i].add(-step_t * flat_g / denom)
            return (weights, acc, t + 1.0), None

        def ftrl_w(z, nacc):
            w = -(z - jnp.sign(z) * l1) / (
                (ftrl_beta + jnp.sqrt(nacc)) / ftrl_alpha + l2
            )
            return jnp.where(jnp.abs(z) > l1, w, 0.0)

        def step_ftrl(carry, xs):
            z, nacc, t = carry
            bi, bv, yy, ww = xs
            zi, ni = z[bi], nacc[bi]
            wi = ftrl_w(zi, ni)
            margin = jnp.sum(wi * bv, axis=1)
            g = (_loss_grad(loss, margin, yy, quantile_tau) * ww)[:, None] * bv
            sigma = (jnp.sqrt(ni + g * g) - jnp.sqrt(ni)) / ftrl_alpha
            flat_i = bi.reshape(-1)
            z = z.at[flat_i].add((g - sigma * wi).reshape(-1))
            nacc = nacc.at[flat_i].add((g * g).reshape(-1))
            return (z, nacc, t + 1.0), None

        t = jnp.zeros(())
        if optimizer == "ftrl":
            z = -weights * (ftrl_beta / ftrl_alpha + l2)
            nacc = acc
            for _ in range(num_passes):
                (z, nacc, t), _ = jax.lax.scan(
                    step_ftrl, (z, nacc, t), (bidx, bval, by, bw)
                )
            return ftrl_w(z, nacc)
        for _ in range(num_passes):
            (weights, acc, t), _ = jax.lax.scan(
                step, (weights, acc, t), (bidx, bval, by, bw)
            )
        return jnp.sign(weights) * jnp.maximum(jnp.abs(weights) - l1, 0.0)

    return jax.jit(jax.vmap(
        fit_one, in_axes=(None, None, None, None, 0, 0, 0, 0, 0, 0)
    ))


def train_linear_many(
    batch: SparseBatch,
    y: np.ndarray,
    sample_weight: np.ndarray,
    *,
    loss: str,
    num_passes: int,
    learning_rates,
    power_ts,
    l1s,
    l2s,
    batch_size: int,
    constant_index: int,
    initial_weights: Optional[np.ndarray] = None,
    quantile_tau: float = 0.5,
    optimizer: str = "adagrad",
    ftrl_alpha: float = 0.005,
    ftrl_beta: float = 0.1,
) -> "list[VWTrainResult]":
    """Train K VW candidates in ONE compiled program (the many-models
    plane). Candidates share the data, loss, pass count, batch size, and
    optimizer — the shape-bucket statics — and differ only in the traced
    (learning_rate, power_t, l1, l2) lanes. Single device only (the
    sweep's gang mode shards BUCKETS across processes instead)."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.observability.profiler import get_profiler

    K = len(learning_rates)
    if not (K == len(power_ts) == len(l1s) == len(l2s)):
        raise ValueError("per-candidate hyperparameter stacks disagree on K")
    sw = StopWatch()
    dim = batch.dim
    n = batch.num_rows
    idx, val, y, sample_weight, k, num_batches = _prep_rows(
        batch, y, sample_weight, constant_index, batch_size, 1
    )
    w0 = (
        initial_weights.copy()
        if initial_weights is not None
        else np.zeros(dim, dtype=np.float32)
    )

    ckey = (loss, int(num_passes), optimizer, float(quantile_tau),
            float(ftrl_alpha), float(ftrl_beta))
    fit = _MANY_FIT_CACHE.get(ckey)
    if fit is None:
        fit = _MANY_FIT_CACHE[ckey] = _make_fit_many(*ckey)

    bidx = jnp.asarray(idx.reshape(num_batches, batch_size, k))
    bval = jnp.asarray(val.reshape(num_batches, batch_size, k))
    by = jnp.asarray(y.reshape(num_batches, batch_size))
    bw = jnp.asarray(sample_weight.reshape(num_batches, batch_size))
    weights0 = jnp.asarray(np.broadcast_to(w0[None], (K, dim)).copy())
    acc0 = jnp.zeros((K, dim), jnp.float32)

    fit = get_profiler().wrap(fit, "vw.fit_many", cost_analysis=False)
    with sw.measure():
        fitted = fit(
            bidx, bval, by, bw, weights0, acc0,
            jnp.asarray(np.asarray(learning_rates, np.float32)),
            jnp.asarray(np.asarray(power_ts, np.float32)),
            jnp.asarray(np.asarray(l1s, np.float32)),
            jnp.asarray(np.asarray(l2s, np.float32)),
        )
        fitted = np.asarray(jax.block_until_ready(fitted))

    results = []
    for ki in range(K):
        stats = {
            "rows": int(n),
            "passes": int(num_passes),
            "learn_time_s": sw.elapsed_s,
            "shards": 1,
            "ipass_loss": None,
        }
        results.append(VWTrainResult(weights=fitted[ki], stats=stats))
    return results


class VowpalWabbitModelBase(HasFeaturesCol, HasPredictionCol, Model):
    """Shared model: weights + raw margin computation
    (``VowpalWabbitBaseModel.scala``)."""

    modelWeights = Param("Fitted weight vector", is_complex=True)
    sparseDim = Param("Feature-space size", default=0, converter=to_int)
    constantIndex = Param("Bias feature index (-1 = trained --noconstant)", default=0, converter=to_int)
    numBits = Param("log2 feature-space size for dense inputs", default=18, converter=to_int)
    linkFunction = Param("Prediction link (--link): identity or logistic", default="identity", converter=to_str)

    def _margins(self, table: Table) -> np.ndarray:
        col = table.column(self.getFeaturesCol())
        w = np.asarray(self.getModelWeights())
        if col.dtype == object:
            batch = column_to_batch(col, len(w))
        else:
            batch = dense_to_batch(np.asarray(col, dtype=np.float32), len(w))
        m = (w[batch.indices] * batch.values).sum(axis=1)
        ci = self.getConstantIndex()
        return m if ci < 0 else m + w[ci]

    def _apply_link(self, m: np.ndarray) -> np.ndarray:
        if self.getLinkFunction() == "logistic":
            return 1.0 / (1.0 + np.exp(-m))
        return m

    def get_performance_statistics(self) -> Table:
        """Diagnostics DataFrame analogue (VowpalWabbitBase.scala:367-391)."""
        stats = self.getTrainingStats() if self.isSet("trainingStats") else {}
        return Table({k: [v] for k, v in stats.items() if v is not None})

    trainingStats = Param("Training diagnostics", is_complex=True)
