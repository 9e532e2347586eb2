"""BASELINE config 4: VowpalWabbit text classification, TPU vs CPU.

Amazon-reviews-like workload synthesized locally (zero-egress rig): a
vocabulary with class-dependent word frequencies, murmur-hashed bag-of-words
featurization (VowpalWabbitFeaturizer, the reference's "Java-side hashing"
path re-done in C++/numpy), then the jitted adagrad-SGD learner vs sklearn's
SGDClassifier(log_loss) on the identical hashed design matrix — accuracy
parity is part of the contract.

Prints ONE JSON line and writes it to benchmarks/vw_text_bench.json:

    python benchmarks/vw_text_bench.py
"""

import json
import os
import time

import numpy as np

N_DOCS = int(os.environ.get("VW_BENCH_DOCS", 200_000))
N_TEST = 20_000
VOCAB = 5000
DOC_LEN = 30
NUM_BITS = 18
PASSES = 3


def make_corpus(n, seed=0):
    rng = np.random.default_rng(seed)
    words = np.array([f"w{i}" for i in range(VOCAB)])
    # class-dependent word distributions (Zipf-ish base, tilted per class)
    base = 1.0 / np.arange(1, VOCAB + 1)
    tilt = rng.normal(size=VOCAB) * 0.7
    p_pos = base * np.exp(tilt)
    p_neg = base * np.exp(-tilt)
    p_pos /= p_pos.sum()
    p_neg /= p_neg.sum()
    y = rng.integers(0, 2, size=n).astype(np.float64)
    docs = np.empty(n, dtype=object)
    pos_draw = rng.choice(VOCAB, size=(n, DOC_LEN), p=p_pos)
    neg_draw = rng.choice(VOCAB, size=(n, DOC_LEN), p=p_neg)
    for i in range(n):
        toks = pos_draw[i] if y[i] > 0 else neg_draw[i]
        docs[i] = " ".join(words[toks])
    return docs, y


def main():
    from mmlspark_tpu.core.device import configure_compile_cache

    configure_compile_cache()

    from mmlspark_tpu.data.table import Table
    from mmlspark_tpu.vw import VowpalWabbitClassifier, VowpalWabbitFeaturizer

    import jax

    backend = jax.default_backend()
    docs, y = make_corpus(N_DOCS + N_TEST)
    t_all = Table({"text": docs, "label": y})

    feat = VowpalWabbitFeaturizer(
        inputCols=["text"], outputCol="features", numBits=NUM_BITS,
        stringSplit=True,
    )
    t0 = time.perf_counter()
    feats = feat.transform(t_all)
    featurize_s = time.perf_counter() - t0

    # combined featurizer + namespace-crossing pass (both column-vectorized)
    from mmlspark_tpu.vw import VowpalWabbitInteractions

    feat2 = VowpalWabbitFeaturizer(
        inputCols=["text"], outputCol="features2", numBits=NUM_BITS,
        stringSplit=True, prefixStringsWithColumnName=False,
    )
    inter = VowpalWabbitInteractions(
        inputCols=["features", "features2"], outputCol="crossed",
        numBits=NUM_BITS,
    )
    inter_docs = min(20_000, N_DOCS)
    t0 = time.perf_counter()
    inter.transform(feat2.transform(feats.head(inter_docs)))
    featurize_inter_s = time.perf_counter() - t0

    tr = feats.slice(0, N_DOCS)
    te = feats.slice(N_DOCS, N_DOCS + N_TEST)
    yte = y[N_DOCS:]

    VowpalWabbitClassifier(numPasses=PASSES, batchSize=1024).fit(tr)  # compile warm-up
    t0 = time.perf_counter()
    m = VowpalWabbitClassifier(numPasses=PASSES, batchSize=1024).fit(tr)
    fit_s = time.perf_counter() - t0
    acc_tpu = float((m.transform(te).column("prediction") == yte).mean())

    # CPU baseline: sklearn SGD logistic on the SAME hashed sparse matrix
    from scipy.sparse import csr_matrix
    from sklearn.linear_model import SGDClassifier

    def to_csr(tbl):
        from mmlspark_tpu.data.sparse import SparseRows

        col = tbl.column("features")
        if isinstance(col, SparseRows):  # CSR column: three array handoffs
            return csr_matrix(
                (col.values, col.indices, col.indptr),
                shape=(tbl.num_rows, 1 << NUM_BITS),
            )
        lens = np.array([len(rv[0]) for rv in col])
        indptr = np.concatenate([[0], np.cumsum(lens)])
        cols = np.concatenate([np.asarray(rv[0]) for rv in col])
        vals = np.concatenate([np.asarray(rv[1]) for rv in col])
        return csr_matrix(
            (vals, cols, indptr), shape=(tbl.num_rows, 1 << NUM_BITS)
        )

    Xtr, Xte = to_csr(tr), to_csr(te)
    ytr = y[:N_DOCS]
    times = []
    for run in range(3):
        sgd = SGDClassifier(loss="log_loss", max_iter=PASSES, tol=None,
                            random_state=run)
        t0 = time.perf_counter()
        sgd.fit(Xtr, ytr)
        times.append(time.perf_counter() - t0)
    cpu_s = float(np.median(times))
    acc_cpu = float((sgd.predict(Xte) == yte).mean())

    out = {
        "metric": f"vw_text_rows_per_sec_{backend}",
        "value": round(N_DOCS * PASSES / fit_s, 1),
        "unit": "rows*passes/sec",
        "vs_baseline": round(cpu_s / fit_s, 3),
        "tpu_fit_secs": round(fit_s, 3),
        "cpu_fit_secs": round(cpu_s, 3),
        "featurize_secs": round(featurize_s, 3),
        "featurize_interactions_secs": round(featurize_inter_s, 3),
        "featurize_interactions_docs": inter_docs,
        "acc_tpu": round(acc_tpu, 4),
        "acc_cpu": round(acc_cpu, 4),
        "docs": N_DOCS,
        "num_bits": NUM_BITS,
        "cpu_engine": "sklearn.SGDClassifier(log_loss, median of 3)",
    }
    print(json.dumps(out))
    with open(os.path.join(os.path.dirname(__file__), "vw_text_bench.json"), "w") as f:
        json.dump(out, f)


if __name__ == "__main__":
    main()
