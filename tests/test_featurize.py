"""Featurization (reference ``featurize/`` suites — SURVEY.md §2.10)."""

import numpy as np
import pytest

from mmlspark_tpu.data.table import Table
from mmlspark_tpu.featurize import (
    AssembleFeatures,
    CleanMissingData,
    DataConversion,
    Featurize,
    IndexToValue,
    MultiNGram,
    PageSplitter,
    TextFeaturizer,
    ValueIndexer,
)


def test_value_indexer_roundtrip():
    t = Table({"cat": np.array(["b", "a", "b", "c"], dtype=object)})
    model = ValueIndexer(inputCol="cat", outputCol="idx").fit(t)
    out = model.transform(t)
    assert list(out["idx"]) == [1, 0, 1, 2]
    assert out.metadata("idx")["categorical"]
    back = IndexToValue(inputCol="idx", outputCol="orig").transform(out)
    assert list(back["orig"]) == ["b", "a", "b", "c"]
    # Unseen value -> unknown bucket -> None on inverse.
    t2 = Table({"cat": np.array(["a", "zzz"], dtype=object)})
    out2 = model.transform(t2)
    assert list(out2["idx"]) == [0, 3]
    assert IndexToValue(inputCol="idx", outputCol="v").transform(out2)["v"][1] is None


def test_value_indexer_numeric():
    t = Table({"x": np.array([10, 5, 10, 7])})
    model = ValueIndexer(inputCol="x", outputCol="idx").fit(t)
    assert list(model.transform(t)["idx"]) == [2, 0, 2, 1]


def test_clean_missing_data():
    t = Table(
        {
            "a": np.array([1.0, np.nan, 3.0]),
            "b": np.array([np.nan, 4.0, 8.0]),
        }
    )
    model = CleanMissingData(inputCols=["a", "b"], cleaningMode="Mean").fit(t)
    out = model.transform(t)
    np.testing.assert_allclose(out["a"], [1.0, 2.0, 3.0])
    np.testing.assert_allclose(out["b"], [6.0, 4.0, 8.0])
    model = CleanMissingData(
        inputCols=["a"], cleaningMode="Custom", customValue=-1
    ).fit(t)
    np.testing.assert_allclose(model.transform(t)["a"], [1.0, -1.0, 3.0])
    model = CleanMissingData(inputCols=["a"], cleaningMode="Median").fit(t)
    np.testing.assert_allclose(model.transform(t)["a"], [1.0, 2.0, 3.0])


def test_data_conversion():
    t = Table({"x": np.array(["1", "2"], dtype=object), "y": np.array([1.5, 2.5])})
    out = DataConversion(inputCols=["x"], convertTo="double").transform(t)
    assert out["x"].dtype == np.float64
    out = DataConversion(inputCols=["y"], convertTo="string").transform(t)
    assert out["y"].dtype == object and out["y"][0] == "1.5"
    out = DataConversion(inputCols=["x"], convertTo="toCategorical").transform(t)
    assert out.metadata("x").get("categorical")
    back = DataConversion(inputCols=["x"], convertTo="clearCategorical").transform(out)
    assert list(back["x"]) == ["1", "2"]


def test_assemble_features():
    t = Table(
        {
            "num": np.array([1.0, 2.0]),
            "vec": np.array([[1.0, 2.0], [3.0, 4.0]]),
            "flag": np.array([True, False]),
        }
    )
    out = AssembleFeatures(inputCols=["num", "vec", "flag"]).transform(t)
    np.testing.assert_allclose(
        out["features"], [[1.0, 1.0, 2.0, 1.0], [2.0, 3.0, 4.0, 0.0]]
    )
    with pytest.raises(ValueError):
        AssembleFeatures(inputCols=["s"]).transform(
            Table({"s": np.array(["x", "y"], dtype=object)})
        )


def test_featurize_mixed_columns():
    rng = np.random.default_rng(0)
    n = 50
    t = Table(
        {
            "num": rng.normal(size=n),
            "with_nan": np.where(rng.random(n) < 0.2, np.nan, rng.normal(size=n)),
            "cat": np.array([["red", "green", "blue"][i % 3] for i in range(n)], dtype=object),
            "text": np.array([f"word{i} common tokens here {i%7}" for i in range(n)], dtype=object),
        }
    )
    model = Featurize(
        inputCols=["num", "with_nan", "cat", "text"],
        outputCol="features",
        numberOfFeatures=64,
    ).fit(t)
    out = model.transform(t)
    f = out["features"]
    # 1 numeric + 1 numeric + (3 levels + unknown) one-hot + 64 hash dims.
    assert f.shape == (n, 2 + 4 + 64)
    assert np.isfinite(f).all()
    # Unknown categorical at transform time goes to the unknown slot.
    t2 = Table(
        {
            "num": np.zeros(1),
            "with_nan": np.array([np.nan]),
            "cat": np.array(["violet"], dtype=object),
            "text": np.array(["common tokens"], dtype=object),
        }
    )
    f2 = model.transform(t2)["features"]
    assert f2[0, 2 + 3] == 1.0  # unknown bucket


def test_featurize_single_vector_passthrough():
    t = Table({"vec": np.array([[1.0, 2.0], [3.0, 4.0]])})
    model = Featurize(inputCols=["vec"], outputCol="features").fit(t)
    np.testing.assert_allclose(model.transform(t)["features"], t["vec"])


def test_text_featurizer_idf():
    docs = ["the cat sat", "the dog sat", "a bird flew"]
    t = Table({"text": np.array(docs, dtype=object)})
    model = TextFeaturizer(
        inputCol="text", outputCol="tf", numFeatures=256, useIDF=True
    ).fit(t)
    out = model.transform(t)
    assert out["tf"].shape == (3, 256)
    # 'the' appears in 2/3 docs; its idf weight is below a unique token's.
    assert out["tf"].max() > 0


def test_text_featurizer_ngrams_binary():
    t = Table({"text": np.array(["a b a b", "c d"], dtype=object)})
    model = TextFeaturizer(
        inputCol="text", outputCol="tf", numFeatures=64,
        useNGram=True, nGramLength=2, binary=True, useIDF=False,
    ).fit(t)
    out = model.transform(t)
    assert set(np.unique(out["tf"])) <= {0.0, 1.0}


def test_text_featurizer_token_list_input():
    t = Table({"tokens": [["x", "y"], ["z"]]})
    model = TextFeaturizer(
        inputCol="tokens", outputCol="tf", numFeatures=32, useIDF=False
    ).fit(t)
    assert model.transform(t)["tf"].shape == (2, 32)


def test_multi_ngram():
    t = Table({"tokens": [["a", "b", "c"]]})
    out = MultiNGram(inputCol="tokens", outputCol="grams", lengths=[1, 2, 3]).transform(t)
    assert list(out["grams"][0]) == ["a", "b", "c", "a b", "b c", "a b c"]


def test_page_splitter():
    text = "word " * 100  # 500 chars
    t = Table({"doc": np.array([text.strip()], dtype=object)})
    out = PageSplitter(
        inputCol="doc", outputCol="pages",
        maximumPageLength=100, minimumPageLength=80,
    ).transform(t)
    pages = out["pages"][0]
    assert "".join(pages) == text.strip()
    assert all(len(p) <= 100 for p in pages)
    assert all(len(p) >= 80 for p in pages[:-1])


def test_featurize_serialization(tmp_path):
    t = Table(
        {
            "num": np.arange(5.0),
            "cat": np.array(list("ababa"), dtype=object),
        }
    )
    model = Featurize(inputCols=["num", "cat"], outputCol="features").fit(t)
    model.save(str(tmp_path / "feat"))
    from mmlspark_tpu.core.pipeline import PipelineStage

    loaded = PipelineStage.load(str(tmp_path / "feat"))
    np.testing.assert_allclose(loaded.transform(t)["features"], model.transform(t)["features"])


# -- LMFeaturizer: the decoder's program is built once a process ----------------

_LM = dict(
    hidden_size=32, num_attention_heads=2, num_key_value_heads=1, head_dim=16,
    intermediate_size=48, moe_intermediate_size=16, num_experts=4, num_experts_per_tok=2,
    num_shared_experts=1, num_dense_layers=1, sliding_window=8, rope_theta=10000,
    rms_norm_eps=1e-5, route_scale=2.0, vocab_size=64,
    layer_types=["sliding_attention", "full_attention"], layers=2,
    interpret=True,  # the attention kernel, on a backend that is no TPU
)
_LM_OUTPUTS = {"hidden": "h", "logits": "l", "expert_load": "e"}
_LM_TOKENS = np.random.default_rng(4).integers(0, 64, size=(5, 24)).astype(np.int32)


def _lm_params(seed):
    import jax

    from mmlspark_tpu.models.afmoe import init_afmoe

    return init_afmoe(jax.random.PRNGKey(seed), _LM)


def _lm_transform(params, config, batch=4):
    """(the three output columns, the ``dnn.transform`` span's ``programs_built``)."""
    from mmlspark_tpu.featurize.lm import LMFeaturizer
    from mmlspark_tpu.observability.tracing import get_tracer

    tracer = get_tracer()
    tracer.clear()
    out = LMFeaturizer(outputCols=_LM_OUTPUTS, modelParams=params, modelConfig=config,
                       batchSize=batch).transform(Table({"tokens": _LM_TOKENS}))
    (built,) = [s["tags"]["programs_built"] for s in tracer.export() if s["name"] == "dnn.transform"]
    return {name: np.asarray(out[col]) for name, col in _LM_OUTPUTS.items()}, built


def _lm_direct(params, config):
    """The decoder called with no stage around it, rows padded as the stage pads."""
    import jax

    from mmlspark_tpu.models import afmoe

    apply = getattr(afmoe.afmoe_apply, "uncounted", afmoe.afmoe_apply)  # see counted_decoder
    forward = jax.jit(lambda p, t: apply(p, t, config))
    batches = np.concatenate([_LM_TOKENS, np.zeros((3, 24), np.int32)]).reshape(2, 4, 24)
    out = [forward(params, batch) for batch in batches]
    return {k: np.concatenate([np.asarray(o[k]) for o in out])[:5] for k in _LM_OUTPUTS}


@pytest.fixture()
def counted_decoder(monkeypatch):
    """Every trace of ``afmoe_apply`` through a featurizer built after this."""
    from mmlspark_tpu.models import afmoe

    traces, apply = [], afmoe.afmoe_apply

    def counting(params, tokens, config):
        traces.append(dict(config))
        return apply(params, tokens, config)

    counting.uncounted = apply
    monkeypatch.setattr(afmoe, "afmoe_apply", counting)
    return traces


def test_two_lm_featurizers_of_equal_configuration_trace_the_decoder_once(counted_decoder):
    config = dict(_LM, note="traced once")  # a key no layer reads: this test's own program
    first, second = _lm_params(1), _lm_params(2)
    out, built = _lm_transform(first, dict(config))
    assert built == 1 and len(counted_decoder) == 1
    want = _lm_direct(first, config)
    for name in _LM_OUTPUTS:
        np.testing.assert_array_equal(out[name], want[name])
    # a fresh dict of equal content, a fresh list in it, another featurizer, other weights
    again = dict(reversed(list(config.items())), layer_types=list(config["layer_types"]))
    out, built = _lm_transform(second, again)
    assert built == 0 and len(counted_decoder) == 1
    want = _lm_direct(second, config)
    assert not np.array_equal(out["hidden"], _lm_transform(first, dict(config))[0]["hidden"])
    for name in _LM_OUTPUTS:
        np.testing.assert_array_equal(out[name], want[name])
    assert len(counted_decoder) == 1


@pytest.mark.parametrize("field,value", [
    ("sliding_window", 4), ("route_scale", 1.0), ("layer_types", ["full_attention"] * 2),
    ("product_dtype", "float8_e4m3fn"),
])
def test_each_field_of_the_decoder_configuration_is_in_the_key(counted_decoder, field, value):
    base = dict(_LM, note="one field changed")
    params = _lm_params(3)
    plain, _ = _lm_transform(params, dict(base))
    traced = len(counted_decoder)
    changed = dict(base, **{field: value})
    out, built = _lm_transform(params, changed)
    assert built == 1 and len(counted_decoder) == traced + 1
    assert counted_decoder[-1][field] == value
    want = _lm_direct(params, changed)
    for name in _LM_OUTPUTS:
        np.testing.assert_array_equal(out[name], want[name])
    assert not np.array_equal(out["hidden"], plain["hidden"])
    # and the first configuration still finds its own
    out, built = _lm_transform(params, dict(base))
    assert built == 0 and np.array_equal(out["hidden"], plain["hidden"])


def test_a_configuration_changed_in_place_after_a_call_is_another_key(counted_decoder):
    """The cached function reads its own copy of the configuration, so a
    later trace under it (another batch shape) sees what its key says."""
    config = dict(_LM, note="changed in place", layer_types=list(_LM["layer_types"]))
    params = _lm_params(5)
    first, built = _lm_transform(params, config)
    config["layer_types"][1] = "sliding_attention"  # the caller edits the list it passed
    edited, built = _lm_transform(params, config)
    assert built == 1 and not np.array_equal(edited["hidden"], first["hidden"])
    original = dict(config, layer_types=list(_LM["layer_types"]))
    out, built = _lm_transform(params, original, batch=5)  # found, and traced anew for the shape
    assert built == 0 and counted_decoder[-1]["layer_types"] == _LM["layer_types"]
    np.testing.assert_allclose(out["hidden"], first["hidden"], rtol=2e-2, atol=2e-2)
