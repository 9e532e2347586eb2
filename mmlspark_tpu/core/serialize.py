"""Stage persistence with a per-type complex-value serializer registry.

Re-design of the reference's ComplexParam machinery
(``core/serialize/ComplexParam.scala:13-34``,
``org/apache/spark/ml/Serializer.scala:21-130``): JSON-simple params go into
``metadata.json``; complex values (arrays, pytrees, nested stages, Tables,
callables) are written next to the metadata by type-dispatched writers, each
directory self-describing via a ``_type`` tag so loading needs no schema.
"""

from __future__ import annotations

import json
import os
import pickle
import shutil
from typing import Any, Callable, Dict, List, Tuple

import numpy as np

from mmlspark_tpu.core.params import lookup_class
from mmlspark_tpu.data.table import Table

FORMAT_VERSION = 1

_JSON_SIMPLE = (type(None), bool, int, float, str)


def _is_json_simple(v: Any) -> bool:
    if isinstance(v, _JSON_SIMPLE):
        return True
    if isinstance(v, (list, tuple)):
        return all(_is_json_simple(x) for x in v)
    if isinstance(v, dict):
        return all(isinstance(k, str) and _is_json_simple(x) for k, x in v.items())
    return False


# ---------------------------------------------------------------------------
# Value writers/readers
# ---------------------------------------------------------------------------

def _write_ndarray(value: np.ndarray, path: str) -> None:
    np.save(
        os.path.join(path, "value.npy"), value, allow_pickle=value.dtype == object
    )


def _read_ndarray(path: str) -> np.ndarray:
    return np.load(os.path.join(path, "value.npy"), allow_pickle=True)


def _write_pytree(value: Any, path: str) -> None:
    """Arbitrary pytree of arrays/leaves — flattened to npz + structure pickle."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(value)
    leaves = [np.asarray(l) for l in leaves]  # one fetch a leaf
    np.savez(
        os.path.join(path, "leaves.npz"),
        **{f"leaf_{i}": l for i, l in enumerate(leaves)},
    )
    with open(os.path.join(path, "treedef.pkl"), "wb") as f:
        pickle.dump(treedef, f)
    # npz keeps an extension dtype (bfloat16 weights) only as raw 2-byte voids
    extended = {str(i): str(l.dtype) for i, l in enumerate(leaves) if l.dtype.kind == "V"}
    if extended:
        with open(os.path.join(path, "extended_dtypes.json"), "w") as f:
            json.dump(extended, f)


def _read_pytree(path: str) -> Any:
    import jax

    with np.load(os.path.join(path, "leaves.npz"), allow_pickle=True) as z:
        leaves = [z[f"leaf_{i}"] for i in range(len(z.files))]
    extended = os.path.join(path, "extended_dtypes.json")
    if os.path.exists(extended):
        import ml_dtypes

        with open(extended) as f:
            for i, name in json.load(f).items():
                leaves[int(i)] = leaves[int(i)].view(getattr(ml_dtypes, name))
    with open(os.path.join(path, "treedef.pkl"), "rb") as f:
        treedef = pickle.load(f)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _write_table(value: Table, path: str) -> None:
    cols = value.to_dict()
    np.savez(
        os.path.join(path, "columns.npz"),
        **{k: v for k, v in cols.items() if v.dtype != object},
    )
    obj_cols = {k: v for k, v in cols.items() if v.dtype == object}
    with open(os.path.join(path, "object_columns.pkl"), "wb") as f:
        pickle.dump(obj_cols, f)
    with open(os.path.join(path, "table_meta.json"), "w") as f:
        json.dump(
            {
                "num_partitions": value.num_partitions,
                "order": value.columns,
                "metadata": {k: value.metadata(k) for k in value.columns if value.metadata(k)},
            },
            f,
        )


def _read_table(path: str) -> Table:
    with open(os.path.join(path, "table_meta.json")) as f:
        meta = json.load(f)
    cols: Dict[str, np.ndarray] = {}
    with np.load(os.path.join(path, "columns.npz")) as z:
        for k in z.files:
            cols[k] = z[k]
    with open(os.path.join(path, "object_columns.pkl"), "rb") as f:
        cols.update(pickle.load(f))
    ordered = {k: cols[k] for k in meta["order"]}
    return Table(
        ordered, metadata=meta.get("metadata") or {}, num_partitions=meta["num_partitions"]
    )


def _write_stage(value: Any, path: str) -> None:
    save_stage(value, os.path.join(path, "stage"), overwrite=True)


def _read_stage(path: str) -> Any:
    return load_stage(os.path.join(path, "stage"))


def _write_stage_list(value: List[Any], path: str) -> None:
    with open(os.path.join(path, "count.json"), "w") as f:
        json.dump(len(value), f)
    for i, stage in enumerate(value):
        save_stage(stage, os.path.join(path, f"stage_{i}"), overwrite=True)


def _read_stage_list(path: str) -> List[Any]:
    with open(os.path.join(path, "count.json")) as f:
        n = json.load(f)
    return [load_stage(os.path.join(path, f"stage_{i}")) for i in range(n)]


def _write_pickle(value: Any, path: str) -> None:
    # cloudpickle handles closures/lambdas — the UDFParam case
    # (org/apache/spark/ml/param/UDFParam.scala uses Java closure serde).
    import cloudpickle

    with open(os.path.join(path, "value.pkl"), "wb") as f:
        cloudpickle.dump(value, f)


def _read_pickle(path: str) -> Any:
    with open(os.path.join(path, "value.pkl"), "rb") as f:
        return pickle.load(f)


def _is_stage(v: Any) -> bool:
    from mmlspark_tpu.core.pipeline import PipelineStage

    return isinstance(v, PipelineStage)


def _is_jax_array(v: Any) -> bool:
    try:
        import jax

        return isinstance(v, jax.Array)
    except ImportError:  # pragma: no cover
        return False


# type tag -> (predicate, writer, reader); checked in order.
_SERIALIZERS: List[Tuple[str, Callable[[Any], bool], Callable, Callable]] = [
    ("stage", _is_stage, _write_stage, _read_stage),
    (
        "stage_list",
        lambda v: isinstance(v, (list, tuple)) and len(v) > 0 and all(_is_stage(x) for x in v),
        _write_stage_list,
        _read_stage_list,
    ),
    ("table", lambda v: isinstance(v, Table), _write_table, _read_table),
    ("ndarray", lambda v: isinstance(v, np.ndarray), _write_ndarray, _read_ndarray),
    ("ndarray", _is_jax_array, lambda v, p: _write_ndarray(np.asarray(v), p), _read_ndarray),
    ("json", _is_json_simple, lambda v, p: _write_json_value(v, p), lambda p: _read_json_value(p)),
    (
        "pytree",
        lambda v: isinstance(v, (dict, list, tuple)) and _pytree_of_arrays(v),
        _write_pytree,
        _read_pytree,
    ),
    ("pickle", lambda v: True, _write_pickle, _read_pickle),
]

_READERS = {
    "stage": _read_stage,
    "stage_list": _read_stage_list,
    "table": _read_table,
    "ndarray": _read_ndarray,
    "json": lambda p: _read_json_value(p),
    "pytree": _read_pytree,
    "pickle": _read_pickle,
}


def _pytree_of_arrays(v: Any) -> bool:
    try:
        import jax
    except ImportError:  # pragma: no cover - fall through to pickle
        return False

    leaves = jax.tree_util.tree_leaves(v)
    return len(leaves) > 0 and all(
        isinstance(l, (np.ndarray, np.generic, int, float, bool)) or _is_jax_array(l)
        for l in leaves
    )


def _write_json_value(v: Any, path: str) -> None:
    with open(os.path.join(path, "value.json"), "w") as f:
        json.dump(v, f)


def _read_json_value(path: str) -> Any:
    with open(os.path.join(path, "value.json")) as f:
        return json.load(f)


def save_value(value: Any, path: str) -> None:
    os.makedirs(path, exist_ok=True)
    for tag, pred, writer, _ in _SERIALIZERS:
        if pred(value):
            with open(os.path.join(path, "_type"), "w") as f:
                f.write(tag)
            writer(value, path)
            return
    raise TypeError(f"no serializer for {type(value)}")  # pragma: no cover


def load_value(path: str) -> Any:
    with open(os.path.join(path, "_type")) as f:
        tag = f.read().strip()
    return _READERS[tag](path)


# ---------------------------------------------------------------------------
# Stage save/load
# ---------------------------------------------------------------------------

def save_stage(stage: Any, path: str, overwrite: bool = True) -> None:
    if os.path.exists(path):
        if not overwrite:
            raise FileExistsError(path)
        shutil.rmtree(path)
    os.makedirs(path)

    simple: Dict[str, Any] = {}
    complex_names: List[str] = []
    for name, spec in stage.params.items():
        if not stage.isSet(name):
            continue
        value = stage.get(name)
        if not spec.is_complex and _is_json_simple(value):
            simple[name] = list(value) if isinstance(value, tuple) else value
        else:
            complex_names.append(name)
            save_value(value, os.path.join(path, "params", name))

    meta = {
        "format_version": FORMAT_VERSION,
        "class": f"{type(stage).__module__}.{type(stage).__qualname__}",
        "uid": stage.uid,
        "params": simple,
        "complex_params": complex_names,
    }
    with open(os.path.join(path, "metadata.json"), "w") as f:
        json.dump(meta, f, indent=2)
    stage._save_extra(path)


def load_stage(path: str) -> Any:
    with open(os.path.join(path, "metadata.json")) as f:
        meta = json.load(f)
    cls = lookup_class(meta["class"])
    stage = cls.__new__(cls)
    stage.uid = meta["uid"]
    stage._paramMap = {}
    for k, v in meta["params"].items():
        stage.set(k, v)
    for name in meta["complex_params"]:
        stage._paramMap[name] = load_value(os.path.join(path, "params", name))
    stage._load_extra(path)
    return stage
