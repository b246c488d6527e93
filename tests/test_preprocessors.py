"""AIR preprocessors — distributed fit, vectorized transform.

Reference tier: python/ray/data/tests/test_preprocessors.py (scalers,
encoders, imputer, concatenator, chain; fit stats computed over the
distributed dataset, transform applied to datasets and raw batches).
"""
import numpy as np
import pytest

# Every case fits or transforms four blocks on `ray_start_regular`'s four
# CPUs: ~2 s alone. A fit on a transformed dataset can deadlock the node's
# leases (ROADMAP D15: the downstream tasks hold all four CPUs while they
# wait for the upstream stage's results; the runtime's to repair — seen in
# `test_chain_fits_on_prior_output` and `test_concatenator_and_batch_mapper`),
# so a recurrence costs a worker 60 s, not the default 300.
pytestmark = pytest.mark.limit(
    60, reason="four blocks on four CPUs, ~2 s alone; a hang is D15's "
               "lease deadlock")


def _toy(ray, n=100, parallelism=4):
    from ray_tpu import data

    rows = [{"x": float(i), "y": float(i % 10), "cat": ["a", "b", "c"][i % 3]}
            for i in range(n)]
    return data.from_items(rows, parallelism=parallelism)


def test_standard_scaler(ray_start_regular):
    from ray_tpu.air import StandardScaler

    ds = _toy(ray_start_regular)
    sc = StandardScaler(columns=["x"]).fit(ds)
    out = sc.transform(ds).to_pandas()
    assert abs(out["x"].mean()) < 1e-9
    assert abs(out["x"].std(ddof=0) - 1.0) < 1e-6
    # raw-batch transform matches
    b = sc.transform_batch({"x": np.array([0.0, 99.0])})
    assert abs(b["x"][0] - out["x"].min()) < 1e-9


def test_minmax_scaler_and_not_fitted(ray_start_regular):
    from ray_tpu.air import MinMaxScaler, PreprocessorNotFittedError

    ds = _toy(ray_start_regular)
    sc = MinMaxScaler(columns=["x", "y"])
    with pytest.raises(PreprocessorNotFittedError):
        sc.transform_batch({"x": np.array([1.0])})
    out = sc.fit_transform(ds).to_pandas()
    assert out["x"].min() == 0.0 and out["x"].max() == 1.0
    assert out["y"].min() == 0.0 and out["y"].max() == 1.0


def test_ordinal_and_onehot_encoders(ray_start_regular):
    from ray_tpu.air import OneHotEncoder, OrdinalEncoder

    ds = _toy(ray_start_regular, n=30)
    enc = OrdinalEncoder(columns=["cat"]).fit(ds)
    out = enc.transform(ds).to_pandas()
    assert set(out["cat"].tolist()) == {0, 1, 2}
    # unseen category -> -1
    b = enc.transform_batch({"cat": np.array(["a", "zzz"])})
    assert b["cat"].tolist() == [0, -1]

    oh = OneHotEncoder(columns=["cat"]).fit(ds)
    out = oh.transform(ds).to_pandas()
    assert {"cat_a", "cat_b", "cat_c"} <= set(out.columns)
    assert (out[["cat_a", "cat_b", "cat_c"]].sum(axis=1) == 1).all()


def test_label_encoder_round_trip(ray_start_regular):
    from ray_tpu.air import LabelEncoder

    ds = _toy(ray_start_regular, n=30)
    le = LabelEncoder("cat").fit(ds)
    b = le.transform_batch({"cat": np.array(["b", "a", "c"])})
    back = le.inverse_transform_batch(b)
    assert back["cat"].tolist() == ["b", "a", "c"]


def test_simple_imputer(ray_start_regular):
    from ray_tpu import data
    from ray_tpu.air import SimpleImputer

    rows = [{"v": float(i)} for i in range(10)]
    rows[3]["v"] = float("nan")
    rows[7]["v"] = float("nan")
    ds = data.from_items(rows, parallelism=3)
    imp = SimpleImputer(columns=["v"], strategy="mean").fit(ds)
    out = imp.transform(ds).to_pandas()
    assert not out["v"].isna().any()
    clean_mean = np.mean([i for i in range(10) if i not in (3, 7)])
    assert abs(out["v"][3] - clean_mean) < 1e-9

    const = SimpleImputer(columns=["v"], strategy="constant",
                          fill_value=-1.0)
    b = const.transform_batch({"v": np.array([1.0, float("nan")])})
    assert b["v"].tolist() == [1.0, -1.0]


def test_concatenator_and_batch_mapper(ray_start_regular):
    from ray_tpu.air import BatchMapper, Concatenator

    ds = _toy(ray_start_regular, n=20)
    out = Concatenator(columns=["x", "y"]).transform(ds)
    batch = next(out.iter_batches(batch_size=20))
    assert batch["features"].shape == (20, 2)
    assert batch["features"].dtype == np.float32

    bm = BatchMapper(lambda b: {**b, "x2": np.asarray(b["x"]) * 2})
    out = bm.transform(ds).to_pandas()
    assert (out["x2"] == out["x"] * 2).all()


def test_chain_fits_on_prior_output(ray_start_regular):
    """Chain semantics: each stage fits on the PREVIOUS stage's output —
    the scaler here sees imputed values, not NaNs."""
    from ray_tpu import data
    from ray_tpu.air import Chain, Concatenator, SimpleImputer, StandardScaler

    rows = [{"v": float(i), "w": float(i * 2)} for i in range(20)]
    rows[5]["v"] = float("nan")
    ds = data.from_items(rows, parallelism=4)
    chain = Chain(
        SimpleImputer(columns=["v"], strategy="mean"),
        StandardScaler(columns=["v", "w"]),
        Concatenator(columns=["v", "w"]),
    ).fit(ds)
    out = chain.transform(ds)
    batch = next(out.iter_batches(batch_size=20))
    assert batch["features"].shape == (20, 2)
    assert np.isfinite(batch["features"]).all()
    # raw-batch path runs the same pipeline
    b = chain.transform_batch({"v": np.array([1.0]), "w": np.array([2.0])})
    assert b["features"].shape == (1, 2)
