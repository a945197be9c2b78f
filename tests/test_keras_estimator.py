"""KerasEstimator tests (ref analog: test_spark_keras.py fit/transform
contract)."""

import numpy as np
import pytest

keras = pytest.importorskip("keras")


def _compiled_model(seed=11):
    keras.utils.set_random_seed(seed)
    m = keras.Sequential([keras.layers.Input((4,)),
                          keras.layers.Dense(8, activation="relu"),
                          keras.layers.Dense(1)])
    m.compile(optimizer=keras.optimizers.Adam(learning_rate=0.05),
              loss="mse")
    return m


def _toy_regression(n=64, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, 4).astype(np.float32)
    w = np.asarray([[1.0], [-2.0], [0.5], [3.0]], np.float32)
    y = x @ w + 0.01 * rng.randn(n, 1).astype(np.float32)
    return x, y


class TestKerasEstimator:
    def test_validation(self):
        from horovod_tpu.orchestrate import KerasEstimator

        with pytest.raises(ValueError, match="compiled"):
            KerasEstimator(model=keras.Sequential(
                [keras.layers.Input((2,)), keras.layers.Dense(1)]))
        with pytest.raises(ValueError, match="requires a compiled"):
            KerasEstimator()

    # ``slow``: 18 s alone and 35 s beside five busy workers, nearly all
    # of it a worker and the driver importing TensorFlow.  What it checks
    # of fit, transform, the store's checkpoint and the handle's save is
    # checked on the two-worker fit below, which stays in tier-1; only the
    # one-worker world is left to the compose test-integration service.
    @pytest.mark.slow
    @pytest.mark.integration
    def test_fit_transform_single_worker(self, tmp_path):
        from horovod_tpu.orchestrate import KerasEstimator

        x, y = _toy_regression()
        est = KerasEstimator(model=_compiled_model(), num_workers=1,
                             epochs=12, batch_size=16,
                             store=str(tmp_path / "store"))
        model = est.fit(x, y)
        assert est.history_ and "loss" in est.history_[0]
        assert est.history_[-1]["loss"] < est.history_[0]["loss"]
        pred = model.transform(x)
        assert pred.shape == (len(x), 1)
        # trains toward the linear target
        mse = float(np.mean((pred - y) ** 2))
        assert mse < 2.0, mse
        assert (tmp_path / "store" / "checkpoint.keras").exists()
        # handle round-trips through keras save
        model.save(str(tmp_path / "final.keras"))

    _two_worker_fit = None

    @pytest.fixture()
    def two_worker_fit(self, tmp_path_factory):
        """ONE fit over two worker processes for the two tests below:
        what each worker returned (through a spy on ``Executor.run``),
        the estimator, the model and the data.  The workers' start-up,
        each importing TensorFlow and jax, is nearly all of the time.
        Kept on the class by hand and not by a class scope, so that the
        fit runs inside a test's time limit."""
        cls = type(self)
        if cls._two_worker_fit is None:
            cls._two_worker_fit = self._fit_two_workers(
                tmp_path_factory.mktemp("keras_two_workers"))
        return cls._two_worker_fit

    @staticmethod
    def _fit_two_workers(tmp_path):
        from horovod_tpu.orchestrate import KerasEstimator
        from horovod_tpu.orchestrate.executor import Executor

        captured = {}
        orig_run = Executor.run

        def spy(self, fn, args=(), kwargs=None, per_rank_args=None):
            captured["results"] = orig_run(
                self, fn, args=args, kwargs=kwargs,
                per_rank_args=per_rank_args)
            return captured["results"]

        x, y = _toy_regression(n=64)
        est = KerasEstimator(model=_compiled_model(), num_workers=2,
                             epochs=10, batch_size=16,
                             validation_split=0.25,
                             store=str(tmp_path / "store"))
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(Executor, "run", spy)
            model = est.fit(x, y)
        return est, model, captured["results"], x, y, tmp_path

    @pytest.mark.integration
    def test_fit_two_workers_matches_contract(self, two_worker_fit):
        """2 worker processes forming ONE world: per-step gradients
        average across ranks (wrapped optimizer), initial state
        broadcast, and both ranks end with IDENTICAL weights — the
        proof the collectives actually ran (fit() itself verifies
        hvd.size()==2 in every worker and raises otherwise)."""
        est, model, _, x, y, tmp_path = two_worker_fit
        pred = model.predict(x)
        mse = float(np.mean((pred - y) ** 2))
        assert mse < 3.0, mse
        assert est.history_ and est.history_[-1]["loss"] < \
            est.history_[0]["loss"]
        assert "val_loss" in est.history_[0]
        assert model.transform(x).shape == (len(x), 1)
        assert (tmp_path / "store" / "checkpoint.keras").exists()
        # handle round-trips through keras save
        model.save(str(tmp_path / "final.keras"))

    @pytest.mark.integration
    def test_two_workers_end_in_sync(self, two_worker_fit):
        """Rank checksums after fit must MATCH — divergent weights mean
        the gradient averaging silently no-opped."""
        res = two_worker_fit[2]
        assert [r["size"] for r in res] == [2, 2]
        assert res[0]["checksum"] == pytest.approx(res[1]["checksum"],
                                                   abs=1e-8)


@pytest.mark.integration
def test_keras_fit_df_disk_cache(monkeypatch):
    """cache='disk' trains model.fit over the spill->stream generator
    with bounded chunks (keras twin of the Jax/Torch out-of-core e2e)."""
    import sys
    import types

    import test_spark as stubmod

    ctx = stubmod._StubContext(default_parallelism=1)
    mod = types.ModuleType("pyspark")
    mod.SparkContext = types.SimpleNamespace(_active_spark_context=ctx)
    mod.BarrierTaskContext = stubmod._BarrierTaskContext
    monkeypatch.setitem(sys.modules, "pyspark", mod)

    from horovod_tpu.orchestrate import KerasEstimator
    from horovod_tpu.orchestrate import spill as spill_mod

    cap = 16
    orig = spill_mod._rows_chunk_to_table
    chunks = []

    def capped(rows, label_col, feature_cols):
        chunks.append(len(rows))
        assert len(rows) <= cap
        return orig(rows, label_col, feature_cols)

    monkeypatch.setattr(spill_mod, "_rows_chunk_to_table", capped)

    rows = [{"x": float(i % 7) / 7.0, "label": 2.0 * (i % 7) / 7.0}
            for i in range(96)]
    df = stubmod._StubDataFrame(rows, ["x", "label"], ctx)

    keras.utils.set_random_seed(3)
    m = keras.Sequential([keras.layers.Input((1,)),
                          keras.layers.Dense(1, use_bias=False)])
    m.compile(optimizer=keras.optimizers.SGD(learning_rate=0.5),
              loss="mse")
    est = KerasEstimator(model=m, num_workers=1, epochs=6, batch_size=16,
                         cache="disk", rows_per_group=cap)
    out = est.fit(df.repartition(1))
    assert len(chunks) >= 96 // cap
    assert est.history_[-1]["loss"] < est.history_[0]["loss"]
    pred = out.predict(np.asarray([[0.5]], np.float32))
    assert abs(float(pred[0, 0]) - 1.0) < 0.4


@pytest.mark.integration
def test_keras_disk_cache_validation_and_store(monkeypatch, tmp_path):
    """Disk mode honors validation_split (val_loss in history) and the
    store= rank-0 checkpoint — parity with the in-memory path."""
    import sys
    import types

    import test_spark as stubmod

    ctx = stubmod._StubContext(default_parallelism=1)
    mod = types.ModuleType("pyspark")
    mod.SparkContext = types.SimpleNamespace(_active_spark_context=ctx)
    mod.BarrierTaskContext = stubmod._BarrierTaskContext
    monkeypatch.setitem(sys.modules, "pyspark", mod)

    from horovod_tpu.orchestrate import KerasEstimator

    rows = [{"x": float(i % 9) / 9.0, "label": 2.0 * (i % 9) / 9.0}
            for i in range(64)]
    df = stubmod._StubDataFrame(rows, ["x", "label"], ctx)

    keras.utils.set_random_seed(4)
    m = keras.Sequential([keras.layers.Input((1,)),
                          keras.layers.Dense(1, use_bias=False)])
    m.compile(optimizer=keras.optimizers.SGD(learning_rate=0.5),
              loss="mse")
    store = str(tmp_path / "store")
    est = KerasEstimator(model=m, num_workers=1, epochs=3, batch_size=16,
                         validation_split=0.25, store=store,
                         cache="disk", rows_per_group=16)
    est.fit(df.repartition(1))
    assert "val_loss" in est.history_[-1]
    import os
    assert os.path.exists(os.path.join(store, "checkpoint.keras"))
