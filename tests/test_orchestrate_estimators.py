"""JaxEstimator tests: the custom-loop, declarative and Parquet forms, each
over real worker processes on localhost.  Split from
tests/test_orchestrate.py (the Executor pool and the RayExecutor adapter)
so that neither file is a worker's whole share of the run under --dist
loadfile."""

import numpy as np
import pytest

from horovod_tpu.orchestrate import JaxEstimator


def _fit_linear(x, y, lr=0.5, steps=60):
    """Closed little least-squares trainer (pure numpy, runs in worker)."""
    w = np.zeros(x.shape[1], np.float64)
    for _ in range(steps):
        grad = x.T @ (x @ w - y) / len(x)
        w -= lr * grad
    return w


def _predict_linear(w, x):
    return x @ w


class TestJaxEstimator:
    def test_fit_transform(self):
        rng = np.random.default_rng(0)
        true_w = np.array([2.0, -1.0, 0.5])
        X = rng.normal(size=(240, 3))
        y = X @ true_w
        est = JaxEstimator(_fit_linear, _predict_linear, num_workers=2)
        model = est.fit(X, y, lr=0.5, steps=120)
        pred = model.transform(X)
        np.testing.assert_allclose(pred, y, atol=0.2)


def _lin_init(key):
    import jax.numpy as jnp

    return {"w": jnp.zeros((3,), jnp.float32)}


def _lin_loss(params, xb, yb):
    import jax.numpy as jnp

    return jnp.mean((xb @ params["w"] - yb) ** 2)


def _lin_predict(params, x):
    return np.asarray(x, np.float32) @ np.asarray(params["w"])


class TestDeclarativeEstimator:
    def test_declarative_fit_with_validation_and_store(self, tmp_path):
        import optax

        rng = np.random.default_rng(1)
        true_w = np.array([1.5, -2.0, 0.75], np.float32)
        X = rng.normal(size=(256, 3)).astype(np.float32)
        y = (X @ true_w).astype(np.float32)
        store = str(tmp_path / "store")
        est = JaxEstimator(
            model_init=_lin_init, loss_fn=_lin_loss,
            predict_fn=_lin_predict, optimizer=optax.sgd(0.3),
            epochs=4, batch_size=32, validation_split=0.25,
            store=store, num_workers=2, seed=3)
        model = est.fit(X, y)
        # converged: predictions match, val loss decreased and is averaged
        np.testing.assert_allclose(model.predict(X), y, atol=0.15)
        assert len(est.history_) == 4
        assert est.history_[-1]["val_loss"] < est.history_[0]["val_loss"]
        assert est.history_[-1]["val_loss"] < 0.05
        # rank-0 checkpoint store has the per-epoch saves
        from horovod_tpu.checkpoint import CheckpointManager

        assert CheckpointManager(store).latest_step() == 3

    def test_constructor_validation(self):
        with pytest.raises(ValueError, match="exactly one"):
            JaxEstimator()
        with pytest.raises(ValueError, match="exactly one"):
            JaxEstimator(_fit_linear, model_init=_lin_init, loss_fn=_lin_loss)
        with pytest.raises(ValueError, match="needs loss_fn"):
            JaxEstimator(model_init=_lin_init)

    def test_uneven_samples_do_not_deadlock(self):
        # 257 % 2 != 0: unequal raw shards used to give ranks different
        # batch counts -> mismatched named collectives -> hang.  Shard
        # equalization must keep the ranks in lockstep.
        import optax

        rng = np.random.default_rng(5)
        true_w = np.array([1.0, 2.0, -0.5], np.float32)
        X = rng.normal(size=(257, 3)).astype(np.float32)
        y = (X @ true_w).astype(np.float32)
        est = JaxEstimator(
            model_init=_lin_init, loss_fn=_lin_loss,
            predict_fn=_lin_predict, optimizer=optax.sgd(0.3),
            epochs=2, batch_size=32, validation_split=0.3,
            num_workers=2, seed=1)
        model = est.fit(X, y)
        np.testing.assert_allclose(model.predict(X), y, atol=0.4)

    def test_requires_predict_fn(self):
        with pytest.raises(ValueError, match="predict_fn is required"):
            JaxEstimator(model_init=_lin_init, loss_fn=_lin_loss)

    def test_too_few_samples_rejected(self):
        est = JaxEstimator(model_init=_lin_init, loss_fn=_lin_loss,
                           predict_fn=_lin_predict, num_workers=4)
        with pytest.raises(ValueError, match="at least num_workers"):
            est.fit(np.zeros((2, 3), np.float32), np.zeros(2, np.float32))

    def test_fit_guards(self):
        import optax

        est = JaxEstimator(model_init=_lin_init, loss_fn=_lin_loss,
                           predict_fn=_lin_predict, optimizer=optax.sgd(0.1),
                           num_workers=2)
        X = np.zeros((8, 3), np.float32)
        with pytest.raises(TypeError, match="no per-call kwargs"):
            est.fit(X, np.zeros(8, np.float32), epochs=10)
        with pytest.raises(ValueError, match="needs y"):
            est.fit(X)
        with pytest.raises(ValueError, match=r"validation_split must be"):
            JaxEstimator(model_init=_lin_init, loss_fn=_lin_loss,
                         predict_fn=_lin_predict, validation_split=1.0)


class TestParquetEstimator:
    def test_fit_from_parquet_row_groups(self, tmp_path):
        import optax
        import pandas as pd
        import pyarrow.parquet as pq
        import pyarrow as pa

        from horovod_tpu.orchestrate import ParquetSource

        rng = np.random.default_rng(9)
        true_w = np.array([2.0, -1.0, 0.5], np.float32)
        X = rng.normal(size=(300, 3)).astype(np.float32)
        y = (X @ true_w).astype(np.float32)
        df = pd.DataFrame({"f0": X[:, 0], "f1": X[:, 1], "f2": X[:, 2],
                           "label": y})
        path = str(tmp_path / "train.parquet")
        # several small row groups so 2 workers get distinct shards
        pq.write_table(pa.Table.from_pandas(df), path, row_group_size=50)

        est = JaxEstimator(
            model_init=_lin_init, loss_fn=_lin_loss,
            predict_fn=_lin_predict, optimizer=optax.sgd(0.3),
            epochs=3, batch_size=25, validation_split=0.2,
            num_workers=2, seed=2)
        model = est.fit(ParquetSource(path, label_col="label"))
        np.testing.assert_allclose(model.predict(X), y, atol=0.3)
        assert est.history_[-1]["val_loss"] < est.history_[0]["val_loss"]

    def test_parquet_guards(self, tmp_path):
        import pandas as pd
        import pyarrow.parquet as pq
        import pyarrow as pa

        from horovod_tpu.orchestrate import ParquetSource

        df = pd.DataFrame({"f0": [1.0, 2.0], "label": [0.0, 1.0]})
        path = str(tmp_path / "tiny.parquet")
        pq.write_table(pa.Table.from_pandas(df), path, row_group_size=2)
        est = JaxEstimator(model_init=_lin_init, loss_fn=_lin_loss,
                           predict_fn=_lin_predict, num_workers=4)
        with pytest.raises(ValueError, match="row groups < num_workers"):
            est.fit(ParquetSource(path, label_col="label"))
        est2 = JaxEstimator(model_init=_lin_init, loss_fn=_lin_loss,
                            predict_fn=_lin_predict, num_workers=1)
        with pytest.raises(ValueError, match="y=None"):
            est2.fit(ParquetSource(path, label_col="label"),
                     np.zeros(2, np.float32))

    def test_parquet_rejected_on_custom_path(self, tmp_path):
        from horovod_tpu.orchestrate import ParquetSource

        est = JaxEstimator(_fit_linear, _predict_linear, num_workers=1)
        with pytest.raises(ValueError, match="declarative estimator"):
            est.fit(ParquetSource(str(tmp_path / "x.parquet"),
                                  label_col="y"))


class TestSplitAndShard:
    """The shared estimator data discipline (estimator.split_and_shard)."""

    def test_insufficient_train_rows_raises_clearly(self):
        from horovod_tpu.orchestrate.estimator import split_and_shard

        x = np.ones((8, 2))
        y = np.ones((8,))
        with pytest.raises(ValueError, match="TRAINING samples"):
            split_and_shard(x, y, 0.7, 4)      # 2 train rows < 4 workers

    def test_val_rows_never_contain_padding(self):
        from horovod_tpu.orchestrate.estimator import split_and_shard

        x = np.arange(10, dtype=np.float64)[:, None]
        y = np.arange(10, dtype=np.float64)
        xs, ys, xv, yv = split_and_shard(x, y, 0.2, 3)
        val_rows = {float(v) for shard in xv for v in np.asarray(shard).ravel()}
        assert val_rows == {8.0, 9.0}          # the global tail, only
        # equalized train shards: identical lengths, only train values
        lens = {len(s) for s in xs}
        assert len(lens) == 1
        train_vals = {float(v) for s in xs for v in np.asarray(s).ravel()}
        assert train_vals <= set(map(float, range(8)))

    def test_no_validation(self):
        from horovod_tpu.orchestrate.estimator import split_and_shard

        xs, ys, xv, yv = split_and_shard(np.ones((6, 1)), np.ones(6),
                                         0.0, 2)
        assert xv == [None, None] and yv == [None, None]
        assert sum(len(s) for s in xs) == 6
