"""Spark adapter tests (ref analogs: test/integration/test_spark.py run
cases; horovod/spark/runner.py:197).

pyspark is not in this image, so the adapter runs against a stub
implementing exactly the Spark surface it touches (active context,
defaultParallelism, parallelize -> barrier -> mapPartitions -> collect,
BarrierTaskContext, job groups).  Partitions execute sequentially in
process — rank layout, env contract, result ordering, and cancellation
logic are what's under test; the distributed init underneath is covered
by the runner/eager suites.
"""

import os
import sys
import types

import numpy as np
import pytest


class _TaskInfo:
    def __init__(self, address):
        self.address = address


class _BarrierTaskContext:
    current = None

    def __init__(self, rank, addresses):
        self._rank = rank
        self._addresses = addresses

    @classmethod
    def get(cls):
        return cls.current

    def partitionId(self):
        return self._rank

    def getTaskInfos(self):
        return [_TaskInfo(a) for a in self._addresses]

    def barrier(self):
        pass


class _BarrierRDD:
    def __init__(self, sc, n):
        self._sc, self._n = sc, n

    def mapPartitions(self, f):
        self._f = f
        return self

    def collect(self):
        if self._sc.fail_with is not None:
            raise self._sc.fail_with
        out = []
        for rank in range(self._n):
            _BarrierTaskContext.current = _BarrierTaskContext(
                rank, self._sc.addresses(self._n))
            try:
                out.extend(self._f(iter([rank])))
            finally:
                _BarrierTaskContext.current = None
        return out


class _RDD(_BarrierRDD):
    def barrier(self):
        return _BarrierRDD(self._sc, self._n)


class _StubContext:
    def __init__(self, default_parallelism=3, hosts=None):
        self.defaultParallelism = default_parallelism
        self._hosts = hosts
        self.cancelled = []
        self.job_groups = []
        self.fail_with = None

    def addresses(self, n):
        if self._hosts:
            return [f"{self._hosts[i % len(self._hosts)]}:{40000 + i}"
                    for i in range(n)]
        return [f"host0:{40000 + i}" for i in range(n)]

    def parallelize(self, data, n):
        return _RDD(self, n)

    def setJobGroup(self, group, desc, interruptOnCancel=False):
        self.job_groups.append(group)

    def cancelJobGroup(self, group):
        self.cancelled.append(group)


@pytest.fixture(autouse=True)
def _env_guard():
    """Stub barrier tasks run in THIS process and os.environ.update a full
    HVDT_* contract: restore os.environ so no stale rank/rendezvous leaks
    into later tests (same guard as tests/test_ray.py)."""
    before = dict(os.environ)
    yield
    os.environ.clear()
    os.environ.update(before)


@pytest.fixture()
def spark_stub(monkeypatch):
    mod = types.ModuleType("pyspark")
    ctx = _StubContext()
    mod.SparkContext = types.SimpleNamespace(_active_spark_context=ctx)
    mod.BarrierTaskContext = _BarrierTaskContext
    monkeypatch.setitem(sys.modules, "pyspark", mod)
    yield ctx


def _echo_contract():
    return {k: os.environ[k] for k in
            ("HVDT_RANK", "HVDT_SIZE", "HVDT_LOCAL_RANK", "HVDT_LOCAL_SIZE",
             "HVDT_CROSS_RANK", "HVDT_CROSS_SIZE",
             "HVDT_RENDEZVOUS_ADDR", "HVDT_RENDEZVOUS_PORT", "HVDT_SECRET")}


class TestSparkRun:
    def test_results_in_rank_order_with_contract(self, spark_stub):
        from horovod_tpu.orchestrate import spark as hspark

        res = hspark.run(_echo_contract, num_proc=3)
        assert [int(r["HVDT_RANK"]) for r in res] == [0, 1, 2]
        assert all(r["HVDT_SIZE"] == "3" for r in res)
        # single stub host: local == global rank, one cross rank
        assert [int(r["HVDT_LOCAL_RANK"]) for r in res] == [0, 1, 2]
        assert all(r["HVDT_CROSS_SIZE"] == "1" for r in res)
        assert all(r["HVDT_SECRET"] for r in res)

    def test_num_proc_defaults_to_parallelism(self, spark_stub):
        from horovod_tpu.orchestrate import spark as hspark

        res = hspark.run(lambda: int(os.environ["HVDT_SIZE"]))
        assert res == [3, 3, 3]

    def test_multihost_rank_layout(self, spark_stub):
        spark_stub._hosts = ["hostA", "hostB"]
        from horovod_tpu.orchestrate import spark as hspark

        res = hspark.run(_echo_contract, num_proc=4)
        # round-robin placement: A,B,A,B
        assert [int(r["HVDT_LOCAL_RANK"]) for r in res] == [0, 0, 1, 1]
        assert [int(r["HVDT_LOCAL_SIZE"]) for r in res] == [2, 2, 2, 2]
        assert [int(r["HVDT_CROSS_RANK"]) for r in res] == [0, 1, 0, 1]
        assert all(int(r["HVDT_CROSS_SIZE"]) == 2 for r in res)

    def test_args_kwargs_and_env_passthrough(self, spark_stub):
        from horovod_tpu.orchestrate import spark as hspark

        def fn(a, b=0):
            return a + b + int(os.environ["HVDT_TEST_EXTRA"])

        res = hspark.run(fn, args=(10,), kwargs={"b": 5}, num_proc=2,
                         env={"HVDT_TEST_EXTRA": "100"})
        assert res == [115, 115]

    def test_no_active_context_raises(self, spark_stub, monkeypatch):
        import pyspark

        monkeypatch.setattr(pyspark.SparkContext, "_active_spark_context",
                            None)
        from horovod_tpu.orchestrate import spark as hspark

        with pytest.raises(RuntimeError, match="active SparkContext"):
            hspark.run(lambda: 0, num_proc=1)

    def test_job_failure_propagates(self, spark_stub):
        from horovod_tpu.orchestrate import spark as hspark

        spark_stub.fail_with = ValueError("executor lost")
        with pytest.raises(ValueError, match="executor lost"):
            hspark.run(lambda: 0, num_proc=2)


class _DataBarrierRDD(_BarrierRDD):
    """Barrier RDD whose partitions carry real rows (DataFrame path)."""

    def __init__(self, sc, partitions):
        super().__init__(sc, len(partitions))
        self._partitions = partitions

    def collect(self):
        if self._sc.fail_with is not None:
            raise self._sc.fail_with
        out = []
        for rank, rows in enumerate(self._partitions):
            _BarrierTaskContext.current = _BarrierTaskContext(
                rank, self._sc.addresses(self._n))
            try:
                out.extend(self._f(iter(rows)))
            finally:
                _BarrierTaskContext.current = None
        return out


class _DataRDD(_DataBarrierRDD):
    def barrier(self):
        b = _DataBarrierRDD(self._sc, self._partitions)
        return b

    def toDF(self):
        """pyspark RDD.toDF surface for the (non-barrier) transform
        path: collect the mapped rows into a new stub DataFrame."""
        rows = self.collect()
        cols = list(rows[0].keys()) if rows else []
        return _StubDataFrame(rows, cols, self._sc)


class _StubDataFrame:
    """Duck-typed pyspark DataFrame: rows + columns + repartition."""

    def __init__(self, rows, columns, sc):
        self._rows = list(rows)
        self.columns = list(columns)
        self._sc = sc
        self._n = None

    def repartition(self, n):
        df = _StubDataFrame(self._rows, self.columns, self._sc)
        df._n = n
        return df

    @property
    def rdd(self):
        n = self._n or self._sc.defaultParallelism
        parts = [self._rows[r::n] for r in range(n)]
        return _DataRDD(self._sc, parts)


class TestRunOnDataFrame:
    def test_rows_are_rank_sharded(self, spark_stub):
        from horovod_tpu.orchestrate import spark as hs

        rows = [{"f1": float(i), "f2": float(10 * i), "label": i % 2,
                 "id": i} for i in range(7)]
        df = _StubDataFrame(rows, ["f1", "f2", "label", "id"], spark_stub)

        def fn(rows):
            import os

            return (os.environ["HVDT_RANK"], sorted(r["id"] for r in rows))

        got = hs.run_on_dataframe(fn, df, num_proc=3)
        # Per-rank results in rank order; rows partition the dataset.
        assert [g[0] for g in got] == ["0", "1", "2"]
        ids = [i for _, part in got for i in part]
        assert sorted(ids) == list(range(7))
        # Every rank saw a NON-overlapping, non-empty shard.
        assert all(part for _, part in got)

    def test_estimator_fit_dataframe_rank_shards(self, spark_stub,
                                                 monkeypatch):
        """fit(df) must dispatch the declarative loop inside barrier
        tasks with each rank's own partition rows (VERDICT r2 #9)."""
        from horovod_tpu import orchestrate
        from horovod_tpu.orchestrate import estimator as est_mod

        rows = [{"x": float(i), "label": float(2 * i)} for i in range(9)]
        df = _StubDataFrame(rows, ["x", "label"], spark_stub)

        shards = {}

        def fake_fit(spec, x_train, y_train, x_val, y_val):
            import os

            rank = os.environ["HVDT_RANK"]
            x, y = est_mod._rows_to_xy(x_train, spec["spark_df"]["label_col"],
                                       spec["spark_df"]["feature_cols"])
            shards[rank] = (x.tolist(), y.tolist())
            return {"params": {"rank": rank, "n": len(x)},
                    "history": [{"epoch": 0, "train_loss": 0.0}],
                    "size": 3}

        monkeypatch.setattr(est_mod, "_declarative_fit", fake_fit)

        est = orchestrate.JaxEstimator(
            model_init=lambda key: {"w": np.zeros(1)},
            loss_fn=lambda p, xb, yb: 0.0,
            predict_fn=lambda p, x: x,
            num_workers=3)
        model = est.fit(df)
        assert model.params == {"rank": "0", "n": 3}
        # All 9 rows arrived, disjointly, 3 per rank, features/labels
        # paired correctly (label = 2 * x).
        assert sorted(shards) == ["0", "1", "2"]
        seen = []
        for x, y in shards.values():
            assert len(x) == 3
            for xi, yi in zip(x, y):
                assert yi == 2 * xi[0]
                seen.append(xi[0])
        assert sorted(seen) == [float(i) for i in range(9)]


class TestStore:
    def test_local_store_roundtrip(self, tmp_path):
        from horovod_tpu.orchestrate.store import LocalStore, Store

        st = Store.create(str(tmp_path / "prefix"))
        assert isinstance(st, LocalStore)
        p = st.get_checkpoint_path("run1")
        assert p.startswith(str(tmp_path)) and "run1" in p
        st.write_bytes(p + "/ckpt.bin", b"abc")
        assert st.exists(p + "/ckpt.bin")
        assert st.read_bytes(p + "/ckpt.bin") == b"abc"

    def test_remote_prefix_resolves_filesystem_store(self, monkeypatch):
        import fsspec.config

        from horovod_tpu.orchestrate.store import FilesystemStore, Store

        # fsspec+gcsfs are importable in this image, so the remote
        # prefix resolves to a FilesystemStore (IO would need real
        # credentials; only construction + path discipline here).
        # Anonymous: left to find credentials, gcsfs asks a metadata
        # server that is not there and backs off for 14 s before it
        # gives up.
        monkeypatch.setitem(fsspec.config.conf, "gcs", {"token": "anon"})
        st = Store.create("gs://bucket/prefix")
        assert isinstance(st, FilesystemStore)
        assert st.get_checkpoint_path("r").startswith("gs://bucket/prefix")


class TestKVShardLengthExchange:
    def test_max_min_across_ranks(self):
        """The DataFrame-path padding handshake (no hvd world needed):
        rank 0 exchanges lengths over a real rendezvous KV against a
        pre-posted peer value (the peer side is just a KV put — the
        interesting machinery is the waiting reader)."""
        from horovod_tpu.orchestrate.estimator import (
            kv_exchange_shard_lengths)
        from horovod_tpu.runner.http_kv import RendezvousServer, new_secret

        server = RendezvousServer(secret=new_secret())
        port = server.start()
        server.put_local("/dfshard/len/1", b"7")   # the peer's post
        saved = dict(os.environ)
        os.environ.update({"HVDT_RENDEZVOUS_ADDR": "127.0.0.1",
                           "HVDT_RENDEZVOUS_PORT": str(port),
                           "HVDT_SECRET": server.secret.hex(),
                           "HVDT_SIZE": "2", "HVDT_RANK": "0"})
        try:
            got = kv_exchange_shard_lengths(4, timeout=30)
        finally:
            os.environ.clear()
            os.environ.update(saved)
            server.stop()
        assert got == (7, 4)


class TestFrameworkEstimatorsDataFrame:
    def test_keras_fit_df_rank_shards(self, spark_stub, monkeypatch):
        keras = pytest.importorskip("keras")
        from horovod_tpu.orchestrate import KerasEstimator
        from horovod_tpu.orchestrate import keras_estimator as ke

        rows = [{"x": float(i), "label": float(3 * i)} for i in range(6)]
        df = _StubDataFrame(rows, ["x", "label"], spark_stub)
        shards = {}

        def fake_worker(spec, meta, model_bytes, rws):
            rank = os.environ["HVDT_RANK"]
            shards[rank] = sorted(r["x"] for r in rws)
            out = {"size": 2}
            if rank == "0":
                out["model"] = model_bytes    # untrained round-trip
                out["history"] = [{"loss": 0.0}]
            return out

        monkeypatch.setattr(ke, "_keras_df_worker", fake_worker)
        model = keras.Sequential(
            [keras.layers.Input((1,)), keras.layers.Dense(1)])
        model.compile(optimizer="sgd", loss="mse")
        est = KerasEstimator(model=model, num_workers=2)
        trained = est.fit(df)
        assert sorted(shards) == ["0", "1"]
        all_x = sorted(v for s in shards.values() for v in s)
        assert all_x == [float(i) for i in range(6)]
        assert trained is not None

    def test_torch_fit_df_rank_shards(self, spark_stub, monkeypatch):
        torch = pytest.importorskip("torch")
        from horovod_tpu.orchestrate import TorchEstimator
        from horovod_tpu.orchestrate import torch_estimator as te

        rows = [{"x": float(i), "label": float(i)} for i in range(6)]
        df = _StubDataFrame(rows, ["x", "label"], spark_stub)
        shards = {}

        def fake_worker(spec, meta, model_bytes, rws):
            import io

            rank = os.environ["HVDT_RANK"]
            shards[rank] = sorted(r["x"] for r in rws)
            out = {"size": 2}
            if rank == "0":
                m = torch.load(io.BytesIO(model_bytes), weights_only=False)
                buf = io.BytesIO()
                torch.save(m.state_dict(), buf)
                out["state"] = buf.getvalue()
                out["history"] = [{"loss": 0.0}]
            return out

        monkeypatch.setattr(te, "_torch_df_worker", fake_worker)
        model = torch.nn.Linear(1, 1)
        est = TorchEstimator(model=model,
                             optimizer=torch.optim.SGD(model.parameters(),
                                                       lr=0.1),
                             loss=torch.nn.MSELoss(), num_workers=2)
        trained = est.fit(df)
        assert sorted(shards) == ["0", "1"]
        all_x = sorted(v for s in shards.values() for v in s)
        assert all_x == [float(i) for i in range(6)]
        assert trained is not None


class TestTransformDataFrame:
    """DataFrame-out inference (ref: spark/torch/estimator.py:413-470
    _transform): model.transform(df) -> df with a prediction column."""

    def _df(self, stub, n=7):
        rows = [{"f1": float(i), "f2": float(10 * i), "label": float(i)}
                for i in range(n)]
        return _StubDataFrame(rows, ["f1", "f2", "label"], stub)

    def test_jax_model_transform_schema_and_values(self, spark_stub):
        from horovod_tpu.orchestrate import JaxModel

        model = JaxModel(
            params={"w": np.asarray([2.0, 0.5])},
            predict_fn=lambda p, x: x @ p["w"],
            df_meta={"label_col": "label", "feature_cols": None,
                     "output_col": "prediction"})
        out = model.transform(self._df(spark_stub))
        # Schema: original columns + the prediction column.
        assert set(out.columns) == {"f1", "f2", "label", "prediction"}
        rows = sorted(out._rows, key=lambda r: r["f1"])
        assert len(rows) == 7
        for r in rows:
            # label was EXCLUDED from features: pred = 2*f1 + 0.5*f2
            assert r["prediction"] == pytest.approx(
                2.0 * r["f1"] + 0.5 * r["f2"])

    def test_predict_runs_once_per_partition(self, spark_stub):
        from horovod_tpu.orchestrate import JaxModel

        calls = []

        def predict_fn(p, x):
            calls.append(len(x))
            return np.zeros(len(x))

        model = JaxModel(params=None, predict_fn=predict_fn,
                         df_meta={"label_col": "label"})
        out = model.transform(self._df(spark_stub))
        # One predict per (non-empty) partition; rows add up.
        assert len(calls) == spark_stub.defaultParallelism
        assert sum(calls) == 7
        assert all(c > 0 for c in calls)
        assert len(out._rows) == 7

    def test_vector_predictions_become_lists(self, spark_stub):
        from horovod_tpu.orchestrate import JaxModel

        model = JaxModel(
            params=None,
            predict_fn=lambda p, x: np.stack([x[:, 0], -x[:, 0]], axis=1),
            df_meta={"label_col": "label", "output_col": "probs"})
        out = model.transform(self._df(spark_stub))
        for r in out._rows:
            assert r["probs"] == [r["f1"], -r["f1"]]

    def test_numpy_input_still_predicts(self):
        from horovod_tpu.orchestrate import JaxModel

        model = JaxModel(params=3.0, predict_fn=lambda p, x: x * p)
        np.testing.assert_allclose(model.transform(np.ones(4)), 3.0)

    def test_torch_model_transform_df(self, spark_stub):
        import torch

        from horovod_tpu.orchestrate import TorchModel

        lin = torch.nn.Linear(2, 1, bias=False)
        with torch.no_grad():
            lin.weight.copy_(torch.tensor([[1.0, 1.0]]))
        model = TorchModel(lin, df_meta={"label_col": "label"})
        out = model.transform(self._df(spark_stub, n=5))
        assert "prediction" in out.columns
        for r in out._rows:
            assert r["prediction"] == pytest.approx(r["f1"] + r["f2"])

    def test_keras_model_transform_df(self, spark_stub):
        keras = pytest.importorskip("keras")

        from horovod_tpu.orchestrate import KerasModel

        m = keras.Sequential([keras.layers.Input((2,)),
                              keras.layers.Dense(1, use_bias=False,
                                                 kernel_initializer="ones")])
        model = KerasModel(m, df_meta={"label_col": "label"})
        out = model.transform(self._df(spark_stub, n=5))
        assert "prediction" in out.columns
        for r in out._rows:
            assert r["prediction"] == pytest.approx(r["f1"] + r["f2"],
                                                    rel=1e-5)


class TestOutOfCore:
    """Out-of-core fit(df) (VERDICT r3 #5; ref: spark/common/util.py
    prepare_data + Petastorm row-group streaming): partitions spill to
    Parquet row groups and stream back batch-wise — bounded memory."""

    def _row_gen(self, n):
        for i in range(n):
            yield {"f1": float(i), "f2": float(2 * i),
                   "label": float(3 * i)}

    def test_spill_is_chunk_bounded(self, tmp_path, monkeypatch):
        """The artificial memory cap: the spiller may never hold more
        than rows_per_group rows at once, even for a partition 10x
        that size."""
        from horovod_tpu.orchestrate import spill as spill_mod

        cap = 8
        seen = []
        orig = spill_mod._rows_chunk_to_table

        def capped(rows, label_col, feature_cols):
            seen.append(len(rows))
            assert len(rows) <= cap, "memory cap exceeded"
            return orig(rows, label_col, feature_cols)

        monkeypatch.setattr(spill_mod, "_rows_chunk_to_table", capped)
        train, val, n_train, n_val, cols = \
            spill_mod.spill_partition_to_parquet(
                self._row_gen(80), "label", None, 0.0, str(tmp_path),
                rows_per_group=cap)
        assert n_train == 80 and n_val == 0 and val is None
        assert len(seen) == 10                    # 80 rows / 8-row chunks
        import pyarrow.parquet as pq

        assert pq.ParquetFile(train).metadata.num_row_groups == 10
        x, y = spill_mod.read_xy(train, "label", cols)
        assert x.shape == (80, 2)
        np.testing.assert_allclose(y, 3 * x[:, 0])

    def test_spill_per_chunk_validation_split(self, tmp_path):
        from horovod_tpu.orchestrate import spill as spill_mod

        train, val, n_train, n_val, cols = \
            spill_mod.spill_partition_to_parquet(
                self._row_gen(40), "label", None, 0.25, str(tmp_path),
                rows_per_group=8)
        assert n_train == 30 and n_val == 10
        xv, yv = spill_mod.read_xy(val, "label", cols)
        assert len(xv) == 10
        # split-clean: no row in both files
        xt, _ = spill_mod.read_xy(train, "label", cols)
        assert not set(xt[:, 0]) & set(xv[:, 0])

    def test_stream_batches_wrap_to_target(self, tmp_path):
        """A rank with 10 rows asked for target 16 wraps around: 4 full
        batches of 4 — the lazy analog of wrap-padding."""
        from horovod_tpu.orchestrate import spill as spill_mod

        train, _, n, _, cols = spill_mod.spill_partition_to_parquet(
            self._row_gen(10), "label", None, 0.0, str(tmp_path),
            rows_per_group=4)
        assert n == 10
        batches = list(spill_mod.stream_batches(
            train, "label", cols, batch_size=4, target_rows=16, seed=0))
        assert len(batches) == 4
        assert all(xb.shape == (4, 2) and yb.shape == (4,)
                   for xb, yb in batches)
        # every one of the 10 distinct rows appears at least once
        seen = {v for xb, _ in batches for v in xb[:, 0]}
        assert seen == {float(i) for i in range(10)}

    def test_estimator_fit_df_disk_cache(self, spark_stub, monkeypatch):
        """e2e: cache='disk' trains through the spill->stream path with
        bounded chunks and never materializes the partition row list."""
        import jax.numpy as jnp

        from horovod_tpu.orchestrate import JaxEstimator
        from horovod_tpu.orchestrate import estimator as est_mod
        from horovod_tpu.orchestrate import spill as spill_mod

        cap = 16
        orig = spill_mod._rows_chunk_to_table
        chunks = []

        def capped(rows, label_col, feature_cols):
            chunks.append(len(rows))
            assert len(rows) <= cap
            return orig(rows, label_col, feature_cols)

        monkeypatch.setattr(spill_mod, "_rows_chunk_to_table", capped)
        # the row-list path must never run in disk mode
        monkeypatch.setattr(
            est_mod, "_rows_to_xy",
            lambda *a, **k: (_ for _ in ()).throw(
                AssertionError("row-list path used in disk mode")))

        rows = [{"x": float(i % 7), "label": 2.0 * (i % 7)}
                for i in range(96)]
        df = _StubDataFrame(rows, ["x", "label"], spark_stub)

        import optax

        est = JaxEstimator(
            model_init=lambda key: {"w": jnp.zeros((1, 1))},
            loss_fn=lambda p, xb, yb: jnp.mean(
                (xb @ p["w"] - yb[:, None]) ** 2),
            predict_fn=lambda p, x: x @ p["w"],
            optimizer=optax.sgd(0.02),
            num_workers=1, epochs=8, batch_size=16, seed=0,
            cache="disk", rows_per_group=cap)
        model = est.fit(df.repartition(1))
        assert len(chunks) >= 96 // cap          # partition streamed
        assert est.history_[-1]["train_loss"] < est.history_[0][
            "train_loss"]
        pred = model.predict(np.asarray([[2.0]], np.float32))
        assert abs(float(pred[0, 0]) - 4.0) < 1.5

    def test_spill_vector_labels_round_trip(self, tmp_path):
        """Vector labels must survive the Parquet round trip (the
        in-memory path supports them; disk mode must not change which
        schemas train)."""
        from horovod_tpu.orchestrate import spill as spill_mod

        rows = [{"f": float(i), "label": [float(i), float(-i)]}
                for i in range(12)]
        train, _, n, _, cols = spill_mod.spill_partition_to_parquet(
            iter(rows), "label", None, 0.0, str(tmp_path),
            rows_per_group=5)
        x, y = spill_mod.read_xy(train, "label", cols)
        assert n == 12 and y.shape == (12, 2)
        np.testing.assert_allclose(y[:, 0], x[:, 0])
        np.testing.assert_allclose(y[:, 1], -x[:, 0])

    def test_stream_val_loss_weighted_mean(self, tmp_path):
        from horovod_tpu.orchestrate import spill as spill_mod

        train, _, n, _, cols = spill_mod.spill_partition_to_parquet(
            self._row_gen(10), "label", None, 0.0, str(tmp_path),
            rows_per_group=4)

        def eval_loss(params, x, y):
            return float(np.mean(y))         # mean label

        # weighted mean over row groups == global mean of 3*i, i<10
        got = spill_mod.stream_val_loss(eval_loss, None, train, "label",
                                        cols)
        assert got == pytest.approx(np.mean([3.0 * i for i in range(10)]))
