"""JaxEstimator — the Spark-estimator fit/transform shape without Spark.

Re-conception of ref: spark/keras & spark/torch estimators
(spark/common/params.py, runner.py — Spark ML fit/transform over
distributed workers).  Petastorm/DataFrame plumbing collapses to numpy
arrays sharded across the Executor pool; what survives is the contract:
``est.fit(X, y) -> model`` trains data-parallel across workers, and the
returned model is a plain local object with ``transform``/``predict``.

Two fit paths:

* **declarative** (ref: KerasEstimator's model/optimizer/loss params,
  spark/common/params.py:64-210) — pass ``model_init``/``loss_fn``/
  ``optimizer`` plus ``epochs``/``batch_size``/``validation_split``/
  ``store`` and the estimator runs the full distributed loop itself:
  broadcast initial params, per-batch eager gradient allreduce across
  worker processes, epoch metric averaging, rank-0 checkpointing into
  the store directory (ref: store.py checkpoint dir + BestModelCheckpoint
  rank-0 discipline).
* **custom** (``train_fn``) — bring-your-own worker loop, as before.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from ..runner.http_kv import free_port
from .executor import Executor
from .ml_params import MLParams

__all__ = ["JaxEstimator", "JaxModel", "ParquetSource"]


@dataclasses.dataclass(frozen=True)
class ParquetSource:
    """Train directly from a Parquet file (ref: the Spark estimators'
    defining input path — Petastorm over Parquet row groups,
    spark/common/util.py).  Workers read only their assigned row groups;
    the driver never materializes the dataset.

    feature_cols: columns forming the feature matrix (None = all columns
    except ``label_col``).
    """

    path: str
    label_col: str
    feature_cols: Optional[Tuple[str, ...]] = None


class JaxModel(MLParams):
    """Trained model handle (ref: spark estimators return a Model whose
    transform() runs the predict path).  MLParams gives it the Spark-ML
    Model persistence surface (``save``/``load``, ``write``/``read``)
    and makes it a registered pyspark Transformer stage
    (orchestrate/ml_params.py)."""

    def __init__(self, params: Any, predict_fn: Callable[[Any, np.ndarray],
                                                         np.ndarray],
                 df_meta: Optional[Dict[str, Any]] = None):
        self.params = params
        self._predict_fn = predict_fn
        self._df_meta = df_meta or {}

    def predict(self, x: np.ndarray) -> np.ndarray:
        return np.asarray(self._predict_fn(self.params, x))

    def transform(self, x):
        """numpy in -> predictions out; Spark DataFrame in -> DataFrame
        out with a prediction column appended (ref: the Spark-ML Model
        _transform contract, spark/torch/estimator.py:413)."""
        if _is_spark_dataframe(x):
            params, predict_fn = self.params, self._predict_fn
            return df_transform(
                x, lambda xa: predict_fn(params, xa), self._df_meta)
        return self.predict(x)


def _worker_fit(train_fn, fit_kwargs, x_shard, y_shard):
    return train_fn(x_shard, y_shard, **fit_kwargs)


def _load_parquet_shard(hvd, spec: Dict[str, Any], row_groups):
    """Worker-side Parquet ingestion: read this rank's row groups, split
    validation locally (before padding, so no train row can leak in), and
    wrap-pad train rows to the cross-rank MAX length so every rank runs
    the same number of lockstep collective steps."""
    import pyarrow.parquet as pq

    meta = spec["parquet"]
    pf = pq.ParquetFile(meta["path"])
    table = pf.read_row_groups(list(row_groups))
    label = meta["label_col"]
    feats = meta["feature_cols"] or [c for c in table.column_names
                                     if c != label]
    x = np.column_stack(
        [np.asarray(table[c], dtype=np.float32) for c in feats])
    # Labels keep their native dtype (int labels index logits in
    # classification losses; array-mode fit preserves the caller's dtype
    # too).
    y = np.asarray(table[label].to_numpy(zero_copy_only=False))

    return _split_and_pad_local(hvd, spec, x, y)


def _hvd_exchange_lengths(hvd, n_train: int,
                          name: str = "est_parquet/target"):
    """Cross-rank (max, min) of per-rank train lengths over one MAX
    allreduce carrying (len, -len) — every rank also learns the MIN, so
    a rank with zero train rows fails on ALL ranks at once instead of
    stranding peers in the next collective until timeout."""
    agg = np.asarray(hvd.allreduce(
        np.asarray([n_train, -n_train], np.int64), op=hvd.Max, name=name))
    return int(agg[0]), int(-agg[1])


def _split_and_pad_local(hvd, spec: Dict[str, Any], x, y):
    """Worker-side lockstep discipline over the established hvd world
    (Parquet + declarative DataFrame paths)."""
    return _split_pad_discipline(
        x, y, spec["validation_split"],
        lambda n: _hvd_exchange_lengths(hvd, n))


def _split_pad_discipline(x, y, validation_split: float, exchange):
    """Shared worker-side lockstep discipline: local validation split
    (before padding, so no train row can leak in), then wrap-padding of
    the train rows to the cross-rank MAX length so every rank runs the
    same number of lockstep collective steps.  ``exchange(n_train)``
    returns the cross-rank (max, min) lengths — hvd MAX-allreduce or
    rendezvous-KV, depending on what the calling path has available."""
    split = validation_split
    n_val = max(1, int(round(len(x) * split))) if split > 0 else 0
    x_train, y_train = x[:len(x) - n_val], y[:len(y) - n_val]
    x_val = x[len(x) - n_val:] if n_val else None
    y_val = y[len(y) - n_val:] if n_val else None

    target, min_len = exchange(len(x_train))
    if min_len == 0:
        raise ValueError(
            "a worker contributed ZERO training rows (empty partition, or "
            "only validation rows after the split) — use more rows per "
            "partition, fewer workers, or a smaller validation_split")
    if len(x_train) < target:
        reps = [i % len(x_train) for i in range(target - len(x_train))]
        x_train = np.concatenate([x_train, x_train[reps]])
        y_train = np.concatenate([y_train, y_train[reps]])
    return x_train, y_train, x_val, y_val


def kv_exchange_shard_lengths(n_rows: int, timeout: Optional[float] = None,
                              key: str = "/dfshard/len"):
    """Cross-rank (max, min) of per-rank row counts over the rendezvous
    KV — the lockstep-padding handshake for barrier-task training paths
    that have not (yet) formed an hvd world.  Requires the launcher env
    contract (HVDT_RANK/SIZE + rendezvous address) in os.environ.
    Callers exchanging MORE than one quantity per run must use distinct
    ``key`` namespaces (per-rank keys are overwritten, not versioned)."""
    import os

    from ..runner.http_kv import KVClient

    if timeout is None:
        from ..common import config

        timeout = config.get_float("HVDT_DFSHARD_TIMEOUT")
    rank = int(os.environ["HVDT_RANK"])
    size = int(os.environ["HVDT_SIZE"])
    kv = KVClient.from_env(os.environ)
    kv.put(f"{key}/{rank}", str(int(n_rows)).encode())
    # KVClient.wait raises TimeoutError itself when a peer never posts.
    lens = [int(kv.wait(f"{key}/{r}", timeout=timeout))
            for r in range(size)]
    return max(lens), min(lens)


def df_rows_to_shards(rows, label_col: str, feature_cols,
                      validation_split: float):
    """Barrier-task DataFrame ingestion shared by the framework
    estimators: rows -> (x_train, y_train, x_val, y_val) with the shared
    split/pad discipline, lengths exchanged over the rendezvous KV (no
    hvd world needed yet).

    An EMPTY partition must fail on ALL ranks at once: this rank posts
    its length (0) to the KV *before* raising, so peers' exchange
    completes immediately and min==0 raises everywhere — instead of
    stranding them in kv.wait until the full timeout."""
    if not rows:
        kv_exchange_shard_lengths(0)
        raise ValueError(
            "a barrier task received an EMPTY DataFrame partition — "
            "repartition produced skew; use more rows or fewer workers")
    x, y = _rows_to_xy(rows, label_col, feature_cols)
    return _split_pad_discipline(x, y, validation_split,
                                 kv_exchange_shard_lengths)


def _row_get(r, c):
    try:
        return r[c]
    except (TypeError, IndexError):
        return getattr(r, c)


def infer_feature_cols(first, feature_cols, exclude=()):
    """Column discovery shared by every row-materialization path
    (in-memory fit, spill, transform): explicit ``feature_cols`` wins;
    otherwise every column of the first Row (pyspark Row or mapping)
    except ``exclude``."""
    if feature_cols:
        return list(feature_cols)
    try:
        names = list(first.__fields__)           # pyspark Row
    except AttributeError:
        names = list(first.keys())               # mapping (stub/tests)
    return [c for c in names if c not in exclude]


def _rows_to_x(rows, feature_cols, exclude=()):
    """Row materialization shared by fit(df) and transform(df): a
    partition's Rows (pyspark Row or plain mappings) -> x float32 [n, d].
    Vector-typed columns are flattened via ``np.asarray`` per cell."""
    cols = infer_feature_cols(rows[0], feature_cols, exclude)
    return np.asarray(
        [np.concatenate([np.ravel(np.asarray(_row_get(r, c), np.float32))
                         for c in cols]) for r in rows], np.float32)


def _rows_to_xy(rows, label_col: str, feature_cols):
    """Barrier-task row materialization: (x float32 [n, d],
    y native-dtype [n])."""
    if not rows:
        raise ValueError(
            "a barrier task received an EMPTY DataFrame partition — "
            "repartition produced skew; use more rows or fewer workers")
    x = _rows_to_x(rows, feature_cols, exclude=(label_col,))
    y = np.asarray([_row_get(r, label_col) for r in rows])
    return x, y


def rows_predictor(predict: Callable, label_col: str, feature_cols,
                   output_col: str):
    """Build the per-partition ``rows -> [value, ...]`` callable for
    :func:`spark.transform_dataframe` from an ``x -> preds`` model
    predict.  Per-row values: scalar predictions become Python floats,
    vector predictions become float lists (the reference flattens to
    DenseVector — torch/estimator.py:452-466)."""

    def rows_predict(rows):
        x = _rows_to_x(rows, feature_cols,
                       exclude=(label_col, output_col))
        preds = np.asarray(predict(x))
        if preds.shape[0] != len(rows):
            raise ValueError(
                f"predict returned {preds.shape[0]} predictions for "
                f"{len(rows)} rows")
        out = []
        for p in preds:
            p = np.ravel(np.asarray(p))
            out.append(float(p[0]) if p.size == 1
                       else [float(v) for v in p])
        return out

    return rows_predict


def df_transform(df, predict: Callable, meta: Dict[str, Any]):
    """DataFrame-out inference dispatch shared by the estimator model
    handles: append ``meta['output_col']`` predictions to ``df``."""
    from . import spark as spark_mod

    output_col = meta.get("output_col") or "prediction"
    return spark_mod.transform_dataframe(
        rows_predictor(predict, meta.get("label_col") or "label",
                       meta.get("feature_cols"), output_col),
        df, output_col)


def _declarative_fit(spec: Dict[str, Any], x_train, y_train, x_val, y_val):
    """Runs inside each Executor worker: the estimator-owned training loop.

    The worker env carries JAX_PLATFORMS=cpu + HVDT_COORDINATOR_ADDR (set
    by ``JaxEstimator.fit``), so ``hvd.init()`` connects the JAX
    distributed runtime across the pool and eager collectives negotiate
    through it — the same per-step gradient-allreduce shape as the
    reference's estimator workers (ref: spark/keras/remote.py train loop).

    Lockstep invariant (the val-metric collective below must be entered
    by every rank or none, and batch counts must match): in ARRAY mode
    the driver established it before dispatch — global tail split, then
    equal-length train shards (padding never touches validation rows).
    In PARQUET mode ``_load_parquet_shard`` establishes the same
    invariant worker-side: local pre-padding split with ``n_val >= 1``
    whenever validation is on, then MAX-allreduce wrap-padding of the
    train rows.  Any change to either path must preserve both halves.
    """
    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp  # noqa: F401
    import optax

    import horovod_tpu as hvd

    hvd.init()
    rank = hvd.rank()

    spill_cleanup = None     # set by the out-of-core branch
    try:
        stream = None        # (train_path, feature_cols, target_rows) or None
        stream_val = None    # val parquet path (streamed eval) or None
        if spec.get("spark_df_stream"):
            # Out-of-core DataFrame mode (ref: spark/common/util.py
            # prepare_data + Petastorm row-group streaming): x_train carries
            # this barrier task's ROW ITERATOR.  Spill it to Parquet in
            # bounded chunks, exchange lengths, then stream row groups
            # batch-wise each epoch — the partition is never materialized.
            from .spill import (ZERO_TRAIN_ROWS_MSG,
                                spill_partition_to_parquet, spill_scratch)

            meta = spec["spark_df_stream"]
            # Cleanup callable is armed BEFORE the spill runs, so a
            # mid-spill failure still removes whatever row groups were
            # already written.
            spill_dir, sp_prefix, spill_cleanup = spill_scratch(
                meta.get("spill_dir"), rank)
            train_path, val_path, n_train, n_val, feat_cols = \
                spill_partition_to_parquet(
                    x_train, meta["label_col"], meta["feature_cols"],
                    spec["validation_split"], spill_dir,
                    meta.get("rows_per_group", 4096), prefix=sp_prefix)
            target, min_len = _hvd_exchange_lengths(hvd, n_train)
            if min_len == 0:
                raise ValueError(ZERO_TRAIN_ROWS_MSG)
            # Validation must be all-or-none across ranks (the est_metric/val
            # allreduce below is collective).  The per-chunk split can give a
            # rank zero val rows (partition an exact multiple of
            # rows_per_group with a tiny split): if ANY rank got none, all
            # ranks skip validation rather than mismatch the collective.
            # Evaluation STREAMS the val file (stream_val_loss) — the val
            # set is partition-proportional, so materializing it would
            # defeat the bounded-memory contract.
            _, min_val = _hvd_exchange_lengths(hvd, n_val,
                                               name="est_stream/val")
            if val_path is not None and min_val > 0:
                stream_val = val_path
            stream = (train_path, meta["label_col"], feat_cols, target)
            x_train = np.zeros((0, 1), np.float32)   # loop streams instead
            y_train = np.zeros((0,), np.float32)
        elif spec.get("parquet"):
            # Parquet mode: x_train carries this rank's ROW-GROUP indices; the
            # worker reads only those groups (the Petastorm-shape contract —
            # ref: spark/common/util.py Parquet row-group partitioning).
            x_train, y_train, x_val, y_val = _load_parquet_shard(
                hvd, spec, x_train)
        elif spec.get("spark_df"):
            # DataFrame mode: x_train carries this barrier task's partition
            # rows; materialize + apply the shared local split/pad
            # discipline (ref: dataframe->Petastorm prep, spark/common/util.py).
            meta = spec["spark_df"]
            if x_train:
                x, y = _rows_to_xy(x_train, meta["label_col"],
                                   meta["feature_cols"])
            else:
                # Empty partition: enter the length exchange with 0 rows so
                # ALL ranks fail the min==0 check together instead of peers
                # hanging in the allreduce this rank never reached.
                x = np.zeros((0, 1), np.float32)
                y = np.zeros((0,), np.float32)
            x_train, y_train, x_val, y_val = _split_and_pad_local(
                hvd, spec, x, y)
        x_train = np.asarray(x_train)
        y_train = np.asarray(y_train)

        params = spec["model_init"](jax.random.PRNGKey(spec["seed"]))
        # Broadcast rank 0's init so all replicas start identical even if
        # model_init is nondeterministic (ref: broadcast_parameters at start
        # of training, torch/functions.py:30).
        params = hvd.broadcast_parameters(params, root_rank=0)
        opt = spec["optimizer"] or optax.adam(1e-3)
        opt_state = opt.init(params)
        loss_fn = spec["loss_fn"]

        grad_step = jax.jit(jax.value_and_grad(loss_fn))
        eval_loss = jax.jit(loss_fn)

        bs = spec["batch_size"]
        rng = np.random.RandomState(spec["seed"] + 101 * rank)
        manager = None
        if spec["store"]:
            # All ranks construct the manager and enter save(): the write is
            # rank-0-only inside save_checkpoint, but its completion barrier
            # is collective.
            from ..checkpoint import CheckpointManager

            manager = CheckpointManager(spec["store"])

        def _epoch_batches(epoch):
            """Equal-count lockstep batches: stream mode yields full batches
            from Parquet row groups (wrap-around to the cross-rank max);
            array mode permutes in memory with tail-batch wrap-padding —
            both give every rank ceil(target / bs) identical-shape steps."""
            if stream is not None:
                from .spill import stream_batches

                train_path, label_c, feat_cols, target = stream
                yield from stream_batches(
                    train_path, label_c, feat_cols, bs, target,
                    seed=spec["seed"] + 7919 * epoch + 101 * rank,
                    shuffle=spec["shuffle"])
                return
            order = (rng.permutation(len(x_train)) if spec["shuffle"]
                     else np.arange(len(x_train)))
            for start in range(0, max(len(order), 1), max(bs, 1)):
                idx = order[start:start + bs]
                if idx.size == 0:
                    continue
                # Pad the tail batch to full size (static shapes: one jit
                # trace) — wrap-around rows re-weight a few samples slightly,
                # matching the reference's repartition-to-equal-shards
                # behavior rather than dropping data.
                if idx.size < bs:
                    idx = np.concatenate([idx, order[:bs - idx.size]])
                yield x_train[idx], y_train[idx]

        history: List[Dict[str, float]] = []
        for epoch in range(spec["epochs"]):
            losses = []
            for xb, yb in _epoch_batches(epoch):
                loss, grads = grad_step(params, xb, yb)
                # One grouped (all-or-nothing fused) eager allreduce per step
                # (ref: grouped allreduce + GroupTable, common/group_table.cc).
                leaves, treedef = jax.tree.flatten(grads)
                reduced = hvd.grouped_allreduce(
                    [np.asarray(g) for g in leaves], name="est_grad")
                grads = jax.tree.unflatten(
                    treedef, [jnp.asarray(r) for r in reduced])
                updates, opt_state = opt.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
                losses.append(float(loss))
            row = {"epoch": epoch,
                   "train_loss": float(np.mean(losses)) if losses else float("nan")}
            # Cross-worker metric averaging (ref: MetricAverageCallback,
            # _keras/callbacks.py:49).
            row["train_loss"] = float(np.asarray(hvd.allreduce(
                np.asarray([row["train_loss"]], np.float32),
                name="est_metric/train"))[0])
            vl = None
            if x_val is not None:
                vl = float(eval_loss(params, np.asarray(x_val),
                                     np.asarray(y_val)))
            elif stream_val is not None:
                from .spill import stream_val_loss

                vl = stream_val_loss(eval_loss, params, stream_val,
                                     stream[1], stream[2])
            if vl is not None:
                row["val_loss"] = float(np.asarray(hvd.allreduce(
                    np.asarray([vl], np.float32), name="est_metric/val"))[0])
            history.append(row)
            if manager is not None:
                manager.save(epoch, params, force=True)
            hvd.barrier()

        return {"params": jax.tree.map(np.asarray, params), "history": history,
                "size": hvd.size()}
    finally:
        # Spilled Parquet is per-fit scratch: reused executor
        # processes must not accumulate dataset-sized files.
        if spill_cleanup is not None:
            spill_cleanup()


class JaxEstimator(MLParams):
    """Data-parallel fit over an Executor pool.

    MLParams (orchestrate/ml_params.py) adds the Spark-ML estimator
    surface: camelCase param get/set (``setEpochs(3)``), ``copy``,
    ``save``/``load`` persistence, and pyspark ``Pipeline`` stage
    compatibility (ref: spark/common/params.py EstimatorParams).

    Args:
      train_fn: ``train_fn(x_shard, y_shard, **fit_kwargs) -> params`` —
        runs inside each worker process (it may hvd.init() and allreduce
        itself, or train purely locally; rank/size come from the env
        contract).  Rank 0's returned params become the model.
      predict_fn: ``predict_fn(params, x) -> y_hat`` for the model handle.
      num_workers: pool size (ref: num_proc on the spark estimators).
    """

    def __init__(self, train_fn: Optional[Callable] = None,
                 predict_fn: Optional[Callable] = None,
                 num_workers: int = 1,
                 env: Optional[Dict[str, str]] = None,
                 *,
                 model_init: Optional[Callable] = None,
                 loss_fn: Optional[Callable] = None,
                 optimizer: Any = None,
                 epochs: int = 1,
                 batch_size: int = 32,
                 validation_split: float = 0.0,
                 shuffle: bool = True,
                 store: Optional[Any] = None,
                 label_col: str = "label",
                 feature_cols: Optional[Tuple[str, ...]] = None,
                 output_col: str = "prediction",
                 cache: str = "memory",
                 rows_per_group: int = 4096,
                 spill_dir: Optional[str] = None,
                 seed: int = 0):
        if (train_fn is None) == (model_init is None):
            raise ValueError(
                "pass exactly one of train_fn (custom loop) or "
                "model_init+loss_fn (declarative loop)")
        if model_init is not None and loss_fn is None:
            raise ValueError("declarative fit needs loss_fn")
        if predict_fn is None:
            raise ValueError(
                "predict_fn is required — the returned JaxModel's "
                "transform/predict contract depends on it")
        if not 0.0 <= validation_split < 1.0:
            raise ValueError(
                f"validation_split must be in [0, 1), got {validation_split}")
        self.train_fn = train_fn
        self.predict_fn = predict_fn
        self.num_workers = num_workers
        self._env = env
        self._label_col = label_col
        self._feature_cols = feature_cols
        self._output_col = output_col
        if cache not in ("memory", "disk"):
            raise ValueError(
                f"cache must be 'memory' or 'disk', got {cache!r}")
        self._cache = cache
        self._rows_per_group = int(rows_per_group)
        self._spill_dir = spill_dir
        if store is not None:
            from .store import _REMOTE_SCHEMES, Store

            if isinstance(store, str):
                # A str store is a LOCAL checkpoint directory, used
                # verbatim.  Remote prefixes must come in as Store
                # objects once CheckpointManager writes through the
                # Store IO backend; today it writes the local
                # filesystem only, so a raw "gs://..." string would
                # silently become a literal ./gs: directory.
                if store.startswith(_REMOTE_SCHEMES):
                    raise ValueError(
                        f"store={store!r}: remote store prefixes are not "
                        "supported as plain strings — CheckpointManager "
                        "writes the local filesystem; pass a local "
                        "directory path (or mount the bucket)")
            else:
                # Store abstraction (orchestrate/store.py): checkpoints
                # go under the prefix's run-path discipline.
                store = Store.create(store).get_checkpoint_path()
                if store.startswith(_REMOTE_SCHEMES):
                    raise ValueError(
                        f"store checkpoint path {store!r}: "
                        "CheckpointManager writes the local filesystem "
                        "only; use a LocalStore (or mount the bucket)")
        self._spec = None if model_init is None else {
            "model_init": model_init, "loss_fn": loss_fn,
            "optimizer": optimizer, "epochs": int(epochs),
            "batch_size": int(batch_size),
            "validation_split": float(validation_split),
            "shuffle": bool(shuffle), "store": store, "seed": int(seed)}
        self.history_: List[Dict[str, float]] = []

    def _shards(self, x: np.ndarray, y: Optional[np.ndarray]
                ) -> Tuple[list, list]:
        xs = np.array_split(np.asarray(x), self.num_workers)
        ys = (np.array_split(np.asarray(y), self.num_workers)
              if y is not None else [None] * self.num_workers)
        return xs, ys

    @staticmethod
    def _equalize(shards: list) -> list:
        """Wrap-pad every shard to the longest shard's length.

        Declarative workers issue name-matched collectives in lockstep, so
        every rank MUST see the same shard length (same batch count) —
        the repartition-to-equal-shards discipline of the reference's
        estimators (spark/common/util.py prep for equal row groups).
        Padding duplicates a shard's OWN rows only; validation rows are
        split off globally before this runs, so they can never leak in.
        """
        target = max(len(s) for s in shards)

        def pad(s):
            if s is None or len(s) == target:
                return s
            reps = [s[i % len(s)] for i in range(target - len(s))]
            return np.concatenate([s, np.stack(reps)]) if reps else s

        return [pad(s) for s in shards]

    def fit(self, x: np.ndarray, y: Optional[np.ndarray] = None,
            **fit_kwargs) -> JaxModel:
        env = dict(self._env or {})
        if isinstance(x, ParquetSource) and self._spec is None:
            raise ValueError(
                "ParquetSource requires the declarative estimator "
                "(model_init/loss_fn); a custom train_fn receives numpy "
                "shards")
        if _is_spark_dataframe(x):
            if self._spec is None:
                raise ValueError(
                    "DataFrame fit requires the declarative estimator "
                    "(model_init/loss_fn) — a custom train_fn receives "
                    "numpy shards")
            return self._fit_spark_df(x, y, env)
        if self._spec is not None:
            if fit_kwargs:
                raise TypeError(
                    "declarative fit() takes no per-call kwargs — pass "
                    f"them to the constructor (got {sorted(fit_kwargs)})")
            if isinstance(x, ParquetSource):
                return self._fit_parquet(x, y, env)
            if y is None:
                raise ValueError("declarative fit needs y (loss_fn is "
                                 "called as loss_fn(params, xb, yb))")
            x, y = np.asarray(x), np.asarray(y)
            xs, ys, xv, yv = split_and_shard(
                x, y, self._spec["validation_split"], self.num_workers)
            return self._run_declarative(
                self._spec, [(xs[r], ys[r], xv[r], yv[r])
                             for r in range(self.num_workers)], env)

        xs, ys = self._shards(x, y)
        with Executor(self.num_workers, env=env) as ex:
            # One concurrent dispatch — workers may collectively train
            # (allreduce etc.), so they must all enter together.  Shards
            # ride per-rank KV keys: each worker downloads only its own.
            results = ex.run(_worker_fit,
                             args=(self.train_fn, fit_kwargs),
                             per_rank_args=[(xs[r], ys[r])
                                            for r in range(self.num_workers)])
        return JaxModel(results[0], self.predict_fn,
                        df_meta=self._df_meta())


    def _fit_parquet(self, source: ParquetSource, y, env) -> JaxModel:
        """Assign Parquet row groups round-robin and let each worker read
        its own (driver touches only metadata)."""
        import pyarrow.parquet as pq

        if y is not None:
            raise ValueError(
                "ParquetSource carries labels via label_col; pass y=None")
        n_rg = pq.ParquetFile(source.path).metadata.num_row_groups
        if n_rg < self.num_workers:
            raise ValueError(
                f"{source.path} has {n_rg} row groups < num_workers="
                f"{self.num_workers}; rewrite with smaller row groups "
                "or fewer workers")
        assign = [list(range(r, n_rg, self.num_workers))
                  for r in range(self.num_workers)]
        spec = dict(self._spec)
        spec["parquet"] = {"path": source.path,
                           "label_col": source.label_col,
                           "feature_cols": (list(source.feature_cols)
                                            if source.feature_cols
                                            else None)}
        return self._run_declarative(
            spec, [(assign[r], None, None, None)
                   for r in range(self.num_workers)], env)

    def _fit_spark_df(self, df, y, env) -> JaxModel:
        """fit(df): training runs INSIDE Spark barrier tasks, each on its
        own partition's rows — the driver never collects the dataset
        (ref: spark estimators' fit(df) over dataframe->Petastorm,
        spark/common/util.py; barrier training, spark/keras/remote.py).
        Rank r's shard is partition r of ``df.repartition(num_workers)``;
        the worker-side split/pad discipline matches the Parquet path."""
        if y is not None:
            raise ValueError(
                "DataFrame fit carries labels in label_col "
                f"({self._label_col!r}); pass y=None")
        from . import spark as spark_mod

        spec = dict(self._spec)
        meta = {"label_col": self._label_col,
                "feature_cols": (list(self._feature_cols)
                                 if self._feature_cols else None)}
        stream = self._cache == "disk"
        if stream:
            # Out-of-core feed (ref: spark/common/util.py prepare_data):
            # the barrier task spills its partition stream to Parquet
            # row groups and trains by streaming them back — a partition
            # larger than task memory never materializes.
            meta["rows_per_group"] = self._rows_per_group
            meta["spill_dir"] = self._spill_dir
            spec["spark_df_stream"] = meta
        else:
            spec["spark_df"] = meta
        env = collective_worker_env(env, local_coordinator=False)

        def task(rows):
            return _declarative_fit(spec, rows, None, None, None)

        results = spark_mod.run_on_dataframe(
            task, df, num_proc=self.num_workers, env=env, stream=stream)
        return self._finish_declarative(results)

    def _run_declarative(self, spec, per_rank_args, env) -> JaxModel:
        """Shared dispatch tail for both declarative input modes."""
        env = collective_worker_env(env)
        with Executor(self.num_workers, env=env) as ex:
            results = ex.run(_declarative_fit, args=(spec,),
                             per_rank_args=per_rank_args)
        return self._finish_declarative(results)

    def _finish_declarative(self, results) -> JaxModel:
        check_one_world(results, self.num_workers)
        self.history_ = results[0]["history"]
        return JaxModel(results[0]["params"], self.predict_fn,
                        df_meta=self._df_meta())

    def _df_meta(self) -> Dict[str, Any]:
        return estimator_df_meta(self)


def estimator_df_meta(est) -> Dict[str, Any]:
    """The df_meta dict shared by every estimator's model handle
    (label/feature/output columns for transform(df) and fit(df))."""
    return {"label_col": est._label_col,
            "feature_cols": (list(est._feature_cols)
                             if est._feature_cols else None),
            "output_col": est._output_col}


def check_one_world(results, num_workers: int) -> None:
    """One-world guard shared by every estimator dispatch tail: workers
    that fail to rendezvous (coordinator unreachable, stale world in a
    reused process) would each train as a size-1 island on its own shard
    — that must be an error, not a silently under-trained model.  Each
    worker reports its ``hvd.size()`` in the result dict's ``size``."""
    sizes = {r["size"] for r in results if r}
    if sizes != {num_workers}:
        raise RuntimeError(
            f"workers did not form one world of {num_workers} "
            f"(saw sizes {sizes}) — collective training did not run")


def _is_spark_dataframe(x) -> bool:
    """Duck-typed Spark DataFrame detection (pyspark may not be
    importable here; barrier tasks see the real class)."""
    return (hasattr(x, "rdd") and hasattr(x, "columns")
            and hasattr(x, "repartition"))


def split_and_shard(x: np.ndarray, y: np.ndarray, validation_split: float,
                    num_workers: int):
    """Shared estimator data discipline: GLOBAL validation tail split
    BEFORE sharding/equalization (padding can never leak train rows into
    validation), equalized train shards (same lockstep collective count
    per worker), round-robin val shards with a whole-set fallback so
    every rank enters the val-metric collectives.

    Returns (xs, ys, xv, yv) — per-rank lists; xv/yv entries are None
    when validation_split == 0."""
    n_val = int(round(len(x) * validation_split))
    x_tr, y_tr = x[:len(x) - n_val], y[:len(y) - n_val]
    if len(x_tr) < num_workers:
        raise ValueError(
            f"need at least num_workers={num_workers} TRAINING samples "
            f"after the validation split, got {len(x_tr)} "
            f"(n={len(x)}, validation_split={validation_split})")
    xs = JaxEstimator._equalize(np.array_split(x_tr, num_workers))
    ys = JaxEstimator._equalize(np.array_split(y_tr, num_workers))
    if n_val:
        xv = [x[len(x) - n_val:][r::num_workers] for r in range(num_workers)]
        yv = [y[len(y) - n_val:][r::num_workers] for r in range(num_workers)]
        xv = [s if len(s) else x[len(x) - n_val:] for s in xv]
        yv = [s if len(s) else y[len(y) - n_val:] for s in yv]
    else:
        xv = yv = [None] * num_workers
    return xs, ys, xv, yv


def collective_worker_env(env: Optional[Dict[str, str]],
                          local_coordinator: bool = True) -> Dict[str, str]:
    """Env for Executor workers that run COLLECTIVE training: pin them to
    the CPU platform (an accelerator-steering outer env would make every
    worker claim the real TPU) and give them a JAX coordination-service
    address so ``hvd.init()`` forms one distributed world — without it
    every worker is a silent size-1 island and collectives no-op.

    ``local_coordinator=False`` (the Spark barrier-task paths) skips the
    ``127.0.0.1:<free_port>`` default: a driver-chosen localhost address
    is only reachable when every worker is colocated with the driver, so
    barrier tasks instead derive the coordinator from rank 0's task
    address over the rendezvous KV (``spark._enter_barrier``)."""
    env = dict(env or {})
    env.setdefault("JAX_PLATFORMS", "cpu")
    if local_coordinator:
        env.setdefault("HVDT_COORDINATOR_ADDR", f"127.0.0.1:{free_port()}")
    return env
