"""Distributed fit launched FROM the Spark data plane.

The reference's signature architecture is that distributed training starts
INSIDE the cluster's executors: LightGBM workers ARE the Spark partitions
(reference: LightGBMClassifier.scala:35-47 — coalesce -> mapPartitions ->
``LGBM_NetworkInit`` with a machine list aggregated on the driver,
LightGBMUtils.scala:98-160), and CNTK training is launched from the driver
onto the worker ring (CommandBuilders.scala:149-267). This module is that
move for the TPU-native framework: a **barrier-stage** job in which every
partition task joins the JAX coordination service, wraps its partition's
Arrow batches as its :class:`ShardedDataFrame` shard, and runs the
existing multi-process collective fit (``TpuLearner.fit`` / GBDT
``fit``) — the histogram/gradient all-reduces ride XLA collectives over
the fleet exactly as they do under the MMLTPU_* launcher contract.

The rendezvous replaces the reference's driver-socket machine-list
aggregation with Spark's own ``BarrierTaskContext.allGather``: task 0
binds a free port on its host and gathers ``host:port`` to everyone;
that address seeds :func:`mmlspark_tpu.parallel.distributed.initialize`
(process_id = partitionId). Every task ends the fit holding the IDENTICAL
replicated model (the collective-fit invariant the fleet tests pin);
task 0 ships it back to the driver as one Arrow binary row.

Requires ``DataFrame.mapInArrow(..., barrier=True)`` (pyspark >= 3.5; the
test shim implements the same contract with real concurrent OS
processes). Use :func:`wrapDistributed`::

    from mmlspark_tpu.spark import wrapDistributed
    est = wrapDistributed(LightGBMClassifier(), numWorkers=4)
    model = est.fit(spark_df)          # fits ACROSS the executors
    scored = model.transform(spark_df)
"""

from __future__ import annotations

import io
import os
import tempfile
import zipfile
from typing import Optional


def stage_to_bytes(stage) -> bytes:
    """Serialize any registered stage (fitted models included) to a
    self-contained zip of its ``save_stage`` directory — the wire format
    for shipping estimators driver->executors and the fitted model back."""
    from ..core.serialize import save_stage
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "stage")
        save_stage(stage, path)
        buf = io.BytesIO()
        with zipfile.ZipFile(buf, "w", zipfile.ZIP_DEFLATED) as z:
            for root, _, files in os.walk(path):
                for f in files:
                    full = os.path.join(root, f)
                    z.write(full, os.path.relpath(full, path))
        return buf.getvalue()


def stage_from_bytes(blob: bytes):
    from ..core.serialize import load_stage
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "stage")
        with zipfile.ZipFile(io.BytesIO(blob)) as z:
            z.extractall(path)
        return load_stage(path)


class BarrierFitTask:
    """The function object ``mapInArrow(..., barrier=True)`` runs on every
    partition. Deliberately a plain picklable class (no closures): real
    pyspark ships it via cloudpickle, the test shim via spawn+pickle.

    Protocol per task:
      1. ``BarrierTaskContext.allGather`` elects task 0's ``host:port`` as
         the JAX coordinator (the machine-list role,
         LightGBMUtils.scala:98-160).
      2. ``distributed.initialize(process_id=partitionId)`` — fleet
         rendezvous, bounded by MMLTPU_INIT_TIMEOUT.
      3. Partition batches -> native frame -> ``ShardedDataFrame`` shard;
         the wrapped estimator's fit runs its collective path.
      4. Task 0 yields the fitted model as a single binary Arrow row.
    """

    def __init__(self, est_blob: bytes, schema_blob: bytes):
        self.est_blob = est_blob
        self.schema_blob = schema_blob   # input Arrow schema (empty shards)

    def __call__(self, batches):
        import socket

        import pyarrow as pa
        from pyspark import BarrierTaskContext

        ctx = BarrierTaskContext.get()
        pid = ctx.partitionId()
        n = len(ctx.getTaskInfos())

        # task 0 binds the coordinator port on its own host; allGather is
        # the broadcast (replaces the reference's driver-socket
        # aggregation). The probe-close-rebind dance is racy by nature
        # (the reference's findFreePort is too); a stolen port fails the
        # rendezvous inside MMLTPU_INIT_TIMEOUT rather than hanging
        msg = ""
        if pid == 0:
            host = _task_host(ctx)
            with socket.socket() as s:
                s.bind((host, 0))
                msg = f"{host}:{s.getsockname()[1]}"
        coordinator = ctx.allGather(msg)[0]

        from ..parallel import distributed as dist
        if n > 1:
            try:
                dist.initialize(coordinator_address=coordinator,
                                num_processes=n, process_id=pid)
            except RuntimeError as e:
                # a REUSED executor python worker has often already run
                # JAX (e.g. a mapInArrow transform), and jax.distributed
                # cannot initialize after backends exist
                raise RuntimeError(
                    "distributed fit needs a fresh executor python worker "
                    "per barrier task (JAX's coordination service must "
                    "initialize before any other JAX work in the "
                    "process). Set spark.python.worker.reuse=false on the "
                    "SparkSession, or run the distributed fit before "
                    "executor-side transforms") from e
        try:
            from ..parallel.dataplane import ShardedDataFrame
            from . import _pdf_to_native

            schema = pa.ipc.read_schema(pa.py_buffer(self.schema_blob))
            got = list(batches)
            table = (pa.Table.from_batches(got) if got
                     else schema.empty_table())
            shard = ShardedDataFrame.fromLocal(_pdf_to_native(
                table.to_pandas()))
            model = stage_from_bytes(self.est_blob).fit(shard)
            if pid == 0:   # model is replicated; one task reports it
                yield pa.RecordBatch.from_arrays(
                    [pa.array([stage_to_bytes(model)], type=pa.binary())],
                    names=["model"])
        finally:
            if n > 1:
                dist.shutdown()


def _task_host(ctx) -> str:
    """Task 0's rendezvous host from the barrier context (executor address;
    loopback when Spark reports none — local[...] masters)."""
    try:
        addr = ctx.getTaskInfos()[ctx.partitionId()].address or ""
    except Exception:
        addr = ""
    host = addr.rsplit(":", 1)[0].strip("[]")
    return host if host and host != "localhost" else "127.0.0.1"


def fit_distributed(inner, sdf, num_workers: Optional[int] = None):
    """Run ``inner.fit`` as a barrier-stage job across ``sdf``'s partitions
    (coalesced/repartitioned to ``num_workers`` when given) and return the
    fitted native model. Every partition becomes one fleet process."""
    import pyarrow as pa
    from pyspark.sql import types as T

    if num_workers is None:
        # a post-shuffle frame can carry hundreds of partitions; a barrier
        # stage needs that many SIMULTANEOUS slots and that many fleet
        # processes, so default to the cluster's parallelism instead of
        # whatever partitioning the frame happens to have
        try:
            num_workers = min(sdf.rdd.getNumPartitions(),
                              sdf.sparkSession.sparkContext
                              .defaultParallelism)
        except Exception:
            num_workers = None     # shim / exotic sessions: keep as-is
    if num_workers is not None:
        try:
            have = sdf.rdd.getNumPartitions()
        except Exception:
            have = None
        if have != num_workers:
            # coalesce when shrinking (the reference's own move,
            # LightGBMClassifier.scala:35 — no shuffle); repartition
            # only when the fleet must GROW
            sdf = (sdf.coalesce(num_workers)
                   if have is not None and have > num_workers
                   else sdf.repartition(num_workers))

    # input schema, captured driver-side so EMPTY partitions can still
    # build a typed zero-row shard (uneven shards are a fleet invariant).
    # Prefer the catalyst-schema conversion (no Spark job); fall back to
    # sampling rows where the session can't convert (the shim)
    schema = None
    try:
        from pyspark.sql.pandas.types import to_arrow_schema
        schema = to_arrow_schema(sdf.schema)
    except Exception:
        pass
    if schema is None:
        head = sdf.limit(64)
        to_arrow = getattr(head, "toArrow", None)
        if callable(to_arrow):
            schema = to_arrow().schema
        else:
            schema = pa.Table.from_pandas(head.toPandas()).schema
    task = BarrierFitTask(stage_to_bytes(inner),
                          schema.serialize().to_pybytes())
    out_schema = T.StructType([T.StructField("model", T.BinaryType(), True)])
    try:
        res = sdf.mapInArrow(task, out_schema, barrier=True)
    except TypeError as e:
        raise RuntimeError(
            "distributed fit needs DataFrame.mapInArrow(..., barrier=True) "
            "(pyspark >= 3.5); upgrade pyspark or use wrap() for a "
            "driver-side fit") from e
    rows = res.toPandas()
    if len(rows) != 1:
        raise RuntimeError(
            f"barrier fit returned {len(rows)} model rows (expected exactly "
            f"1 from task 0) — did a task fail silently?")
    return stage_from_bytes(bytes(rows["model"].iloc[0]))


def wrapDistributed(stage, numWorkers: Optional[int] = None):
    """Wrap a TPU-native Estimator so ``fit`` runs ACROSS the Spark
    executors as one collective fleet (the reference's
    partitions-are-workers architecture) instead of collecting to the
    driver. ``transform`` on the result runs via mapInArrow as usual."""
    from ..core.pipeline import Estimator
    from . import SparkEstimator
    if not isinstance(stage, Estimator):
        raise TypeError(
            f"wrapDistributed expects an Estimator (got "
            f"{type(stage).__name__}); transformers have no fit to "
            f"distribute — use wrap()")
    return SparkEstimator(stage, distributed=True, numWorkers=numWorkers)
