"""TpuModel: batched pjit inference as a pipeline stage.

The CNTKModel analog (reference: cntk-model/.../CNTKModel.scala:125-261):
the reference broadcasts a serialized CNTK net, then per partition feeds
rows one-by-one through JNI FloatVectorVectors (:67-74, the known copy
bottleneck) into native eval. Here: the whole minibatch column block goes
host->HBM in one device_put sharded over the mesh's data axis, and the
forward pass is one jitted XLA program; output-node selection by layer name
(reference :98-108) is the static ``output_layer`` argument (see
models/modules._LayerTap).
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np

from ..core.dataframe import DataFrame
from ..core.params import (ComplexParam, DictParam, IntParam, ListParam,
                           StringParam)
from ..core.pipeline import Transformer
from ..core.schema import image_to_array, is_image_column
from ..core.utils import get_logger, to_float32_matrix
from ..parallel import mesh as meshlib
from .. import telemetry

log = get_logger("tpu_model")


def _coerce_wire_dtype(x: np.ndarray) -> np.ndarray:
    """Cast an unsupported transfer dtype onto the wire table (int -> int32,
    else float32) — with a range check and a one-time warning instead of
    the previous silent cast (ADVICE r5): int64 feature values beyond the
    int32 range would otherwise be silently corrupted, and float64 inputs
    lose precision without a trace."""
    if np.issubdtype(x.dtype, np.integer):
        info = np.iinfo(np.int32)
        if x.size and (x.min() < info.min or x.max() > info.max):
            raise ValueError(
                f"{x.dtype} feature values exceed the int32 transfer range "
                f"[{info.min}, {info.max}]; rescale or re-index them "
                f"before scoring (the device wire format is int32)")
        tgt = np.int32
    else:
        tgt = np.float32
    telemetry.warn_once(
        log, "wire-dtype-downcast",
        "input dtype %s is not a device wire format; casting to %s "
        "(precision beyond %s is dropped — cast explicitly to silence "
        "this)", x.dtype, np.dtype(tgt).name, np.dtype(tgt).name)
    return x.astype(tgt)


def _next_pow2(n: int) -> int:
    """Smallest power of two >= n (>= 8 so tiny serving batches share one
    compiled shape)."""
    t = 8
    while t < n:
        t <<= 1
    return t


def _prep_input(df: DataFrame, col_name: str, input_shape) -> np.ndarray:
    """Column -> device-ready batch. Images become NHWC and STAY uint8 —
    the device cast is free and shipping bytes moves 4x less host->HBM
    traffic than f32 (the transfer is the inference bottleneck; reference
    ships f32 JNI vectors, CNTKModel.scala:67-74). Flat vectors are f32,
    reshaped from CHW (the UnrollImage layout, = CNTK's input layout) to
    NHWC when input_shape=(C,H,W) is given."""
    col = df.col(col_name)
    if is_image_column(df, col_name):
        if len(col) == 0:
            # layout unknowable from an empty shard; multi-host scoring
            # adopts a peer's (see _transform_multihost's meta allgather)
            return np.zeros((0, 1, 1, 3), np.uint8)
        return np.stack([image_to_array(r) for r in col])
    mat = to_float32_matrix(col)
    if input_shape:
        c, h, w = input_shape
        return mat.reshape(-1, c, h, w).transpose(0, 2, 3, 1)
    return mat


class TpuModel(Transformer):
    """Batch inference over a device mesh.

    Params mirror CNTKModel's surface: inputCol/outputCol, miniBatchSize
    (reference default 10 rows/JNI call; ours defaults to 4096 rows/XLA call),
    outputLayer = outputNodeName (truncation), inputShape = CHW shape for
    flat-vector inputs.
    """

    inputCol = StringParam("input column (vectors or images)", default="features")
    outputCol = StringParam("output column", default="scores")
    modelConfig = DictParam("declarative model config (models.build_model)",
                            default=None)
    modelParams = ComplexParam("trained parameter pytree", default=None)
    outputLayer = StringParam("layer name to emit (headless nets)", default="")
    inputShape = ListParam("CHW shape to reshape flat vectors", default=())
    miniBatchSize = IntParam("rows per device batch", default=4096, min=1)
    transferDtype = StringParam(
        "wire dtype for float inputs: bfloat16 halves host->HBM traffic "
        "(inputs are cast on device anyway; ~3 decimal digits kept)",
        default="float32", choices=("float32", "bfloat16"))
    tensorParallel = IntParam(
        "size of the model (TP) mesh axis for inference: wide Dense "
        "kernels shard over it (same placement rules as training), so a "
        "model whose params exceed one chip's HBM can still serve; batch "
        "stays sharded over the remaining data axis. Multi-host: must "
        "divide the local device count (model axis rides ICI)", default=1,
        min=1)

    def setModelLocation(self, path: str) -> "TpuModel":
        """Load a saved model — the CNTKModel.setModelLocation parity point,
        fed by ModelDownloader. Accepts either a directory ({config.json,
        params.msgpack}) or a packed ``.model`` zip artifact."""
        import json
        import os
        if os.path.isfile(path):
            from .downloader import unpack_model
            with open(path, "rb") as f:
                config, params = unpack_model(f.read())
            self.setModelConfig(config)
            self.setModelParams(params)
            return self
        from flax import serialization
        with open(os.path.join(path, "config.json")) as f:
            self.setModelConfig(json.load(f))
        with open(os.path.join(path, "params.msgpack"), "rb") as f:
            self.setModelParams(serialization.msgpack_restore(f.read()))
        return self

    def setModelSchema(self, schema) -> "TpuModel":
        """Load from a ModelDownloader ModelSchema (local uri)."""
        return self.setModelLocation(schema.uri)

    def layerNames(self) -> list[str]:
        from .modules import build_model
        return build_model(self.getModelConfig()).layer_names()

    def _is_moe(self) -> bool:
        cfg = self.getModelConfig()
        return (cfg.get("type") == "transformer"
                and cfg.get("num_experts", 0) > 0)

    def _cached_mesh(self):
        """One mesh per (device topology, tp) — a new Mesh object per call
        would also defeat the device-params cache below."""
        tp = self.getTensorParallel()
        devs = (tuple(id(d) for d in jax.devices()), tp,
                meshlib.in_local_fit())
        if getattr(self, "_mesh_key", None) != devs:
            if tp > 1:
                if meshlib.in_local_fit():
                    # local-fit trials pin every program to ONE device
                    raise ValueError(
                        "tensorParallel serving is unavailable inside "
                        "local-fit mode (fleet tuner trials run "
                        "single-device)")
                if meshlib.effective_process_count() > 1:
                    meshlib.require_inner_block_local(
                        {"tensorParallel": tp})
            # create_mesh raises when tp does not divide the device count
            self._mesh_cache = meshlib.create_mesh(model=tp)
            self._mesh_key = devs
        return self._mesh_cache

    def _device_params(self, mesh):
        """Device-resident params, uploaded ONCE per (params, mesh) — the
        serving loop calls transform per request batch, and re-shipping the
        whole tree host->HBM each time (~100 MB for a ResNet-50) would
        dominate request latency. Replicated by default; with
        ``tensorParallel > 1`` wide Dense kernels shard over the model
        axis (the training-side placement rules), so per-chip residency is
        ~1/tp of the sharded mass.

        Cache validity is object identity via STRONG references (`is`, not
        id()): holding the uploaded tree alive means a new tree can never
        alias a freed id. Updating weights therefore means setModelParams
        (a new tree), the framework-wide convention — in-place mutation of
        the current tree is not a supported update path."""
        host = self.getModelParams()
        if (getattr(self, "_dev_params_src", None) is not host
                or getattr(self, "_dev_params_mesh", None) is not mesh):
            if self.getTensorParallel() > 1:
                self._dev_params = meshlib.shard_params_tp(
                    host, mesh, list(meshlib.TP_PARAM_RULES))
            else:
                self._dev_params = meshlib.put_replicated(host, mesh)
            self._dev_params_src = host
            self._dev_params_mesh = mesh
        return self._dev_params

    def _forward(self, mesh):
        """``module.apply`` as transform dispatches it, the module built for
        ``mesh`` (None: a one-device program — what exportStableHLO writes)."""
        from .modules import build_model
        module = build_model(self.getModelConfig(), mesh=mesh)
        ol = self.getOutputLayer() or None
        if self._is_moe():
            # MoE routing must know which rows are mesh padding: they
            # may not claim expert capacity (same contract as training)
            return lambda p, x, m: module.apply(p, x, output_layer=ol,
                                                row_mask=m)
        return lambda p, x: module.apply(p, x, output_layer=ol)

    # one jitted program per (config, output_layer, tp); reused across
    # transforms
    def _apply_fn(self):
        key = getattr(self, "_apply_cache_key", None)
        tp = self.getTensorParallel()
        cur = (tuple(sorted((k, str(v)) for k, v in self.getModelConfig().items())),
               self.getOutputLayer(), tp)
        if key != cur or not hasattr(self, "_apply_jit"):
            kw = {}
            if tp > 1:
                # the last Dense's columns land model-axis-sharded under
                # the TP rules; pin the OUTPUT to data-only sharding so
                # host reads (np.asarray / local_rows) see whole rows
                from jax.sharding import NamedSharding, PartitionSpec as P
                kw["out_shardings"] = NamedSharding(self._cached_mesh(),
                                                    P("data"))
            self._apply_jit = jax.jit(self._forward(self._cached_mesh()),
                                      **kw)
            self._apply_cache_key = cur
        return self._apply_jit

    def exportStableHLO(self, path: str, batch: Optional[int] = None,
                        in_dtype=None) -> str:
        """AOT-lower the inference program to StableHLO text and write it to
        ``path`` — a compiler-level deployment artifact any XLA-hosting
        runtime (PJRT plugins, IREE, serving systems) can consume without
        Python. The reference's deployment unit is a CNTK model file run by
        a JVM wrapper (SURVEY.md §2.2); here the model IS a compiled
        program, so the export carries the whole forward computation.

        Lowering uses abstract shapes (no device transfer, no execution);
        ``batch`` defaults to miniBatchSize. Requires modelConfig to know
        the input feature shape (inputShape, or model-config dims).

        The input dtype matches what transform() actually compiles and
        serves: int32 for token models; uint8 for image-shaped models fed
        image columns (``_prep_input`` keeps bytes on the wire); otherwise
        float32, or bfloat16 under transferDtype. Flat-vector inputs
        (inputShape set) always arrive as floats. Pass ``in_dtype`` to
        override (e.g. ``np.float32`` to export a float-input variant of an
        image model)."""
        if self.getModelParams() is None:
            raise ValueError("TpuModel has no params; set modelParams or "
                             "call setModelLocation before exporting")
        cfg = self.getModelConfig()
        from .modules import TOKEN_MODELS, example_input
        b = batch or self.getMiniBatchSize()
        if self.getInputShape():
            # the serving shape: _prep_input reshapes CHW vectors to NHWC
            c, h, w = self.getInputShape()
            row_shape = (h, w, c)
        else:
            row_shape = tuple(example_input(cfg).shape[1:])
        if in_dtype is None:
            if cfg.get("type") in TOKEN_MODELS:
                in_dtype = np.int32
            elif (cfg.get("type") in ("convnet", "resnet", "resnet50")
                  and not self.getInputShape()):
                in_dtype = np.uint8  # image rows ship as bytes
            elif self.getTransferDtype() == "bfloat16":
                import ml_dtypes
                in_dtype = ml_dtypes.bfloat16
            else:
                in_dtype = np.float32
        x_spec = jax.ShapeDtypeStruct((b,) + row_shape, in_dtype)
        p_spec = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), np.result_type(a)),
            self.getModelParams())
        fn = jax.jit(self._forward(None))
        args = ((p_spec, x_spec,
                 jax.ShapeDtypeStruct((b,), np.float32))
                if self._is_moe() else (p_spec, x_spec))
        text = fn.lower(*args).as_text()
        with open(path, "w") as f:
            f.write(text)
        return path

    def warmup(self, example_df: DataFrame, max_rows: Optional[int] = None
               ) -> "TpuModel":
        """Pre-compile every bucketed batch shape up to ``max_rows``
        (default miniBatchSize) by scoring tiled copies of ``example_df``'s
        first row. Serving loops call this once at startup so no client
        request ever pays an XLA compile (seconds) in its latency."""
        row = {k: example_df.col(k)[:1] for k in example_df.columns}
        cap = min(self.getMiniBatchSize(),
                  _next_pow2(max_rows or self.getMiniBatchSize()))
        t = 8
        while True:
            n = min(t, cap)
            tiled = DataFrame({k: np.concatenate([v] * n)
                               for k, v in row.items()})
            self.transform(tiled)
            if t >= cap:
                break
            t <<= 1
        return self

    def capture(self, columns):
        """The inference forward pass as a traced callable (cross-stage
        fusion, core/capture.py): the SAME ``module.apply`` body the
        jitted transform dispatches, minus the host-side chunking /
        bucketing — the fused segment dispatches the whole batch as part
        of ONE pipeline program. Offered for single-process, non-TP,
        non-MoE models with flat float inputs (the wire shape a fused
        column feed can produce); everything else keeps the staged
        transform's windowed dispatch machinery."""
        from ..core.capture import StageCapture
        cfg = self.getModelConfig()
        if (cfg is None or self.getModelParams() is None
                or self.getInputCol() not in columns):
            return None
        if (self._is_moe() or self.getTensorParallel() > 1
                or meshlib.effective_process_count() > 1
                or self.getInputShape()):
            return None
        from .modules import example_input
        try:
            ex = example_input(cfg)
        except Exception:
            return None
        if ex.ndim != 2 or np.asarray(ex).dtype.kind not in "f":
            return None     # image/token models keep the staged wire path
        from .modules import build_model
        module = build_model(cfg)
        ol = self.getOutputLayer() or None

        def fn(p, xs):
            return (module.apply(p, xs[0].astype(np.float32),
                                 output_layer=ol),)

        return StageCapture(fn, inputs=(self.getInputCol(),),
                            outputs=(self.getOutputCol(),),
                            params=self.getModelParams(),
                            tag="tpu_model.apply")

    def transform(self, df: DataFrame) -> DataFrame:
        if self.getModelParams() is None:
            raise ValueError("TpuModel has no params; set modelParams or "
                             "call setModelLocation")
        x = _prep_input(df, self.getInputCol(), tuple(self.getInputShape()))
        from .modules import TOKEN_MODELS
        if self.getModelConfig().get("type") in TOKEN_MODELS:
            x = x.astype(np.int32)
        elif x.dtype == np.float32 and self.getTransferDtype() == "bfloat16":
            import ml_dtypes
            x = x.astype(ml_dtypes.bfloat16)
        mesh = self._cached_mesh()
        apply_fn = self._apply_fn()
        from ..parallel import mesh as _meshlib
        nproc = _meshlib.effective_process_count()
        params = self._device_params(mesh)
        # tp inference is a COLLECTIVE program (sharded-matmul all-gathers
        # + the output reshard); interleaving it with another thread's
        # collective fit deadlocks (parallel/mesh.py invariant) — same
        # guard the trainers take. tp=1 programs have no collectives.
        import contextlib
        guard = (meshlib.collective_fit_lock if self.getTensorParallel() > 1
                 else contextlib.nullcontext())
        if nproc > 1:
            # multi-host: this df is the process-local shard; SPMD demands
            # identical shapes/call counts everywhere, so the fleet agrees
            # on a chunk count and every process dispatches that many
            # fixed-shape global chunks in lockstep (HBM stays bounded by
            # miniBatchSize, not shard size)
            with guard:
                y = self._transform_multihost(x, mesh, apply_fn, params)
            if y.ndim == 1:
                return df.withColumn(self.getOutputCol(), y)
            from ..core.utils import object_column
            return df.withColumn(self.getOutputCol(), object_column(y))

        bs = self.getMiniBatchSize()

        def chunks():
            for lo in range(0, len(x), bs):
                chunk = x[lo:lo + bs]
                n_real = len(chunk)
                # bucket partial chunks to the next power of two: serving
                # feeds ragged request batches, and every distinct shape
                # is a fresh XLA compile (seconds) — bucketing bounds the
                # shape set to log2(miniBatchSize) and the padding rows
                # are sliced off on read-back
                target = min(_next_pow2(n_real), bs)
                if n_real < target:
                    filler = np.zeros((target - n_real,) + chunk.shape[1:],
                                      chunk.dtype)
                    chunk = np.concatenate([chunk, filler])
                padded, _ = meshlib.pad_batch_to_devices(chunk, mesh)
                yield padded, n_real

        with guard:
            y = self._dispatch_windowed(
                chunks(), apply_fn, params,
                put=lambda a: meshlib.shard_batch(a, mesh),
                read=lambda yd, m: np.asarray(yd)[:m])

        if y.ndim == 1:
            return df.withColumn(self.getOutputCol(), y)
        from ..core.utils import object_column
        return df.withColumn(self.getOutputCol(), object_column(y))

    def _transform_multihost(self, x, mesh, apply_fn, params) -> np.ndarray:
        """Fleet-synchronized CHUNKED inference over every process's local
        shard. The fleet agrees ONCE (allgather) on the chunk count — the
        max over processes at miniBatchSize rows per chunk — then every
        process makes that many identical-shape global calls in lockstep,
        short shards contributing zero-padded dummy chunks (the fitStream
        drain pattern). Bounds HBM at ~window * miniBatchSize per process
        where the previous whole-shard dispatch scaled with shard size;
        a windowed pending queue overlaps transfer with compute like the
        single-host path."""
        from jax.experimental import multihost_utils

        from ..parallel import mesh as meshlib

        per_proc = mesh.shape["data"] // meshlib.effective_process_count()
        n = len(x)
        # shard size AND row layout agreed fleet-wide in one allgather: a
        # zero-row shard cannot know the feature shape/dtype, so it adopts
        # a peer's to build its dummy chunks (dims padded into a fixed-size
        # int vector; last slot is a dtype code)
        import ml_dtypes
        dtypes = [np.dtype(np.float32), np.dtype(np.int32),
                  np.dtype(np.uint8), np.dtype(ml_dtypes.bfloat16)]
        meta = np.full(10, -1, np.int64)
        meta[0] = n
        if n > 0:
            if np.dtype(x.dtype) not in dtypes:
                # the wire table covers the supported transfer dtypes; cast
                # anything else (f64/i64 reaching transform) like the
                # single-host path accepts instead of an opaque index error
                # — range-checked and warned, never silent (ADVICE r5)
                x = _coerce_wire_dtype(x)
            meta[1] = x.ndim - 1
            meta[2:2 + x.ndim - 1] = x.shape[1:]
            meta[-1] = dtypes.index(np.dtype(x.dtype))
        gathered = multihost_utils.process_allgather(meta)
        max_n = int(gathered[:, 0].max())
        if max_n == 0:
            return np.empty((0,))
        # fixed per-process chunk length, identical fleet-wide (derived
        # from gathered values only): miniBatchSize rounded to the local
        # share of the data axis, but never beyond the fleet's LARGEST
        # shard — a small scoring call must not pad (and compile) a full
        # miniBatchSize of dummy rows
        bs = max(min(self.getMiniBatchSize(), max_n), per_proc)
        bs = -(-bs // per_proc) * per_proc
        n_chunks = -(-max_n // bs)
        if n == 0:
            rows = gathered[gathered[:, 1] >= 0]
            if not len(rows):       # every shard empty yet chunks > 0
                return np.empty((0,))
            rank = int(rows[0, 1])
            x = np.zeros((0,) + tuple(int(d) for d in
                                      rows[0, 2:2 + rank]),
                         dtypes[int(rows[0, -1])])

        shape_tail = x.shape[1:]

        def chunks():
            for k in range(n_chunks):
                chunk = x[k * bs:(k + 1) * bs]
                n_real = len(chunk)    # 0 for a drained shard's dummy chunk
                if n_real < bs:
                    filler = np.zeros((bs - n_real,) + shape_tail, x.dtype)
                    chunk = (np.concatenate([chunk, filler])
                             if n_real else filler)
                yield chunk, n_real

        return self._dispatch_windowed(
            chunks(), apply_fn, params,
            put=lambda a: meshlib.put_global_batch(a, mesh),
            read=meshlib.local_rows)

    def _dispatch_windowed(self, chunks, apply_fn, params, put, read,
                           window: int = 2) -> np.ndarray:
        """Shared dispatch loop for both scoring paths: each (padded_chunk,
        n_real) ships via ``put`` and runs, with a small in-flight window —
        JAX async dispatch overlaps the next chunk's host transfer with
        compute while finished results drain through ``read`` — so HBM
        residency stays ~window * miniBatchSize instead of the dataset.
        MoE models get a per-row weight vector zeroing the padding so dummy
        rows never claim expert capacity."""
        pending: list = []
        outs: list = []
        for chunk, n_real in chunks:
            xb = put(chunk)
            if self._is_moe():
                wb = np.zeros(len(chunk), dtype=np.float32)
                wb[:n_real] = 1.0
                yd = apply_fn(params, xb, put(wb))
            else:
                yd = apply_fn(params, xb)
            pending.append((yd, n_real))
            if len(pending) > window:
                done, m = pending.pop(0)
                outs.append(read(done, m))
        outs.extend(read(yd, m) for yd, m in pending)
        return (np.concatenate(outs, axis=0) if outs
                else np.empty((0,)))

    def saveModel(self, path: str):
        """Persist {config.json, params.msgpack} (ModelDownloader layout)."""
        import json
        import os
        from flax import serialization
        os.makedirs(path, exist_ok=True)
        with open(os.path.join(path, "config.json"), "w") as f:
            json.dump(self.getModelConfig(), f)
        with open(os.path.join(path, "params.msgpack"), "wb") as f:
            f.write(serialization.msgpack_serialize(
                jax.tree_util.tree_map(np.asarray, self.getModelParams())))
