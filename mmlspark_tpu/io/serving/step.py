"""The fused decode->pad->pjit->unpad serving step.

The polling loop's scorer pays the hot path in pieces: a per-row host
decode into float32 (4x the wire bytes), a DataFrame hop, TpuModel's
chunking/bucketing logic, a host-side cast, the dispatch, and a full
score matrix read back. :class:`FusedServingStep` collapses the per-batch
work to exactly four steps, one of which touches the device:

1. **decode** (host): payload string -> one wire-format row (uint8 for
   images — bytes on the wire, cast on device where it's free);
2. **pad** (host): rows land in a zeroed ``(bucket, *row_shape)`` buffer
   — the bucket is one of :class:`~.batcher.BucketPolicy`'s static
   power-of-two shapes, so the executable cache is bounded and warm;
3. **pjit** (device, ONE dispatch): the whole cast -> forward ->
   postprocess (argmax / scores) computation is a single compiled XLA
   program per bucket, AOT-compiled through
   :class:`~...telemetry.profiler.ProfiledFunction`'s lower/compile
   cache — live traffic never compiles (and when it does, the
   cache-miss counter says so);
4. **unpad** (host): slice ``[:n_real]`` off the readback (argmax mode
   reads 4 bytes/row back, not the score matrix).

The per-bucket executables serialize into the AOT bundle
(:mod:`.bundle`) so a restarted worker's first request is warm.
"""

from __future__ import annotations

import base64
import json
import time
from typing import Callable, Optional

import numpy as np

from ... import telemetry
from ...core.utils import get_logger
from .batcher import BucketPolicy

log = get_logger("io.serving")

_m_aot_compiles = telemetry.registry.counter(
    "mmlspark_serving_aot_compiles_total",
    "bucket executables compiled ahead of live traffic (startup warmup "
    "or bundle build)")
_m_cache_hits = telemetry.registry.counter(
    "mmlspark_serving_exec_cache_hits_total",
    "dispatches served by an already-compiled bucket executable")
_m_cache_misses = telemetry.registry.counter(
    "mmlspark_serving_exec_cache_misses_total",
    "dispatches that had to compile on live traffic (a cold compile some "
    "client's latency paid for — zero when warmup/bundle covered every "
    "bucket)")


def _default_decode(row_shape, dtype):
    """base64 payload -> one wire row. The ubiquitous serving wire format
    (bench_serving's image payloads): raw bytes, base64'd for HTTP."""
    size = int(np.prod(row_shape)) if row_shape else 1

    def decode(value: str) -> np.ndarray:
        a = np.frombuffer(base64.b64decode(value), dtype=dtype)
        if a.size != size:
            raise ValueError(f"payload decodes to {a.size} {dtype} "
                             f"elements, expected {size} {row_shape}")
        return a.reshape(row_shape)
    return decode


def _default_encode(output: str):
    if output == "argmax":
        return lambda y: json.dumps({"label": int(y)})
    return lambda y: json.dumps({"scores": np.asarray(y).tolist()})


class FusedServingStep:
    """One-dispatch-per-bucket scoring over a built model.

    ``model_config`` / ``params`` are the :func:`models.build_model`
    pair (the same artifacts TpuModel serves); ``row_shape`` is the
    per-row wire shape (e.g. ``(32, 32, 3)``) and ``in_dtype`` its wire
    dtype (uint8 ships bytes; the cast to compute dtype happens inside
    the fused program). ``output='argmax'`` folds the reply reduction
    into the device program (4 readback bytes/row); ``'scores'`` returns
    the score rows. ``decode``/``encode`` override the payload codecs.
    """

    def __init__(self, model_config: Optional[dict], params, *,
                 policy: Optional[BucketPolicy] = None,
                 row_shape=(), in_dtype=np.uint8, output: str = "argmax",
                 decode: Optional[Callable] = None,
                 encode: Optional[Callable] = None,
                 tag: str = "serving.step", _body: Optional[Callable] = None):
        import jax
        import jax.numpy as jnp
        if output not in ("argmax", "scores"):
            raise ValueError(f"output must be argmax|scores, got {output!r}")
        self.model_config = None if model_config is None \
            else dict(model_config)
        self.policy = policy or BucketPolicy()
        self.row_shape = tuple(int(d) for d in row_shape)
        self.in_dtype = np.dtype(in_dtype)
        self.output = output
        self.decode = decode or _default_decode(self.row_shape,
                                                self.in_dtype)
        self.encode = encode or _default_encode(output)
        self.params = params
        self._params_dev = jax.device_put(params)
        if _body is None:
            from ...models.modules import build_model
            module = build_model(self.model_config)
            _body = module.apply

        def fused(p, x):
            y = _body(p, x)
            if output == "argmax" and y.ndim > 1:
                return jnp.argmax(y, axis=-1).astype(jnp.int32)
            return y

        # aot=True: the executable cache stays authoritative even with
        # profiling off — that cache IS the warm-start story
        self._pf = telemetry.profiler.wrap(jax.jit(fused), tag, aot=True)

    @classmethod
    def from_pipeline(cls, pipeline, *, input_col: str = "features",
                      score_col: Optional[str] = None, row_shape=(),
                      in_dtype=np.float32,
                      policy: Optional[BucketPolicy] = None,
                      output: str = "argmax",
                      decode: Optional[Callable] = None,
                      encode: Optional[Callable] = None,
                      tag: str = "serving.pipeline") -> "FusedServingStep":
        """A whole PIPELINE as the fused step body: every stage of
        ``pipeline`` (a ``PipelineModel``) must expose a capture
        (core/capture.py — uncapturable stages raise), and the composed
        featurize→predict program compiles as ONE executable per bucket,
        bundle-serializable like any model step — a serving worker loads
        the pipeline composite warm. ``input_col`` is the wire column the
        decoded payload feeds; ``score_col`` the pipeline output column
        served (default: ``scores``/``probability``/``prediction``,
        first match, else the last produced column)."""
        from ...core import capture as capturelib
        stages = tuple(pipeline.getOrDefault("stages"))
        seg = capturelib.whole_pipeline_capture(stages, [input_col])
        if list(seg.in_names) != [input_col]:
            raise ValueError(
                f"pipeline serving composites take ONE wire column "
                f"({input_col!r}); this pipeline also reads "
                f"{[n for n in seg.in_names if n != input_col]}")
        if score_col is None:
            score_col = next((c for c in ("scores", "probability",
                                          "prediction")
                              if c in seg.out_names), seg.out_names[-1])
        body, params = capturelib.segment_body(seg, score_col)
        step = cls(None, params, policy=policy, row_shape=row_shape,
                   in_dtype=in_dtype, output=output, decode=decode,
                   encode=encode, tag=tag,
                   _body=lambda p, x: body(p, (x,)))
        step.pipeline = pipeline
        step.bundle_kind = "pipeline"
        step.input_col = input_col
        step.score_col = score_col
        return step

    # ---- warmup / bundle surface ----
    def devices(self) -> list:
        """The devices every bucket executable runs on (where the params
        live), in id order — what a bundle records and reloads onto."""
        import jax
        leaf = jax.tree_util.tree_leaves(self._params_dev)[0]
        return sorted(leaf.devices(), key=lambda d: d.id)

    def bucket_spec(self, bucket: int):
        import jax
        return jax.ShapeDtypeStruct((bucket,) + self.row_shape,
                                    self.in_dtype)

    def compile_bucket(self, bucket: int):
        """AOT-compile one bucket (no-op when cached); returns the
        compiled executable for bundle serialization."""
        spec = self.bucket_spec(bucket)
        fresh = not self._pf.is_cached(self._params_dev, spec)
        compiled = self._pf.aot_compile(self._params_dev, spec)
        if fresh:
            _m_aot_compiles.inc()
        return compiled

    def compile_buckets(self) -> int:
        """Warm every bucket of the policy ahead of live traffic (the
        startup path when no bundle exists; also the bundle build).
        Returns the number of executables actually compiled."""
        n = 0
        for b in self.policy.buckets:
            if not self._pf.is_cached(self._params_dev,
                                      self.bucket_spec(b)):
                self.compile_bucket(b)
                n += 1
        return n

    def preload_bucket(self, bucket: int, compiled) -> None:
        """Seed one bucket with a deserialized bundle executable — the
        warm path a restarted worker takes instead of compiling."""
        self._pf.preload((self._params_dev, self.bucket_spec(bucket)),
                         compiled)

    def warm_buckets(self) -> list:
        """Buckets whose executable is already cached (warm telemetry for
        /healthz and tests)."""
        return [b for b in self.policy.buckets
                if self._pf.is_cached(self._params_dev,
                                      self.bucket_spec(b))]

    def compiles(self) -> int:
        """Total XLA compiles this step has performed (warm-restart tests
        assert this stays flat across a bundle-loaded restart)."""
        return self._pf.compiles

    # ---- the hot path ----
    #: the engine may pass per-request phase ledgers (ledgers=) — step
    #: doubles without this attribute get the bare two-arg call
    accepts_ledgers = True

    def score_rows(self, rows: np.ndarray, bucket: int,
                   ledgers=None) -> np.ndarray:
        """(n, *row_shape) wire rows -> (n, ...) outputs via ONE padded
        bucket dispatch. ``ledgers`` (one per row, from the serving
        engine) get pad / device / readback phase stamps — the
        ``block_until_ready`` between the device and readback stamps
        splits device execution from the D2H copy but adds no wall time:
        ``np.asarray`` would have blocked on the same dispatch anyway."""
        n = len(rows)
        xb = np.zeros((bucket,) + self.row_shape, self.in_dtype)
        xb[:n] = rows
        if ledgers:
            t = time.perf_counter_ns()
            for led in ledgers:
                led.mark("pad", t)
        if self._pf.is_cached(self._params_dev, xb):
            _m_cache_hits.inc()
        else:
            _m_cache_misses.inc()
            log.warning("serving bucket %d cold-compiled on live traffic "
                        "(warmup/bundle did not cover it)", bucket)
        y = self._pf(self._params_dev, xb)
        if ledgers:
            import jax
            jax.block_until_ready(y)
            t = time.perf_counter_ns()
            for led in ledgers:
                led.mark("device", t)
        out = np.asarray(y)[:n]
        if ledgers:
            t = time.perf_counter_ns()
            for led in ledgers:
                led.mark("readback", t)
        return out

    def __call__(self, values: list, bucket: Optional[int] = None) -> list:
        """Payload strings -> reply strings (decode -> pad -> one
        dispatch -> unpad -> encode)."""
        rows = np.stack([self.decode(v) for v in values])
        out = self.score_rows(rows,
                              bucket or self.policy.bucket_for(len(values)))
        return [self.encode(y) for y in out]
