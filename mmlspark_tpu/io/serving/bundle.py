"""Versioned model + executable bundles: the AOT warm-start artifact.

A serving worker's cold start pays one XLA compile per shape bucket —
seconds each, paid by whichever requests arrive first. The supervisor's
"self-healing" restart therefore used to be lossy at p99: the fleet
recovered, but the restarted worker's first clients ate the compiles.
The bundle closes that hole: at deploy (or first warmup) time the
per-bucket compiled executables are serialized (``jax.experimental.
serialize_executable`` — the ``jax.export``-shaped AOT artifact) next to
the model config + params into ONE integrity-checked directory, and a
restarting worker deserializes them instead of compiling. First
post-restart request: warm.

Commit protocol — PR 10's sharded-checkpoint manifest format, verbatim
(:mod:`mmlspark_tpu.resilience.ckpt`):

* every component (``bundle_meta.json``, ``bundle_model.msgpack``, one
  ``bundle_exec_b<rows>.bin`` per bucket) is committed as a SHARD:
  tmp-write + fsync + atomic rename (fault site ``ckpt.shard``), no
  individual manifest entry;
* the head (``serving_bundle.json``) + ``manifest.json`` commit LAST,
  recording every shard's size + sha256 — a crash mid-publish leaves a
  directory the loader treats as absent, never a half-trusted bundle.

Load-time integrity is graded, not all-or-nothing:

* torn/missing **model or meta** shard -> the bundle is unusable;
  :func:`load_bundle` raises (there is nothing to serve);
* torn/missing **executable** shard (or an injected
  ``serving.bundle_load`` fault, or a jax-version / device-placement
  mismatch) -> that bucket falls back to a cold compile, counted on
  ``mmlspark_serving_bundle_exec_failures_total`` — degraded warmth,
  never a wrong answer;
* a bundle built for another **backend** (a ``tpu`` bundle where this
  process runs ``cpu``) -> :func:`load_bundle` raises: serving a chip's
  model from whatever backend came up instead is not degraded warmth.
"""

from __future__ import annotations

import json
import os
import pickle
from typing import Optional

import numpy as np

from ... import telemetry
from ...core.utils import get_logger
from ...resilience import ckpt, faults
from .batcher import BucketPolicy
from .step import FusedServingStep

log = get_logger("io.serving")

#: the bundle head's canonical name (the manifest's multi-shard record)
BUNDLE_HEAD = "serving_bundle.json"
SCHEMA = "mmlspark-serving-bundle/v1"
#: the pipeline composite's model-component shard (kind == "pipeline")
_PIPELINE_SHARD = "bundle_pipeline.bin"

_m_bundle_loads = telemetry.registry.counter(
    "mmlspark_serving_bundle_loads_total",
    "bundle load attempts by outcome: warm (every bucket's executable "
    "deserialized), partial (some buckets fell back to cold compile), "
    "cold (no executable usable), absent (no committed bundle found)",
    labels=("result",))
_m_exec_failures = telemetry.registry.counter(
    "mmlspark_serving_bundle_exec_failures_total",
    "bucket executables that could not be loaded from the bundle (torn "
    "shard, deserialize error, backend mismatch, injected fault) — each "
    "one is a cold compile at first use of that bucket")
_m_execs_loaded = telemetry.registry.counter(
    "mmlspark_serving_bundle_execs_loaded_total",
    "bucket executables deserialized warm from a bundle")


def _exec_shard(bucket: int) -> str:
    return f"bundle_exec_b{bucket}.bin"


def save_bundle(directory: str, step: FusedServingStep,
                extra_meta: Optional[dict] = None) -> str:
    """Compile every bucket of ``step`` (no-op for already-warm ones)
    and commit the versioned model+executable bundle into ``directory``.
    Returns the head path. Safe to re-run: a newer save atomically
    replaces the head + manifest."""
    import jax
    from flax import serialization
    from jax.experimental import serialize_executable
    os.makedirs(directory, exist_ok=True)
    step.compile_buckets()
    kind = getattr(step, "bundle_kind", "model")
    meta = {
        "schema": SCHEMA,
        "version": 1,
        "kind": kind,
        "backend": jax.default_backend(),
        "jax": jax.__version__,
        "exec_devices": [d.id for d in step.devices()],
        "model_config": step.model_config,
        "row_shape": list(step.row_shape),
        "in_dtype": step.in_dtype.name,
        "output": step.output,
        "min_bucket": step.policy.min_bucket,
        "max_batch": step.policy.max_batch,
        "buckets": list(step.policy.buckets),
    }
    if kind == "pipeline":
        # a pipeline composite's "model" component is the serialized
        # PipelineModel itself (stages + fitted params); the fused body
        # and its capture params are rebuilt from it at load time
        meta["input_col"] = step.input_col
        meta["score_col"] = step.score_col
        model_shard = (_PIPELINE_SHARD, pickle.dumps(step.pipeline))
    else:
        model_shard = ("bundle_model.msgpack",
                       serialization.msgpack_serialize(
                           jax.tree_util.tree_map(np.asarray,
                                                  step.params)))
    if extra_meta:
        meta.update(extra_meta)
    shards = [("bundle_meta.json",
               json.dumps(meta, sort_keys=True).encode("utf-8")),
              model_shard]
    for b in step.policy.buckets:
        compiled = step.compile_bucket(b)
        shards.append((_exec_shard(b),
                       pickle.dumps(serialize_executable.serialize(
                           compiled))))
    names = []
    with telemetry.trace.span("serving/bundle_save",
                              buckets=len(step.policy.buckets)):
        for name, data in shards:
            ckpt.write_shard(os.path.join(directory, name), data)
            names.append(name)
        head = os.path.join(directory, BUNDLE_HEAD)
        ckpt.commit_sharded(head, names)
    log.info("serving bundle committed: %s (%d buckets, backend=%s)",
             head, len(step.policy.buckets), meta["backend"])
    return head


def _read_shard(directory: str, name: str) -> Optional[bytes]:
    """One shard's bytes, content-verified against the manifest (via the
    head's shards map); None when torn/missing."""
    try:
        with open(os.path.join(directory, name), "rb") as f:
            data = f.read()
    except OSError:
        return None
    if not ckpt.verify_bytes(directory, name, data):
        return None
    return data


def load_bundle(directory: str, policy: Optional[BucketPolicy] = None,
                **step_kwargs) -> FusedServingStep:
    """Rebuild a :class:`FusedServingStep` from a committed bundle,
    seeding every readable bucket executable into its AOT cache.

    Raises ``FileNotFoundError`` when no committed bundle exists and
    :class:`~...resilience.ckpt.CorruptCheckpoint` when the model/meta
    shards are torn — both counted. Torn *executable* shards degrade to
    cold compiles for their buckets (counted), never an error: a worker
    with intact weights must come up even if warmth was lost. A bundle
    built for a different backend raises ``RuntimeError``.
    """
    import jax
    from flax import serialization
    from jax.experimental import serialize_executable
    # graded integrity: verify the HEAD itself (its content hash via the
    # manifest), then each shard individually — ckpt.verify()'s whole-
    # candidate semantics would let one torn executable take down a
    # bundle whose weights are perfectly intact
    try:
        with open(os.path.join(directory, BUNDLE_HEAD), "rb") as f:
            head_blob = f.read()
    except OSError:
        head_blob = None
    files = ckpt.load_manifest(directory) or {}
    if (head_blob is None or BUNDLE_HEAD not in files
            or not ckpt.verify_bytes(directory, BUNDLE_HEAD, head_blob)):
        _m_bundle_loads.labels(result="absent").inc()
        raise FileNotFoundError(
            f"no committed serving bundle in {directory} (head "
            f"{BUNDLE_HEAD} missing or failed manifest verification)")
    meta_blob = _read_shard(directory, "bundle_meta.json")
    if meta_blob is None:
        _m_bundle_loads.labels(result="cold").inc()
        ckpt.note_corrupt(BUNDLE_HEAD, "model/meta shard torn")
        raise ckpt.CorruptCheckpoint(
            f"serving bundle in {directory} has a torn meta shard")
    meta = json.loads(meta_blob.decode("utf-8"))
    if meta.get("backend") != jax.default_backend():
        raise RuntimeError(
            f"serving bundle in {directory} was built for backend "
            f"{meta.get('backend')!r}; this process runs "
            f"{jax.default_backend()!r}")
    kind = meta.get("kind", "model")
    model_blob = _read_shard(
        directory,
        _PIPELINE_SHARD if kind == "pipeline" else "bundle_model.msgpack")
    if model_blob is None:
        _m_bundle_loads.labels(result="cold").inc()
        ckpt.note_corrupt(BUNDLE_HEAD, "model/meta shard torn")
        raise ckpt.CorruptCheckpoint(
            f"serving bundle in {directory} has a torn model/meta shard")
    if policy is None:
        policy = BucketPolicy(max_batch=meta["max_batch"],
                              min_bucket=meta["min_bucket"])
    if kind == "pipeline":
        pipeline = pickle.loads(model_blob)
        step = FusedServingStep.from_pipeline(
            pipeline, input_col=meta["input_col"],
            score_col=meta["score_col"], policy=policy,
            row_shape=tuple(meta["row_shape"]),
            in_dtype=np.dtype(meta["in_dtype"]),
            output=meta["output"], **step_kwargs)
    else:
        params = serialization.msgpack_restore(model_blob)
        step = FusedServingStep(meta["model_config"], params,
                                policy=policy,
                                row_shape=tuple(meta["row_shape"]),
                                in_dtype=np.dtype(meta["in_dtype"]),
                                output=meta["output"], **step_kwargs)
    # a serialized executable reloads onto the devices it was compiled
    # for (deserialize_and_load would otherwise spread a one-device
    # program over every device of the backend)
    devices = step.devices()
    device_ids = [d.id for d in devices]
    compatible = (meta.get("jax") == jax.__version__
                  and meta.get("exec_devices") == device_ids)
    loaded = 0
    with telemetry.trace.span("serving/bundle_load",
                              buckets=len(policy.buckets)):
        for b in policy.buckets:
            if b not in set(meta.get("buckets", ())):
                _m_exec_failures.inc()
                continue
            try:
                # the chaos site: an injected fault here means "this
                # executable could not be loaded" — the recovery path is
                # a cold compile of that bucket, nothing worse
                faults.inject("serving.bundle_load")
                if not compatible:
                    raise RuntimeError(
                        f"bundle built with jax={meta.get('jax')} for "
                        f"devices {meta.get('exec_devices')}; this "
                        f"process runs jax={jax.__version__} on devices "
                        f"{device_ids}")
                blob = _read_shard(directory, _exec_shard(b))
                if blob is None:
                    raise RuntimeError(f"executable shard for bucket {b} "
                                       f"torn or missing")
                ser, in_tree, out_tree = pickle.loads(blob)
                compiled = serialize_executable.deserialize_and_load(
                    ser, in_tree, out_tree, execution_devices=devices)
                step.preload_bucket(b, compiled)
                loaded += 1
                _m_execs_loaded.inc()
            except Exception as e:
                _m_exec_failures.inc()
                log.warning("bundle executable for bucket %d unusable "
                            "(cold compile at first use): %s", b, e)
    result = ("warm" if loaded == len(policy.buckets)
              else "partial" if loaded else "cold")
    _m_bundle_loads.labels(result=result).inc()
    log.info("serving bundle loaded %s from %s: %d/%d bucket executables "
             "warm", result, directory, loaded, len(policy.buckets))
    return step
