"""Device mesh + sharding helpers: the framework's distributed substrate.

Replaces the reference's three communication mechanisms (SURVEY.md §2.7) with
one: XLA collectives over an explicit ``jax.sharding.Mesh``.
  * MPI ring over ssh (cntk-train/.../CommandBuilders.scala:149-267)  → data-
    parallel gradient all-reduce inserted by XLA when params are replicated
    and batches are sharded over the ``data`` axis;
  * LightGBM socket collective (TrainUtils.scala:141-142)             → psum
    of histograms over the mesh (models/gbdt);
  * ssh/scp data movement                                             → one
    ``jax.device_put`` of columnar batches with a NamedSharding.

Axis conventions (used across the framework):
  ``data``  — batch dimension (DP);
  ``model`` — tensor-parallel dimension (TP, e.g. wide dense kernels);
additional axes (pipeline/sequence/expert) compose the same way.
"""

from __future__ import annotations

import threading
import time
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import telemetry
from ..resilience import faults

# host->HBM placement telemetry: every sharded batch/replicated-tree put
# made through this module (trainer feeds, GBDT bin uploads, serving
# batches). No-ops unless MMLSPARK_TPU_TELEMETRY=1.
_m_put_bytes = telemetry.registry.counter(
    "mmlspark_mesh_put_bytes",
    "host bytes handed to device placement (shard_batch/put_global_batch)")
_m_put_seconds = telemetry.registry.histogram(
    "mmlspark_mesh_put_seconds",
    "wall time of one device placement call (dispatch side — transfers "
    "may complete asynchronously)")


def _observe_put(t0: float, tree):
    _m_put_seconds.observe(time.perf_counter() - t0)
    _m_put_bytes.inc(sum(getattr(a, "nbytes", 0)
                         for a in jax.tree_util.tree_leaves(tree)))

# Collectives issued concurrently from multiple host threads can interleave
# across the same devices and deadlock (each device waits on a different
# collective). Any fit that runs a multi-device collective program while
# other fits may run on other threads (e.g. TuneHyperparameters' pool)
# must hold this lock; single-device fits need not. Reentrant so a stage
# can span feature-planning collectives AND the engine fit (which acquires
# it again) in one critical section — two separate acquisitions would let
# another thread's collectives interleave between them with a different
# order on each process.
collective_fit_lock = threading.RLock()

# ---- local-fit mode -------------------------------------------------------
# Embarrassingly-parallel search on a fleet (TuneHyperparameters) assigns
# whole trials to processes; each process then fits ITS trials with no
# cross-process collectives at all. Inside this mode every fit behaves as a
# single-process single-device program: effective_process_count() is 1 and
# create_mesh()/make_mesh() default to one local device. A module-level
# counter (not a contextvar) because the tuner's worker THREADS must see
# the flag set by the coordinating thread.
_local_fit_count = 0
_local_fit_guard = threading.Lock()


class local_fit_mode:
    """Context manager: fits inside run process-locally (no collectives)."""

    def __enter__(self):
        global _local_fit_count
        with _local_fit_guard:
            _local_fit_count += 1
        return self

    def __exit__(self, *exc):
        global _local_fit_count
        with _local_fit_guard:
            _local_fit_count -= 1
        return False


def in_local_fit() -> bool:
    return _local_fit_count > 0


def effective_process_count() -> int:
    """jax.process_count(), except 1 inside local-fit mode — the switch
    that steers every fleet-collective code path (pooled GBDT statistics,
    multi-host batch assembly, trainer rendezvous) to its single-process
    form."""
    return 1 if in_local_fit() else jax.process_count()


def create_mesh(data: Optional[int] = None, model: int = 1,
                devices: Optional[Sequence] = None,
                axis_names: tuple[str, ...] = ("data", "model")) -> Mesh:
    """Build a 2-D (data, model) mesh over the available devices.

    With a single chip this degrades to a 1x1 mesh and every sharding becomes
    a no-op — the same program runs unchanged from 1 chip to a pod, which is
    the core TPU-first contract (vs. the reference's separate single-node and
    MPI code paths, CommandBuilders.scala:90-100 vs :149-267).
    """
    if devices is None:
        devices = ([jax.local_devices()[0]] if in_local_fit()
                   else jax.devices())
    devices = list(devices)
    n = len(devices)
    if data is None:
        if model < 1 or n % model != 0:
            raise ValueError(
                f"model axis ({model}) must divide the device count ({n}) "
                f"— a silently-truncated mesh would train/serve on a "
                f"subset of the chips")
        data = n // model
    if data < 1 or model < 1:
        raise ValueError(f"mesh {data}x{model} is empty: {n} devices cannot "
                         f"host a model axis of {model}")
    if data * model > n:
        raise ValueError(f"mesh {data}x{model} needs {data*model} devices, have {n}")
    dev_array = np.asarray(devices[:data * model]).reshape(data, model)
    return Mesh(dev_array, axis_names)


def make_mesh(axes: dict[str, int],
              devices: Optional[Sequence] = None) -> Mesh:
    """Build an N-D mesh from {axis_name: size}. Axis order = dict order
    (outermost first — put ``data`` outermost so DP collectives cross the
    slowest links and tp/sp/ep ride contiguous ICI neighbors)."""
    if devices is None:
        devices = ([jax.local_devices()[0]] if in_local_fit()
                   else jax.devices())
    devices = list(devices)
    sizes = list(axes.values())
    if any(s < 1 for s in sizes):
        raise ValueError(f"mesh axes must be >= 1, got {axes}")
    total = int(np.prod(sizes))
    if total > len(devices):
        raise ValueError(f"mesh {axes} needs {total} devices, "
                         f"have {len(devices)}")
    dev_array = np.asarray(devices[:total]).reshape(sizes)
    return Mesh(dev_array, tuple(axes.keys()))


def stable_host_id() -> str:
    """This process's STABLE elastic host identity: its LAUNCH rank
    (``MMLTPU_PROCESS_ID``) when the launcher's env contract set one,
    else the current ``jax.process_index()``. Heartbeat files, death/
    evict verdicts, and rendezvous ranks all key on this id — and it
    must survive re-ranking across rendezvous generations (a survivor
    that becomes rank 0 of a shrunken incarnation keeps the host id it
    launched with)."""
    import os
    v = os.environ.get("MMLTPU_PROCESS_ID", "")
    if v.isdigit():
        return f"host{int(v)}"
    return f"host{jax.process_index()}"


def host_device_groups(n_groups: int = 0) -> list[tuple[str, list]]:
    """Partition the visible devices into named "host" groups — the failure
    domains elastic training (resilience/elastic.py) supervises and
    re-meshes over.

    Default (``n_groups=0``): one group per JAX process (device.process_index
    — the real host boundary on a TPU fleet; a preempted VM takes exactly
    its process's chips with it). Single-process with ``n_groups>1``: split
    the local devices into ``n_groups`` contiguous chunks — simulated hosts
    for chaos testing and laptop rehearsal of the multi-host recovery path
    (the conftest 8-device CPU mesh plays a 4-host fleet). Group ids are
    stable across calls ("host0", "host1", ... in device order), which is
    what heartbeat files and death verdicts key on.
    """
    devices = list(jax.devices())
    if n_groups and n_groups > 1:
        if n_groups > len(devices):
            raise ValueError(f"cannot split {len(devices)} devices into "
                             f"{n_groups} host groups")
        per = len(devices) // n_groups
        groups = [(f"host{g}", devices[g * per:(g + 1) * per])
                  for g in range(n_groups)]
        # a non-divisible split must not silently strand chips: the tail
        # devices ride with the last host
        groups[-1][1].extend(devices[n_groups * per:])
        return groups
    by_proc: dict[int, list] = {}
    for d in devices:
        by_proc.setdefault(getattr(d, "process_index", 0), []).append(d)
    return [(f"host{p}", by_proc[p]) for p in sorted(by_proc)]


def batch_sharding(mesh: Mesh, batch_axis: str = "data") -> NamedSharding:
    """Shard dim 0 (batch) over the data axis, replicate the rest."""
    return NamedSharding(mesh, P(batch_axis))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_batch(arrays, mesh: Mesh, batch_axis: str = "data"):
    """device_put a pytree of host arrays with dim-0 sharded over `data` —
    the one host->HBM hop that replaces the reference's per-element JNI
    copies (CNTKModel.scala:67-74) and scp legs (CommandBuilders.scala:200-228).
    One path for every mesh size: on a single v5e chip a committed
    one-device NamedSharding costs the same per dispatch and per put as an
    uncommitted array (PERF.md, PR 21)."""
    t0 = time.perf_counter()
    sh = batch_sharding(mesh, batch_axis)
    out = jax.tree_util.tree_map(lambda a: jax.device_put(a, sh), arrays)
    if telemetry.enabled():
        _observe_put(t0, arrays)
    return out


def _pad_rows_to_multiple(arr: np.ndarray, mult: int) -> tuple[np.ndarray, int]:
    n = arr.shape[0]
    rem = (-n) % max(1, mult)
    if rem == 0:
        return arr, n
    pad = np.repeat(arr[-1:], rem, axis=0)
    return np.concatenate([arr, pad], axis=0), n


def pad_batch_to_devices(arr: np.ndarray, mesh: Mesh,
                         batch_axis: str = "data") -> tuple[np.ndarray, int]:
    """Pad dim 0 to a multiple of the data-axis size (XLA needs equal shards).
    Returns (padded, original_n)."""
    return _pad_rows_to_multiple(arr, mesh.shape[batch_axis])


def pad_batch_to_local_devices(arr: np.ndarray, mesh: Mesh,
                               batch_axis: str = "data") -> tuple[np.ndarray, int]:
    """Multi-host variant of pad_batch_to_devices: pad THIS process's local
    rows to a multiple of its share of the batch axis, so the per-process
    shards concatenate into an evenly divisible global batch. NOTE: in SPMD
    every process must end up with the SAME padded length — callers feed
    equal-length slices (models.trainer synchronizes the per-step row count)."""
    return _pad_rows_to_multiple(arr, mesh.shape[batch_axis]
                                 // effective_process_count())


def local_rows(global_array, n: Optional[int] = None) -> np.ndarray:
    """THIS process's contiguous rows of a dim-0-sharded global array
    (inverse of put_global_batch), optionally sliced to the first n real
    (unpadded) rows. Arrays replicated over an inner (model/seq) axis
    expose one addressable shard PER replica — dedupe by row range so a
    tp-sharded inference output doesn't repeat its rows."""
    shards = {}
    for s in global_array.addressable_shards:
        shards.setdefault(s.index[0].start or 0, s)
    out = np.concatenate([np.asarray(shards[k].data)
                          for k in sorted(shards)], axis=0)
    return out[:n] if n is not None else out


def put_global_batch(arr, mesh: Mesh, batch_axis: str = "data"):
    """Place a batch dim-0-sharded over `batch_axis`. Single-process: one
    device_put. Multi-process: `arr` is THIS process's local rows; the global
    array is assembled from every process's shard (the reference has no
    analog — its data stays in Spark partitions and is shipped per-worker
    over scp/JNI, CommandBuilders.scala:200-228)."""
    faults.inject("dataplane.put")
    t0 = time.perf_counter()
    if effective_process_count() == 1:
        out = jax.device_put(arr, batch_sharding(mesh, batch_axis))
    else:
        out = jax.make_array_from_process_local_data(
            batch_sharding(mesh, batch_axis), np.asarray(arr))
    if telemetry.enabled():
        _observe_put(t0, arr)
    return out


def put_replicated(tree, mesh: Mesh):
    """Replicate a pytree over the whole (possibly multi-host) mesh. Every
    process must hold identical values (same-seed init guarantees this)."""
    if effective_process_count() == 1:
        return jax.device_put(tree, replicated(mesh))
    sh = replicated(mesh)
    return jax.tree_util.tree_map(
        lambda a: jax.make_array_from_process_local_data(sh, np.asarray(a)),
        tree)


#: tensor-parallel placement rules shared by training (TpuLearner) and
#: inference (TpuModel): wide Dense kernels shard columns over ``model``,
#: every other kernel replicates. First match wins (shard_params_tp).
TP_PARAM_RULES = (("Dense", P(None, "model")), ("kernel", P()))


def require_inner_block_local(axes: dict):
    """Multi-host locality rule shared by fit()/fitStream()/transform():
    the inner parallel block (product of the non-data axes) must divide
    the LOCAL device count. make_mesh puts ``data`` outermost, so inner
    axes span contiguous device ranges — this keeps every
    seq/expert/model/pipe collective on within-host ICI while only the dp
    all-reduce crosses hosts, and keeps checkpointing and model export
    reading process-locally-complete params."""
    inner = int(np.prod([max(1, v) for v in axes.values()]))
    if inner <= 1:
        return
    n_local = jax.local_device_count()
    if inner > n_local or n_local % inner != 0:
        desc = "*".join(f"{nm}={v}" for nm, v in axes.items() if v > 1)
        raise ValueError(
            f"the inner parallel block ({desc} = {inner}) must divide the "
            f"LOCAL device count ({n_local}) on a multi-host mesh: "
            f"seq/expert/model/pipe axes must ride ICI within a host "
            f"while dp crosses hosts")


def shard_params_tp(params, mesh: Mesh, rules: Sequence[tuple[str, P]] = (),
                    default: Optional[P] = None):
    """Apply tensor-parallel shardings to a param pytree by path substring.

    rules: [(path_substring, PartitionSpec)] — first match wins; unmatched
    leaves are replicated. This is the declarative knob the trainer uses to
    put wide dense kernels on the ``model`` axis.
    """
    flat = jax.tree_util.tree_flatten_with_path(params)
    leaves, treedef = flat
    out = []
    def _divisible(leaf, spec: P) -> bool:
        for dim, axis in enumerate(spec):
            if axis is None:
                continue
            axes = (axis,) if isinstance(axis, str) else tuple(axis)
            size = int(np.prod([mesh.shape[a] for a in axes]))
            if leaf.shape[dim] % size != 0:
                return False
        return True

    multiproc = effective_process_count() > 1
    for path, leaf in leaves:
        pstr = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        spec = default if default is not None else P()
        for sub, candidate in rules:
            if (sub in pstr and len(candidate) <= np.ndim(leaf)
                    and _divisible(leaf, candidate)):
                spec = candidate
                break
        sh = NamedSharding(mesh, spec)
        if multiproc:
            # process-spanning mesh: every process holds the identical full
            # value (same-seed init), so each addressable shard is a slice
            # of the local copy — device_put cannot target non-addressable
            # devices
            host = np.asarray(leaf)
            out.append(jax.make_array_from_callback(
                host.shape, sh, lambda idx, h=host: h[idx]))
        else:
            out.append(jax.device_put(leaf, sh))
    return jax.tree_util.tree_unflatten(treedef, out)
