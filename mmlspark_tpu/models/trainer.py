"""TpuLearner: distributed SGD over a device mesh, as an Estimator.

The CNTKLearner analog (reference: cntk-train/.../CNTKLearner.scala:84-175).
The reference's path — write CNTK text files, scp them + the working dir to
GPU VMs, emit BrainScript, `ssh mpirun cntk configFile=...`, scp the model
back (CommandBuilders.scala:149-267) — collapses to: declarative model config
(modules.build_model = BrainScript's role), columnar batches device_put onto
the mesh, and ONE jitted train step whose gradient all-reduce is inserted by
XLA because params are replicated while the batch is sharded over ``data``
(replacing the MPI ring at CommandBuilders.scala:241-243). Tensor parallelism
is the same program with a ``model`` axis in the mesh and kernel sharding
rules — no second code path.

Improvement over the reference (SURVEY.md §5: "no training checkpoint /
resume"): per-epoch checkpointing with automatic resume.
"""

from __future__ import annotations

import collections
import contextlib
import os
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import serialization

from ..core.dataframe import DataFrame
from ..core.env import on_tpu
from ..core.params import (BooleanParam, DictParam, FloatParam, IntParam,
                           ListParam, StringParam)
from ..core.pipeline import Estimator
from ..core.utils import get_logger, to_float32_matrix
from ..parallel import mesh as meshlib
from ..parallel import sequence
from .. import telemetry
from ..resilience import faults
from ..resilience.policy import RetryPolicy
from .modules import TOKEN_MODELS, build_model, has_experts
from .tpu_model import TpuModel, _prep_input

log = get_logger("trainer")

# runtime telemetry (off-by-default no-ops; MMLSPARK_TPU_TELEMETRY=1)
_m_step_time = telemetry.registry.histogram(
    "mmlspark_trainer_step_seconds",
    "wall time per optimizer dispatch: the host time of one step's "
    "dispatch call on the feed and stream paths (the fit/dispatch span's "
    "clock reads), a device-synced stepsPerDispatch window on the scan "
    "path")
_m_rows_per_sec = telemetry.registry.gauge(
    "mmlspark_trainer_rows_per_sec",
    "training throughput over the last epoch (rows == imgs for image fits)")
_m_recompiles = telemetry.registry.counter(
    "mmlspark_trainer_recompiles",
    "train-step dispatches whose abstract (shape, dtype) signature was "
    "not seen before in this process — each is an XLA compile")
_m_transfer_bytes = telemetry.registry.counter(
    "mmlspark_trainer_transfer_bytes",
    "host->device bytes shipped by the trainer (epoch uploads + per-step "
    "batch feeds)")

#: abstract-shape signatures already dispatched (recompile detection)
_seen_step_sigs: set = set()

#: retry-once-on-transient around each dispatched optimizer step
#: (preemption blips, injected ``trainer.step`` faults). The injection
#: site fires BEFORE the dispatch, so a retried attempt re-enters with
#: the donated batch buffers still intact; a genuinely fatal error (bad
#: model code) classifies non-transient and raises immediately.
_STEP_RETRY = RetryPolicy(name="trainer.step", max_attempts=2,
                          base_delay=0.05, max_delay=0.25)


class _StepsInFlight:
    """The losses of dispatched steps that are not known to be finished:
    what `fit/dispatch` reports as ``in_flight``. Polled with
    ``is_ready()``, never waited on; steps finish in dispatch order, so
    the finished ones are a prefix. Holds one scalar a step in flight and
    exists only in a fit that started with telemetry on."""

    def __init__(self):
        #: (loss, step, the step program's per-step counts or None): the
        #: counts of the models that return any (`step_stat_names`) are read
        #: once their loss is ready, so never waited for either
        self.losses = collections.deque()

    def add(self, step: int, loss, stats=None):
        self.losses.append((loss, step, stats))

    def count(self, finished: bool = False) -> int:
        while self.losses and (finished or self.losses[0][0].is_ready()):
            _, step, stats = self.losses.popleft()
            if stats is not None:
                _observe_step_stats(step, stats)
        return len(self.losses)

    def clear(self):
        """The fit has read its last loss: every step is in, whatever a
        poll would say."""
        self.count(finished=True)


def _observe_step_stats(step: int, stats: dict):
    """A finished step's counts and loss terms (outputs of the step program,
    on the device until here): the expert layers' counts into the registry
    and all of them, numbered by `step`, onto the ring, whole numbers as ints
    and the rest as floats. The event `fit/step_stats` spans the read, the
    one round trip to the device the loop makes a step: time of the loop's
    thread that neither `fit/feed_wait` nor `fit/dispatch` covers."""
    from .moe import observe_step_stats
    start = time.perf_counter_ns()
    values = {k: (int(v) if np.issubdtype(v.dtype, np.integer) else float(v))
              for k, v in jax.device_get(stats).items()}
    telemetry.trace.complete("fit/step_stats", start, step=step, **values)
    observe_step_stats(values)


def _dispatch_step(train_step, params, opt_state, scale_state, xb, yb, wb, *,
                   step, in_flight, fused=None, elastic_ctx=None,
                   step_stats=False):
    """Enqueue one optimizer step of the stream or the feed loop through
    `_STEP_RETRY`, under the span `fit/dispatch`: host time only (JAX
    returns before the device finishes; `setProfile(True)` gives a
    device-timed step), including any time the runtime holds the call back.
    The span's two clock reads also feed `mmlspark_trainer_step_seconds`.

    ``fused`` is the placed capture params of a fit-side fused step (``xb``
    is then the placed raw column tuple and ``yb`` None); ``in_flight`` the
    fit's `_StepsInFlight`, None when the fit started with telemetry off.
    ``step_stats``: the step program was built to return the model's
    per-step counts after the loss (`_make_train_step`); they ride with the
    loss in ``in_flight`` and are read when it is ready.
    Returns ``(params, opt_state, scale_state, loss)``."""
    state = ((params, opt_state) if scale_state is None
             else (params, opt_state, scale_state))
    batch = (xb, yb, wb) if fused is None else (fused, xb, wb)

    def dispatch(_attempt):
        if elastic_ctx is not None:
            # host-loss / grow check + the elastic.step fault site; both
            # raise non-transient, skip the retry and unwind to the
            # coordinator's re-mesh
            elastic_ctx.check_step()
        faults.inject("trainer.step")
        return train_step(*state, *batch)

    attrs = {} if in_flight is None else {"in_flight": in_flight.count()}
    with telemetry.trace.span("fit/dispatch", step=step, **attrs) as sp:
        out = _STEP_RETRY.run(dispatch)
    _m_step_time.observe(sp.seconds)
    stats = None
    if step_stats:
        *out, stats = out
    if in_flight is not None:
        in_flight.add(step, out[-1], stats)
    if fused is not None:
        from ..core import capture as capturelib
        capturelib._m_fit_fused.inc()
    if scale_state is None:
        params, opt_state, loss = out
        return params, opt_state, None, loss
    return out


def _note_step_signature(tag: str, *arrays):
    """Count a recompile when this (tag, shapes, dtypes) signature is new —
    the same key jit uses for its compilation cache, observed host-side."""
    sig = (tag,) + tuple((np.shape(a), str(getattr(a, "dtype", type(a))))
                         for a in arrays)
    if sig not in _seen_step_sigs:
        _seen_step_sigs.add(sig)
        _m_recompiles.inc()


def make_optimizer(name: str, lr: float, momentum: float = 0.9,
                   weight_decay: float = 0.0):
    if name == "sgd":
        tx = optax.sgd(lr)
    elif name == "momentum":
        tx = optax.sgd(lr, momentum=momentum)
    elif name == "adam":
        tx = optax.adam(lr)
    elif name == "adamw":
        tx = optax.adamw(lr, weight_decay=weight_decay)
    else:
        raise ValueError(f"unknown optimizer {name!r}")
    if weight_decay and name != "adamw":
        tx = optax.chain(optax.add_decayed_weights(weight_decay), tx)
    return tx


def make_loss(name: str, per_example: bool = False):
    """Loss on (preds, labels); per_example=True returns the (n,) vector so
    callers can weight out padding rows.

    ``next_token`` is the language-model objective of the families with a
    vocabulary head (`has_lm_head`): `_make_loss_compute` asks the module
    itself for the rows' losses (``module_kwargs``), which it computes from
    the row's own ids, every position against the next id, chunked over
    positions. The labels handed over with a batch are not read."""
    if name == "next_token":
        def vec(row_losses, labels):
            if row_losses.ndim != 1:
                raise ValueError(
                    "loss 'next_token' takes the model's own row losses; this "
                    f"step path handed it predictions of shape "
                    f"{row_losses.shape}")
            return row_losses
        vec.module_kwargs = {"row_losses": True}
    elif name == "cross_entropy":
        def vec(logits, labels):
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, labels.astype(jnp.int32))
    elif name == "mse":
        def vec(preds, labels):
            preds = preds.squeeze(-1) if preds.ndim > labels.ndim else preds
            return (preds - labels.astype(preds.dtype)) ** 2
    else:
        raise ValueError(f"unknown loss {name!r}")
    if per_example:
        return vec
    return lambda p, l: vec(p, l).mean()


def _stream_batch(b, cfg: dict, loss_name: str):
    """Normalize one (features, labels) generator item to device-ready
    numpy: token models take int32 ids, labels follow the loss dtype
    (``next_token`` reads none: they ride along as float32, one a row).
    uint8 image batches stay uint8 — the device cast is free and shipping
    bytes is 4x less host->HBM traffic, the same wire contract fit() and
    TpuModel._prep_input keep."""
    x, y = b
    x = np.asarray(x)
    if cfg.get("type") in TOKEN_MODELS:
        x = x.astype(np.int32)
    elif x.dtype != np.uint8:
        x = x.astype(np.float32)
    y = np.asarray(y)
    y = (y.astype(np.int32) if loss_name == "cross_entropy"
         else y.astype(np.float32))
    if len(x) != len(y):
        raise ValueError(f"batch features/labels length mismatch: "
                         f"{len(x)} vs {len(y)}")
    return x, y


# fit() keeps the epoch data device-resident (one upload, indexed batches)
# up to this many bytes; past it, the per-step host-feed path takes over.
# Half the device's reported HBM limit (the rest is params + activations).
# The cpu test backend reports no limit and gets half of a v5e chip's
# 16 GiB; a tpu that reports none is an error, not a guess.
# Overridable per-fit via TpuLearner.deviceDataCap.
_DEVICE_DATA_CAP_CPU = 8 << 30
_device_data_cap_cache: Optional[int] = None


def _device_data_cap() -> int:
    global _device_data_cap_cache
    if _device_data_cap_cache is None:
        dev = jax.local_devices()[0]
        limit = int((dev.memory_stats() or {}).get("bytes_limit", 0))
        if limit > 0:
            _device_data_cap_cache = limit // 2
        elif on_tpu():
            raise RuntimeError(
                f"{dev.device_kind} reports no memory_stats()['bytes_limit']"
                f"; set TpuLearner.deviceDataCap explicitly")
        else:
            _device_data_cap_cache = _DEVICE_DATA_CAP_CPU
    return _device_data_cap_cache


# below this size the scan path re-uploads a freshly permuted epoch every
# epoch (true reshuffle; the transfer is cheaper than one train step);
# above it, shuffling is upload-permutation + per-epoch rotation/window
# order (see _make_scan_epoch_fn). Overridable via
# TpuLearner.epochReshuffleCap.
_EPOCH_RESHUFFLE_CAP = 32 << 20


def _wrap_rows(arr: np.ndarray, n_pad: int) -> np.ndarray:
    """Extend dim 0 to exactly ``n_pad`` rows by wrapping from the start
    (the pad rows are weighted out by the caller)."""
    if len(arr) == n_pad:
        return arr
    reps = -(-n_pad // max(1, len(arr)))
    return np.concatenate([arr] * reps, axis=0)[:n_pad]


def _scan_batch(bs: int, mesh, micro: int = 1) -> int:
    """The scan path's device batch: requested batch rounded up to a
    data-axis multiple (windows must shard evenly); pipeline runs also
    need divisibility by microbatches x data axis."""
    mult = mesh.shape["data"] * max(1, micro)
    return -(-bs // mult) * mult


def _host_tree(tree):
    """Pytree of device arrays -> host numpy. Handles multiprocess
    TP-sharded leaves: the trainer constrains model axes to be
    process-local, so each process's addressable shards cover the full
    array (replicated leaves read the local copy directly)."""
    def conv(a):
        if not isinstance(a, jax.Array) \
                or meshlib.effective_process_count() == 1 \
                or a.is_fully_replicated:
            return np.asarray(a)
        out = np.empty(a.shape, a.dtype)
        for sh in a.addressable_shards:
            out[sh.index] = np.asarray(sh.data)
        return out
    return jax.tree_util.tree_map(conv, tree)


def _params_digest(params) -> str:
    """sha256 over the host bytes of every param leaf (treedef order) —
    the elastic coordinator's bit-exact-resume evidence: a resumed
    attempt's digest must equal the digest of the checkpoint it claims to
    restore."""
    import hashlib
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(_host_tree(params)):
        h.update(np.ascontiguousarray(leaf).tobytes())
    return h.hexdigest()


def _replace_like(host_tree, placed_tree):
    """Put a host-numpy tree back onto the shardings of an already-placed
    tree (multi-process checkpoint restore: device_put cannot target
    non-addressable devices, so rebuild each global array from the local
    slice of the identical host value every process holds)."""
    def conv(h, p):
        if not isinstance(p, jax.Array):
            return h
        host = np.asarray(h)
        return jax.make_array_from_callback(
            host.shape, p.sharding, lambda idx, hh=host: hh[idx])
    return jax.tree_util.tree_map(conv, host_tree, placed_tree)


_require_inner_block_local = meshlib.require_inner_block_local


def _fmt_pos(pos: Optional[tuple]) -> str:
    """Human form of a checkpoint position tuple for error messages."""
    if pos is None:
        return "none"
    epoch, step = pos
    return (f"epoch {epoch}" if step is None
            else f"epoch {epoch} step {step}")


def _place_params(params, mesh, tx, *, tp: int = 1, ep: int = 1):
    """Place params AND optimizer state on the mesh with explicit
    shardings. The opt state is initialized on host and placed under the
    same rules as the params (optax state trees embed the param tree, so
    the path-substring rules match the mirrored buffers) — letting jit
    infer the init's output shardings instead leaves them compiler-chosen,
    which on a multi-process mesh can land buffers on one device per
    process and poison every later step with inconsistent shardings."""
    from jax.sharding import PartitionSpec as P
    rules = []
    if ep > 1:
        rules += [("expert_w", P("expert",)), ("expert_b", P("expert",))]
    if tp > 1:
        rules += list(meshlib.TP_PARAM_RULES)
    if meshlib.effective_process_count() == 1:
        # single process: jit-inferred init shardings are correct AND free
        # (no host round-trip of the whole model)
        if rules:
            params = meshlib.shard_params_tp(params, mesh, rules)
        else:
            params = meshlib.put_replicated(params, mesh)
        return params, jax.jit(tx.init)(params)
    opt = tx.init(jax.tree_util.tree_map(np.asarray, params))
    if rules:
        params = meshlib.shard_params_tp(params, mesh, rules)
        opt = meshlib.shard_params_tp(opt, mesh, rules)
    else:
        params = meshlib.put_replicated(params, mesh)
        opt = meshlib.put_replicated(opt, mesh)
    return params, opt


def _make_loss_compute(module, loss_fn, is_moe: bool, moe_aux: float,
                       step_stats: bool = False):
    """The weighted scalar loss of one batch — the ONE forward every
    precision mode and step path shares. The model casts itself to its
    compute dtype (flax ``dtype=``), so precision selection rides the
    model config; the loss reduction stays f32. ``step_stats``: the model
    returns per-step counts beside its predictions (`step_stat_names`) and
    ``compute`` returns ``(loss, counts)``. A loss with ``module_kwargs``
    (`make_loss`: ``next_token``) is computed by the module from its own
    input: the kwargs are passed on and what comes back are the rows'
    losses."""
    asked = getattr(loss_fn, "module_kwargs", {})
    if asked and not getattr(module, "has_lm_head", False):
        raise ValueError(
            f"loss 'next_token' needs a model with a vocabulary head "
            f"(`has_lm_head`); {type(module).__name__} has none")

    def compute(p, xb, yb, wb):
        # weighted mean so mesh-padding rows (weight 0) carry no gradient.
        # MoE routing must see the row weights too: padded rows may not
        # claim expert capacity or skew the balancing stats
        kw = {"row_mask": wb} if is_moe else {}
        kw.update(asked)
        if step_stats:
            kw["step_stats"] = True
        if moe_aux > 0.0:
            preds, inter = module.apply(p, xb, mutable=["intermediates"],
                                        **kw)
            from .moe import read_moe_aux_loss
            # a family without an auxiliary loss sows nothing
            aux = read_moe_aux_loss(inter.get("intermediates", {}))
        else:
            preds = module.apply(p, xb, **kw)
            aux = 0.0
        stats = None
        if step_stats:
            preds, stats = preds
        losses = loss_fn(preds, yb)
        main = jnp.sum(losses * wb) / jnp.maximum(jnp.sum(wb), 1.0)
        loss = main + moe_aux * aux
        return (loss, stats) if step_stats else loss

    return compute


def _make_step_body(module, tx, loss_fn, is_moe: bool, moe_aux: float,
                    grad_clip: float = 0.0, step_stats: bool = False):
    """The un-jitted optimizer step: loss -> grads -> update. Shared by the
    one-step-per-dispatch path (fitStream, multi-host) and the scanned
    multi-step path (fit's default). With ``step_stats`` the model's
    per-step counts follow the loss: ``(params, opt_state, loss, counts)``."""
    compute = _make_loss_compute(module, loss_fn, is_moe, moe_aux,
                                 step_stats)

    def step_body(params, opt_state, xb, yb, wb):
        loss, grads = jax.value_and_grad(
            lambda p: compute(p, xb, yb, wb), has_aux=step_stats)(params)
        if grad_clip > 0.0:
            from .precision import clip_by_global_norm
            grads = clip_by_global_norm(grads, grad_clip)
        updates, opt2 = tx.update(grads, opt_state, params)
        new_params = optax.apply_updates(params, updates)
        if step_stats:
            loss, stats = loss
            return new_params, opt2, loss, stats
        return new_params, opt2, loss

    return step_body


def _make_mixed_step_body(module, tx, loss_fn, is_moe: bool, moe_aux: float,
                          grad_clip: float = 0.0):
    """bf16_mixed twin of _make_step_body: the fused
    cast→grad→unscale→clip→update body threading a ScaleState
    (models/precision.py). Signature gains the scale_state operand:
    ``(params, opt_state, scale_state, xb, yb, wb) ->
    (params, opt_state, scale_state, loss)``."""
    from .precision import make_mixed_step_body
    return make_mixed_step_body(
        _make_loss_compute(module, loss_fn, is_moe, moe_aux), tx, grad_clip)


def _make_pp_step_body(cfg: dict, mesh, tx, loss_fn, n_micro: int):
    """Optimizer step whose forward runs the encoder stack as a GPipe
    pipeline over the mesh's ``pipe`` axis (parallel.pipeline_parallel.
    transformer_pp_forward); params keep the plain flax layout so
    checkpoints/TpuModel reuse the tree unchanged."""
    from ..parallel.pipeline_parallel import transformer_pp_forward

    def step_body(params, opt_state, xb, yb, wb):
        def compute(p):
            preds = transformer_pp_forward(cfg, p, xb, mesh,
                                           n_microbatches=n_micro)
            losses = loss_fn(preds, yb)
            return jnp.sum(losses * wb) / jnp.maximum(jnp.sum(wb), 1.0)
        loss, grads = jax.value_and_grad(compute)(params)
        updates, opt2 = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt2, loss

    return step_body


def _make_train_step(module, tx, loss_fn, is_moe: bool, moe_aux: float,
                     step_body=None, mixed: bool = False,
                     grad_clip: float = 0.0, featurize=None,
                     step_stats: bool = False):
    """One jitted optimizer step (fitStream / multi-host feed path).

    ``step_stats`` (the plain step only: not ``mixed``, ``featurize`` or a
    caller's ``step_body``): the program also returns the model's per-step
    counts, last (`_make_step_body`); `_dispatch_step` is told the same.

    ``featurize`` (fit-side pipeline fusion, core/capture.py) is a pure
    traced ``(fparams, raw_arrays) -> (xb, yb)`` body run INSIDE the
    same program as the optimizer step: the step signature becomes
    ``(params, opt_state, fparams, raws, wb)`` (mixed: scale_state after
    opt_state), the raw column tuple is donated in place of (xb, yb),
    and the featurized intermediates only ever exist as XLA temporaries
    — they never touch host, and the H2D transfer is the raw wire-dtype
    rows. ``fparams`` are fit-constants placed once, never donated.

    The batch buffers (xb, yb) are DONATED on accelerator backends: the
    feed path uploads a fresh batch every step and never reads it back, so
    XLA reuses their HBM for the step's outputs instead of allocating
    alongside. The weight mask wb is NOT donated — the feed path caches one
    placed mask per (rows, n_real) signature and reuses it across steps.

    ``mixed=True`` (precision='bf16_mixed') jits the fused loss-scaling
    body instead and additionally donates the FULL training state —
    (params, opt_state, scale_state) — so the whole update is one
    dispatch whose state buffers are reused in place (the state outputs
    are jit outputs, never host-aliased, so this donation is safe on
    every backend).

    On the CPU backend the BATCH donation is DISABLED: ``device_put``
    there can alias the host numpy buffer zero-copy, and donating an
    aliased buffer hands memory the host allocator still owns back to
    XLA as scratch — the step outputs land in pages numpy reuses for
    later allocations, and training corrupts nondeterministically
    (losses exploding to ~1e35 on a fitStream that is bit-identical to
    fit() with donation off). Host memory is not the scarce resource on
    CPU, so nothing is lost."""
    from ..analysis import sanitize
    cpu = jax.default_backend() == "cpu"
    # `mixed`/`featurize` are host-side factory flags, static at build
    # time (the profiler.wrap discovery over-approximates this FACTORY
    # as a traced body — only the returned step functions are ever
    # traced)
    if featurize is not None:   # graftlint: disable=jit-traced-branch
        if mixed:   # graftlint: disable=jit-traced-branch
            inner = step_body or _make_mixed_step_body(
                module, tx, loss_fn, is_moe, moe_aux, grad_clip)

            def fused_mixed(params, opt_state, scale_state, fparams,
                            raws, wb):
                xb, yb = featurize(fparams, raws)
                return inner(params, opt_state, scale_state, xb, yb, wb)

            donate = (0, 1, 2) if cpu else (0, 1, 2, 4)
            return sanitize.wrap_donated(
                jax.jit(fused_mixed, donate_argnums=donate), donate,
                label="trainer.step_fused_mixed")
        inner = step_body or _make_step_body(module, tx, loss_fn, is_moe,
                                             moe_aux, grad_clip)

        def fused_step(params, opt_state, fparams, raws, wb):
            xb, yb = featurize(fparams, raws)
            return inner(params, opt_state, xb, yb, wb)

        donate = () if cpu else (3,)
        return sanitize.wrap_donated(
            jax.jit(fused_step, donate_argnums=donate), donate,
            label="trainer.step_fused")
    if mixed:   # graftlint: disable=jit-traced-branch
        body = step_body or _make_mixed_step_body(
            module, tx, loss_fn, is_moe, moe_aux, grad_clip)
        donate = (0, 1, 2) if cpu else (0, 1, 2, 3, 4)
        return sanitize.wrap_donated(jax.jit(body, donate_argnums=donate),
                                     donate, label="trainer.step_mixed")
    donate = () if cpu else (2, 3)
    return sanitize.wrap_donated(
        jax.jit(step_body or
                _make_step_body(module, tx, loss_fn, is_moe, moe_aux,
                                grad_clip, step_stats=step_stats),
                donate_argnums=donate),
        donate, label="trainer.step")


def _make_scan_epoch_fn(module, tx, loss_fn, is_moe: bool, moe_aux: float,
                        mesh, bs: int, step_body=None, mixed: bool = False,
                        grad_clip: float = 0.0, featurize=None):
    """A whole epoch of optimizer steps per XLA dispatch over
    DEVICE-RESIDENT data.

    The single-step loop pays one host dispatch (~ms) plus a host->HBM batch
    transfer per step; here the epoch stays in HBM, the host ships only a
    tiny shuffle plan, and ``lax.scan`` runs every step inside one jitted
    call with params/opt_state donated, so the steady state is pure device
    work. Reference contrast: cntk-train re-reads its training file from
    disk every epoch (CommandBuilders.scala:200-228 scp + CNTK text reader).

    Shuffling is rotation + window permutation, NOT a per-step random
    gather: a row gather from HBM measures ~3x a whole ResNet-20 train
    step on v5e (XLA lowers 1-byte-row gathers near-scalar), while
    contiguous ``dynamic_slice`` windows from a resident array are pure
    sequential HBM traffic (measured at full step rate). The epoch array
    carries a bs-row wrap margin (its own first rows repeated) so a
    rotated window never wraps; the host picks a fresh rotation and window
    order per epoch — every row exactly once per epoch, batch boundaries
    shifting every epoch.
    """
    from functools import partial

    data_sh = meshlib.batch_sharding(mesh)

    def window(arrs, o):
        xb = jax.lax.dynamic_slice_in_dim(arrs[0], o, bs, 0)
        yb = jax.lax.dynamic_slice_in_dim(arrs[1], o, bs, 0)
        wb = jax.lax.dynamic_slice_in_dim(arrs[2], o, bs, 0)
        if mesh.size > 1:  # trivial meshes stay off the SPMD path
            xb = jax.lax.with_sharding_constraint(xb, data_sh)
            yb = jax.lax.with_sharding_constraint(yb, data_sh)
        return xb, yb, wb

    # host-side factory flags, static at build time (see _make_train_step)
    if featurize is not None:   # graftlint: disable=jit-traced-branch
        # fit-side pipeline fusion: the epoch data stays resident as RAW
        # wire-dtype columns and every scan window featurizes inside the
        # same dispatch as its optimizer step — the featurized epoch
        # never exists anywhere, not even in HBM
        def fused_window(fparams, raw_alls, w_all, o):
            rs = tuple(jax.lax.dynamic_slice_in_dim(r, o, bs, 0)
                       for r in raw_alls)
            wb = jax.lax.dynamic_slice_in_dim(w_all, o, bs, 0)
            xb, yb = featurize(fparams, rs)
            if mesh.size > 1:
                xb = jax.lax.with_sharding_constraint(xb, data_sh)
                yb = jax.lax.with_sharding_constraint(yb, data_sh)
            return xb, yb, wb

        from ..analysis import sanitize
        if mixed:   # graftlint: disable=jit-traced-branch
            mixed_body = step_body or _make_mixed_step_body(
                module, tx, loss_fn, is_moe, moe_aux, grad_clip)

            @partial(jax.jit, donate_argnums=(0, 1, 2))
            def run_epoch_fused_mixed(params, opt_state, scale_state,
                                      fparams, raw_alls, w_all, starts):
                def body(carry, o):
                    p, opt, s = carry
                    xb, yb, wb = fused_window(fparams, raw_alls, w_all, o)
                    p, opt, s, loss = mixed_body(p, opt, s, xb, yb, wb)
                    return (p, opt, s), loss
                (params, opt_state, scale_state), losses = jax.lax.scan(
                    body, (params, opt_state, scale_state), starts)
                return params, opt_state, scale_state, losses[-1]

            return sanitize.wrap_donated(
                run_epoch_fused_mixed, (0, 1, 2),
                label="trainer.scan_epoch_fused_mixed")

        plain_body = step_body or _make_step_body(module, tx, loss_fn,
                                                  is_moe, moe_aux,
                                                  grad_clip)

        @partial(jax.jit, donate_argnums=(0, 1))
        def run_epoch_fused(params, opt_state, fparams, raw_alls, w_all,
                            starts):
            def body(carry, o):
                p, opt = carry
                xb, yb, wb = fused_window(fparams, raw_alls, w_all, o)
                p, opt, loss = plain_body(p, opt, xb, yb, wb)
                return (p, opt), loss
            (params, opt_state), losses = jax.lax.scan(
                body, (params, opt_state), starts)
            return params, opt_state, losses[-1]

        return sanitize.wrap_donated(run_epoch_fused, (0, 1),
                                     label="trainer.scan_epoch_fused")
    if mixed:   # graftlint: disable=jit-traced-branch
        mixed_body = step_body or _make_mixed_step_body(
            module, tx, loss_fn, is_moe, moe_aux, grad_clip)

        # the scale state scans WITH (params, opt_state): a skipped step
        # inside the window backs the scale off for the very next step of
        # the same dispatch — no host round-trip in the recurrence
        @partial(jax.jit, donate_argnums=(0, 1, 2))
        def run_epoch_mixed(params, opt_state, scale_state, x_all, y_all,
                            w_all, starts):
            def body(carry, o):
                p, opt, s = carry
                xb, yb, wb = window((x_all, y_all, w_all), o)
                p, opt, s, loss = mixed_body(p, opt, s, xb, yb, wb)
                return (p, opt, s), loss
            (params, opt_state, scale_state), losses = jax.lax.scan(
                body, (params, opt_state, scale_state), starts)
            return params, opt_state, scale_state, losses[-1]

        from ..analysis import sanitize
        return sanitize.wrap_donated(run_epoch_mixed, (0, 1, 2),
                                     label="trainer.scan_epoch_mixed")

    step_body = step_body or _make_step_body(module, tx, loss_fn, is_moe,
                                             moe_aux, grad_clip)

    @partial(jax.jit, donate_argnums=(0, 1))
    def run_epoch(params, opt_state, x_all, y_all, w_all, starts):
        # starts: (S,) int32 rotated+permuted window offsets into an
        # epoch array of n_pad + bs rows; w_all weights out padding rows
        def body(carry, o):
            p, opt = carry
            xb, yb, wb = window((x_all, y_all, w_all), o)
            p, opt, loss = step_body(p, opt, xb, yb, wb)
            return (p, opt), loss
        (params, opt_state), losses = jax.lax.scan(
            body, (params, opt_state), starts)
        return params, opt_state, losses[-1]

    from ..analysis import sanitize
    return sanitize.wrap_donated(run_epoch, (0, 1),
                                 label="trainer.scan_epoch")


class TpuLearner(Estimator):
    """Data-parallel (optionally tensor-parallel) neural-net training."""

    featuresCol = StringParam("features column (vectors or images)",
                              default="features")
    labelCol = StringParam("label column", default="label")
    modelConfig = DictParam("declarative model config", default=None)
    inputShape = ListParam("CHW shape for flat-vector features", default=())
    optimizer = StringParam("sgd|momentum|adam|adamw", default="momentum",
                            choices=("sgd", "momentum", "adam", "adamw"))
    learningRate = FloatParam("learning rate", default=0.01, min=0.0)
    momentum = FloatParam("momentum coefficient", default=0.9)
    weightDecay = FloatParam("weight decay", default=0.0)
    batchSize = IntParam("global batch size", default=256, min=1)
    epochs = IntParam("training epochs", default=5, min=1)
    loss = StringParam("cross_entropy|mse|next_token (the per-token "
                       "language-model loss of a family with a vocabulary "
                       "head; reads no label)", default="cross_entropy",
                       choices=("cross_entropy", "mse", "next_token"))
    seed = IntParam("PRNG seed", default=0)
    shuffle = BooleanParam("shuffle each epoch", default=True)
    checkpointDir = StringParam("per-epoch checkpoint directory ('' = off)",
                                default="")
    checkpointEverySteps = IntParam(
        "also checkpoint every N optimizer steps WITHIN an epoch (0 = "
        "epoch boundaries only). Step checkpoints make long epochs "
        "preemption-tolerant: a killed fit resumes from the last step "
        "interval instead of the last epoch. Applies to the per-step "
        "feed/stream paths; the scan path's epoch is already one "
        "dispatch. Requires checkpointDir", default=0, min=0)
    asyncCheckpoint = BooleanParam(
        "publish checkpoints from a background writer thread "
        "(resilience/ckpt.py): the step loop takes only the host "
        "snapshot; serialization, fsync, the atomic rename and the "
        "manifest commit overlap with the next steps (depth-1 queue, "
        "newest-wins coalescing, wait() barrier at epoch end / fit "
        "exit). Lets checkpointEverySteps drop ~10x — a smaller elastic "
        "replay window — without stalling the fit", default=False)
    checkpointKeepSteps = IntParam(
        "step checkpoints retained per epoch (keep-last-K pruning as new "
        "ones commit; the epoch-final save still clears the rest). The "
        "checkpoint an elastic fit last resumed from — the consensus "
        "floor — is never pruned. Bounds a long fit's msgpack "
        "accumulation at K files per in-flight epoch", default=3, min=1)
    checkpointShards = IntParam(
        "split each checkpoint into this many byte-balanced shard files "
        "(0/1 = one msgpack). Multi-process fleets write ONE shard per "
        "host (this param arms the mode; the shard count is the process "
        "count) so no host ever serializes the whole model; the "
        "coordinator commits the manifest LAST, after verifying every "
        "shard's size+sha256 — a torn shard disqualifies the whole "
        "candidate and resume falls back to the previous committed "
        "checkpoint. Shard count is recorded in the manifest, so an "
        "N-shard checkpoint resumes onto any mesh size", default=0,
        min=0)
    tensorParallel = IntParam("size of the model (TP) mesh axis", default=1,
                              min=1)
    sequenceParallel = IntParam("size of the sequence (SP) mesh axis "
                                "(transformer only)", default=1, min=1)
    spMode = StringParam("sequence-parallel collective form", default="ring",
                         choices=("ring", "ulysses"))
    expertParallel = IntParam("size of the expert (EP) mesh axis (MoE "
                              "transformer only)", default=1, min=1)
    pipelineParallel = IntParam(
        "size of the pipeline (PP) mesh axis: the transformer's encoder "
        "blocks split into stages run as a GPipe microbatch pipeline over "
        "ppermute (transformer only; layers must divide by it)", default=1,
        min=1)
    moeAuxWeight = FloatParam("weight of the MoE load-balancing aux loss",
                              default=0.01, min=0.0)
    precision = StringParam(
        "compute precision of the jitted train step: 'bf16' (default) = "
        "bf16 activations/grads over f32 master weights (the MXU-native "
        "mode the model families already default to); 'f32' = full-"
        "precision compute (parity baseline / numerics debugging); "
        "'bf16_mixed' = bf16 compute PLUS dynamic loss scaling — the "
        "fused step scales the loss before the backward pass, unscales "
        "and (optionally) clips the grads, SKIPS the update when any "
        "grad is non-finite (scale backs off; skips counted on "
        "mmlspark_trainer_skipped_steps_total), grows the scale on "
        "sustained stability, and donates (params, opt_state, "
        "scale_state) so the whole update stays one XLA dispatch. "
        "Checkpoints always store the f32 masters, plus the scale state "
        "under bf16_mixed, so resume is bit-exact per mode",
        default="bf16", choices=("f32", "bf16", "bf16_mixed"))
    gradClipNorm = FloatParam(
        "global-L2-norm gradient clip applied inside the fused step "
        "(0 = off); under bf16_mixed the clip runs AFTER unscaling, so "
        "the threshold is in true gradient units", default=0.0, min=0.0)
    lossScaleInit = FloatParam(
        "initial dynamic loss scale for precision='bf16_mixed' "
        "(backoff halves it on non-finite grads; growth doubles it "
        "after sustained finite steps)", default=float(2.0 ** 15),
        min=1.0)
    haltOnNonFinite = BooleanParam(
        "raise when the epoch loss goes NaN/inf instead of training on "
        "garbage (failure detection the reference lacks, SURVEY.md §5)",
        default=True)
    stepsPerDispatch = IntParam(
        "optimizer steps fused into one XLA dispatch (lax.scan over "
        "device-resident epoch windows, donated state); 0 = whole epoch. "
        "Amortizes host dispatch latency — the single-host fit() fast "
        "path", default=0, min=0)
    deviceDataCap = IntParam(
        "bytes of epoch data kept device-resident before the per-step "
        "host-feed path takes over; 0 = derive from the chip's reported "
        "HBM (half of bytes_limit; 8 GiB fallback where the backend "
        "reports none)", default=0, min=0)
    epochReshuffleCap = IntParam(
        "datasets up to this many bytes re-upload a true fresh "
        "permutation every epoch on the scan path; larger ones rotate + "
        "window-permute a once-permuted upload; 0 = the 32 MiB default",
        default=0, min=0)
    prefetchDepth = IntParam(
        "host batches prepared + placed on device ahead of the step "
        "consuming them (feed/stream paths; the scan path is already "
        "device-resident). 2 = double buffering; 0 = synchronous. The "
        "prefetched loss trajectory is bit-identical to the synchronous "
        "one — only the overlap changes", default=2, min=0)
    profile = BooleanParam(
        "device-profile this fit: per-dispatch XLA cost analysis (FLOPs, "
        "bytes), compile accounting with recompile-cause attribution, "
        "achieved-FLOPs/roofline gauges, and live-buffer HBM sampling "
        "(telemetry.profiler). Enables telemetry and adds a sync point "
        "per dispatch — measurement mode, not the production default",
        default=False)
    elastic = BooleanParam(
        "run fit through the elastic training runtime "
        "(resilience/elastic.py): host heartbeats + a TrainSupervisor "
        "declare a dead/preempted host within the grace window, the fit "
        "re-meshes over the surviving hosts and resumes from the latest "
        "(epoch, step) consensus checkpoint — zero committed steps lost. "
        "Requires checkpointDir; forces the per-step feed path; composes "
        "with data(+tensor) parallelism only", default=False)
    elasticHosts = IntParam(
        "failure domains for elastic training: 0 = one host per JAX "
        "process (the real host boundary); >1 single-process = split the "
        "local devices into this many simulated host groups (chaos "
        "testing / laptop rehearsal of the multi-host recovery path)",
        default=0, min=0)
    elasticMinHosts = IntParam(
        "survivors needed to keep training in-job after a host loss; "
        "below it the fit raises ElasticFleetLost (relaunch the fleet "
        "against the same checkpointDir to resume)", default=1, min=1)
    elasticGraceSeconds = FloatParam(
        "heartbeat age that turns silence into a death verdict; 0 = "
        "MMLSPARK_TPU_ELASTIC_GRACE or 2.0", default=0.0, min=0.0)
    elasticMaxFailures = IntParam(
        "transient fit failures tolerated WITHOUT a host verdict before "
        "the elastic loop gives up (failures attributed to a dead host "
        "re-mesh instead and do not burn this budget)", default=5, min=1)
    elasticMaxHosts = IntParam(
        "ceiling for in-job GROW: a relaunched host whose joining "
        "heartbeat earns a grow verdict re-enters the mesh at the next "
        "checkpoint boundary only while the pool is below this many "
        "hosts (0 = the launch fleet size). Shrink is unaffected",
        default=0, min=0)
    stragglerEvictAfter = IntParam(
        "promote a straggler verdict (rolling-MAD step-time anomaly, "
        "advisory by default) into a proactive EVICT after this many "
        "consecutive flagged supervisor passes: the slow host is "
        "dropped at the next committed checkpoint boundary — the same "
        "unwind path as a host loss, fired BEFORE the slow-then-dead "
        "host actually dies — and rejoins through the grow path once "
        "recovered. Floors: survivors must satisfy elasticMinHosts and "
        "the coordinator host is never evicted. 0 = advisory only",
        default=0, min=0)
    sloConfig = DictParam(
        "declarative SLO config evaluated DURING this fit "
        "(telemetry.slo): either a full {'objectives': [...], "
        "'interval': s} document, or the {'stepTimeBudget': seconds, "
        "'windows': [fast_s, slow_s]} shorthand for a mean-step-time "
        "objective over mmlspark_trainer_step_seconds. Enables telemetry "
        "+ the time-series sampler for the fit; breaches surface as "
        "slo/breach trace instants, flight-recorder notes and the "
        "mmlspark_slo_* gauges, and the final per-objective state lands "
        "on the learner as _last_slo_report", default=None)

    # ---- checkpointing (reference has none; SURVEY.md §5) ----
    # Two granularities: ``ckpt_EEEEE.msgpack`` marks epoch E COMPLETE;
    # ``ckpt_EEEEE_sSSSSSSS.msgpack`` (checkpointEverySteps > 0) marks
    # step S within epoch E done — preemption tolerance for long epochs.
    def _ckpt_path(self, epoch: int, step: Optional[int] = None) -> str:
        name = (f"ckpt_{epoch:05d}.msgpack" if step is None
                else f"ckpt_{epoch:05d}_s{step:07d}.msgpack")
        return os.path.join(self.getCheckpointDir(), name)

    @staticmethod
    def _parse_ckpt_name(fname: str) -> Optional[tuple]:
        """'ckpt_00002.msgpack' -> (2, None); 'ckpt_00002_s0000005.msgpack'
        -> (2, 5); anything else -> None."""
        if not (fname.startswith("ckpt_") and fname.endswith(".msgpack")):
            return None
        stem = fname[len("ckpt_"):-len(".msgpack")]
        try:
            if "_s" in stem:
                e, s = stem.split("_s", 1)
                return int(e), int(s)
            return int(stem), None
        except ValueError:
            return None

    def _ckpt_candidates(self) -> list:
        """Every on-disk checkpoint as ``((epoch, step), filename)``,
        best candidate first (epoch desc; an epoch-final outranks any
        step checkpoint of its epoch; later steps outrank earlier)."""
        d = self.getCheckpointDir()
        if not d or not os.path.isdir(d):
            return []
        found = [(p, f) for f in os.listdir(d)
                 if (p := self._parse_ckpt_name(f)) is not None]
        found.sort(key=lambda pf: (pf[0][0], pf[0][1] is None,
                                   -1 if pf[0][1] is None else pf[0][1]),
                   reverse=True)
        return found

    def _latest_checkpoint(self) -> Optional[tuple]:
        """The newest MANIFEST-VERIFIED training position on disk as
        ``(epoch, step)`` — ``step is None`` means the epoch completed.
        A file the manifest doesn't vouch for (a torn write: renamed but
        crashed before the manifest commit, or size drift) is skipped
        with a warning and ``mmlspark_ckpt_corrupt_total``; the previous
        checkpoint becomes the candidate. Pre-manifest directories pass
        verification unconditionally."""
        from ..resilience import ckpt as ckptlib
        d = self.getCheckpointDir()
        for pos, fname in self._ckpt_candidates():
            if ckptlib.verify(d, fname):
                return pos
        return None

    def _ckpt_writer(self):
        """The per-learner background checkpoint publisher (created on
        first async save)."""
        w = getattr(self, "_ckpt_writer_inst", None)
        if w is None:
            from ..resilience.ckpt import AsyncCheckpointWriter
            w = self._ckpt_writer_inst = AsyncCheckpointWriter("trainer")
        return w

    def _ckpt_barrier(self):
        """Async-checkpoint barrier: returns once no write is pending or
        in flight (no-op when asyncCheckpoint never armed). Taken at
        epoch boundaries, fit exit, and before any resume read. A
        writer-thread error re-raises here — unless another exception is
        already unwinding (a HostLossError mid-recovery must not be
        masked by a failed background write; it is logged instead).
        Elastic multi-process fits bound the wait: a writer snapshotting
        the output of a collective whose peer died blocks FOREVER (the
        buffers never materialize), so past the bound the writer is
        orphaned (daemon thread) and recovery proceeds — the
        manifest-last protocol guarantees its partial write can never
        become a resume candidate."""
        import sys
        import threading
        # an ORPHANED elastic attempt thread (abandoned while pinned in
        # a dead collective) must not touch the live writer when its
        # collective finally times out and it unwinds
        active = getattr(self, "_active_fit_thread", None)
        if active is not None \
                and active is not threading.current_thread():
            return
        w = getattr(self, "_ckpt_writer_inst", None)
        if w is None:
            return
        timeout = (10.0 if getattr(self, "_elastic_multiproc", False)
                   else None)

        def _bounded_wait():
            if w.wait(timeout=timeout):
                return True
            log.warning("async checkpoint writer stalled past %.0fs "
                        "(dead-collective snapshot?); abandoning it — "
                        "uncommitted writes can never become resume "
                        "candidates", timeout)
            self._ckpt_writer_inst = None
            return False

        if sys.exc_info()[0] is None:
            _bounded_wait()
            return
        try:
            _bounded_wait()
        except Exception as e:
            log.warning("async checkpoint failure surfaced while another "
                        "error unwinds (kept secondary): %s", e)

    def _prune_step_checkpoints(self, epoch: int, keep: Optional[int]):
        """Drop this epoch's step checkpoints beyond the newest ``keep``
        (``None`` = drop them all — the epoch-final save supersedes
        them). The consensus floor — the checkpoint this fit resumed
        from — is never pruned: a re-meshing peer may still target it."""
        from ..resilience import ckpt as ckptlib
        d = self.getCheckpointDir()
        floor = getattr(self, "_ckpt_floor", None)
        steps = sorted(p[1] for p, _f in self._ckpt_candidates()
                       if p[0] == epoch and p[1] is not None)
        drop = steps if keep is None else \
            (steps[:-keep] if len(steps) > keep else [])
        names = [f"ckpt_{epoch:05d}_s{s:07d}.msgpack" for s in drop
                 if floor is None or (epoch, s) != tuple(floor)]
        ckptlib.prune(d, names)

    def _ckpt_should_write(self) -> bool:
        """Does THIS process take part in checkpoint saves? Process 0
        always (it owns the single-file commit); on a sharded
        multi-process fleet every process does — each writes its own
        shard, and only process 0 commits the head + manifest."""
        return jax.process_index() == 0 or (
            self.getCheckpointShards() > 0
            and meshlib.effective_process_count() > 1)

    def _save_checkpoint(self, epoch: int, params, opt_state,
                         step: Optional[int] = None, scale_state=None,
                         elastic_ctx=None,
                         state_donated: Optional[bool] = None):
        from ..resilience import ckpt as ckptlib
        os.makedirs(self.getCheckpointDir(), exist_ok=True)
        # fused fits store LEARNER state only — featurize params are fit
        # constants, recorded by digest so resume rejects a checkpoint
        # written under a different featurize plan
        fplan = getattr(self, "_featurize_plan", None)
        extra = ({"featurize_digest": fplan.digest()}
                 if fplan is not None else None)

        # params are ALWAYS the f32 masters (bf16 compute casts per-layer
        # inside the step and never writes back), so every precision mode
        # checkpoints the same full-precision state; bf16_mixed adds its
        # loss-scale recurrence so a resumed fit continues bit-exact
        def build_state():
            st = {"params": _host_tree(params),
                  "opt": serialization.to_state_dict(
                      _host_tree(opt_state))}
            if scale_state is not None:
                from .precision import scale_state_to_host
                st["scale"] = scale_state_to_host(scale_state)
            return st

        # Whether the NEXT dispatch donates these state buffers decides
        # where the device->host snapshot may run. The feed/stream step
        # fns donate state only under bf16_mixed (batches aside), so the
        # plain modes defer the whole snapshot+serialize to the writer
        # thread — JAX arrays are immutable and these buffers are never
        # handed back to XLA, so reading them concurrently is safe, and
        # the step loop pays ~nothing. Donated-state paths (mixed; the
        # scan path donates (params, opt_state) too — its caller passes
        # state_donated=True) must snapshot INLINE before the donation
        # invalidates the buffers.
        if state_donated is None:
            state_donated = scale_state is not None
        path = self._ckpt_path(epoch, step)
        keep = self.getCheckpointKeepSteps()
        nproc = meshlib.effective_process_count()
        # multi-process fleets snapshot INLINE even when nothing is
        # donated: a writer-thread materialization would block on the
        # step's collective output while the fit thread keeps enqueueing
        # more collectives — concurrent tag-matched gloo ops from a deep
        # async queue can wedge cross-rank. The inline device_get is the
        # per-save materialization barrier that keeps the in-flight
        # depth bounded (the posture every multi-host save had before
        # sharding); serialization + IO still overlap on the writer.
        state_donated = state_donated or nproc > 1
        cfg_shards = self.getCheckpointShards()
        # multi-process fleets shard per host (no host serializes the
        # whole model); single-process splits into the configured count
        n_shards = (nproc if (cfg_shards and nproc > 1)
                    else (cfg_shards if cfg_shards > 1 else 0))
        rank = jax.process_index() if nproc > 1 else 0

        def on_commit():
            # runs strictly AFTER the rename + manifest commit (writer
            # thread under asyncCheckpoint, inline otherwise): pruning
            # and the elastic checkpoint-boundary hook must only ever
            # see durable state. The consensus floor advances to the
            # just-committed position — the previous floor is superseded
            # as a resume target and becomes prunable
            self._ckpt_floor = (epoch, step)
            if step is None:
                self._prune_step_checkpoints(epoch, keep=None)
            else:
                self._prune_step_checkpoints(epoch, keep=keep)
            if elastic_ctx is not None:
                elastic_ctx.checkpoint_saved(epoch, step)

        # elastic multi-process fits route EVERY save through the async
        # writer: a synchronous snapshot materializes device buffers on
        # the fit thread, and a peer dying mid-collective would block
        # that thread forever — on the writer thread the stall is
        # bounded + abandoned by _ckpt_barrier instead
        use_async = (self.getAsyncCheckpoint()
                     or getattr(self, "_elastic_multiproc", False))

        if not n_shards:
            if use_async:
                if state_donated:
                    state = build_state()   # inline: donation is imminent
                    payload = (lambda:
                               serialization.msgpack_serialize(state))
                else:
                    payload = (lambda: serialization.msgpack_serialize(
                        build_state()))
                self._ckpt_writer().submit(
                    path, payload, on_commit=on_commit,
                    publish_fn=((lambda p, d: ckptlib.publish(
                        p, d, extra=extra)) if extra else None))
                if step is None:
                    self._ckpt_barrier()  # epoch boundaries stay ordered
            else:
                ckptlib.publish(
                    path, serialization.msgpack_serialize(build_state()),
                    extra=extra)
                on_commit()
            return

        # ---- sharded save: byte-balanced leaf partition of the full
        # state dict; every host computes the identical split (same
        # replicated state, sorted keys), so host i serializes shard i
        # alone. Commit protocol: shard files first (fsync+rename, site
        # ckpt.shard), then the coordinator verifies all shards and
        # commits head + manifest LAST.
        base = os.path.basename(path)

        def build_flat():
            return ckptlib.flatten_state(
                serialization.to_state_dict(build_state()))

        def split(flat):
            keys = sorted(flat)
            sizes = [getattr(flat[k], "nbytes", 64) for k in keys]
            return keys, ckptlib.partition_leaves(sizes, n_shards)

        shard_names = [ckptlib.shard_name(base, i) for i in range(n_shards)]

        committed = {"ok": True}
        if nproc > 1:
            def payload_fn(flat=None):
                flat = build_flat() if flat is None else flat
                keys, parts = split(flat)
                return serialization.msgpack_serialize(
                    {keys[i]: flat[keys[i]] for i in parts[rank]})

            def publish_fn(p, payload):
                ckptlib.write_shard(
                    os.path.join(os.path.dirname(p),
                                 ckptlib.shard_name(base, rank)), payload)
                if rank == 0:
                    # a peer's newest-wins writer may have coalesced this
                    # snapshot away: skip the commit (no manifest entry
                    # -> never a candidate) instead of stalling the fit
                    if ckptlib.await_shards(os.path.dirname(p),
                                            shard_names, timeout=30.0):
                        ckptlib.commit_sharded(p, shard_names, extra=extra)
                    else:
                        committed["ok"] = False
                        log.warning("sharded checkpoint %s left "
                                    "uncommitted (peer shard missing)",
                                    base)
        else:
            def payload_fn(flat=None):
                flat = build_flat() if flat is None else flat
                keys, parts = split(flat)
                return [serialization.msgpack_serialize(
                    {keys[i]: flat[keys[i]] for i in idxs})
                    for idxs in parts]

            def publish_fn(p, payloads):
                ckptlib.publish_sharded(p, payloads, extra=extra)

        def on_commit_sharded():
            # only a commit that actually landed (head + manifest) may
            # advance the floor and fire the elastic boundary hook
            if rank == 0 and committed["ok"]:
                on_commit()

        if use_async:
            if state_donated:
                flat = build_flat()       # inline: donation is imminent
                payload = (lambda flat=flat: payload_fn(flat))
            else:
                payload = payload_fn
            self._ckpt_writer().submit(path, payload,
                                       on_commit=on_commit_sharded,
                                       publish_fn=publish_fn)
            if step is None:
                self._ckpt_barrier()
        else:
            publish_fn(path, payload_fn())
            on_commit_sharded()

    def _restore_checkpoint(self, pos: tuple, params_tmpl, opt_tmpl):
        """-> (params, opt, scale_host) — scale_host is the checkpointed
        loss-scale dict (bf16_mixed fits) or None (every other mode, and
        checkpoints written before the precision param existed). Raises
        :class:`~..resilience.ckpt.CorruptCheckpoint` when the bytes
        fail the manifest digest or won't decode — the resume loop falls
        back to the previous checkpoint."""
        from ..resilience import ckpt as ckptlib
        path = self._ckpt_path(*pos)
        d, name = os.path.split(path)
        with open(path, "rb") as f:
            blob = f.read()
        if not ckptlib.verify_bytes(d, name, blob):
            raise ckptlib.CorruptCheckpoint(name)
        shards = ckptlib.parse_head(blob)
        try:
            if shards is not None:
                # sharded checkpoint: content-verify + merge every shard
                # and rebuild the state dict; the shard count came from
                # the manifest, not the current mesh, so an N-shard save
                # restores onto any fleet size
                flat: dict = {}
                for sblob in ckptlib.read_shards(d, shards):
                    flat.update(serialization.msgpack_restore(sblob))
                state = ckptlib.unflatten_state(flat)
            else:
                state = serialization.msgpack_restore(blob)
        except ckptlib.CorruptCheckpoint:
            raise
        except Exception as e:
            ckptlib.note_corrupt(name, f"undecodable: {e}")
            raise ckptlib.CorruptCheckpoint(name) from e
        params = serialization.from_state_dict(params_tmpl, state["params"])
        opt = serialization.from_state_dict(opt_tmpl, state["opt"])
        return params, opt, state.get("scale")

    def _consensus_resume(self, resume: Optional[tuple], nproc: int):
        """Multi-host: resume only when EVERY process sees the same
        checkpoint position (shared filesystem); otherwise processes would
        run different step counts -> mismatched collectives -> deadlock.
        Shared by fit() and fitStream()."""
        if nproc <= 1 or not self.getCheckpointDir():
            return resume
        from jax.experimental import multihost_utils
        enc = ((-1, -1) if resume is None
               else (resume[0], -1 if resume[1] is None else resume[1]))
        seen = multihost_utils.process_allgather(np.asarray(enc))
        if (seen == seen[0]).all() and seen[0][0] >= 0:
            e, s = int(seen[0][0]), int(seen[0][1])
            return (e, None if s < 0 else s)
        if seen[:, 0].max() >= 0:
            log.warning(
                "checkpoint positions differ across processes (%s) — "
                "checkpointDir is not shared storage; starting fresh on "
                "all processes", seen.tolist())
        return None

    def _resume_training_state(self, params, opt_state, nproc: int,
                               scale_state=None):
        """Consensus-pick the resume position and restore (params,
        opt_state) onto their existing mesh shardings. Returns (params,
        opt_state, start_epoch, start_step, resume_pos, scale_state) —
        resume_pos is the ``(epoch, step)`` consensus position restored
        from, or None for a fresh start; scale_state is the checkpointed
        loss-scale recurrence when this fit runs bf16_mixed (else the
        passed-through value). Candidates are manifest-verified and a
        restore that still finds corruption (digest mismatch, truncated
        msgpack) falls back to the NEXT-best checkpoint instead of
        bricking the fit — on shared storage every process reads the
        same files, so the fallback lands identically fleet-wide.
        Shared by fit() and fitStream()."""
        from ..resilience import ckpt as ckptlib
        # a previous attempt's async write must land before we list
        # candidates (elastic re-entry resumes what the writer published)
        self._ckpt_barrier()
        d = self.getCheckpointDir()
        # fused fits (fit-side pipeline fusion) record the featurize plan
        # by digest: a candidate committed under a DIFFERENT plan trained
        # on different features — resuming its learner state would be
        # silent garbage, so it is skipped (absent digest = pre-fusion
        # checkpoint or staged fit: allowed)
        fplan = getattr(self, "_featurize_plan", None)
        fdig = fplan.digest() if fplan is not None else None
        manifest = (ckptlib.load_manifest(d) or {}) if d else {}

        def _plan_ok(f):
            rec = (manifest.get(f) or {}).get("featurize_digest")
            if rec is None or fdig is None or rec == fdig:
                return True
            log.warning("checkpoint %s was written under a different "
                        "featurize plan — skipping it as a resume "
                        "candidate", f)
            return False

        cands = [pos for pos, f in self._ckpt_candidates()
                 if ckptlib.verify(d, f) and _plan_ok(f)] if d else []
        placed = (params, opt_state)
        resume = restored = None
        for cand in cands:
            resume = self._consensus_resume(cand, nproc)
            if resume is None:
                break
            try:
                restored = self._restore_checkpoint(resume, params,
                                                    opt_state)
                break
            except (ckptlib.CorruptCheckpoint, OSError) as e:
                log.warning("restore of checkpoint %s failed (%s); "
                            "trying the previous checkpoint",
                            _fmt_pos(resume), e)
                resume = None
        if resume is None or restored is None:
            return params, opt_state, 0, 0, None, scale_state
        self._ckpt_floor = resume    # never pruned while this fit runs
        params, opt_state, scale_host = restored
        if scale_host is not None and scale_state is not None:
            from .precision import scale_state_from_host
            scale_state = scale_state_from_host(scale_host)
        if nproc > 1:
            # restored host arrays must go back onto the global mesh
            # shardings (replicated for dp, model/expert axes for tp/ep)
            params = _replace_like(params, placed[0])
            opt_state = _replace_like(opt_state, placed[1])
        else:
            # restored leaves are HOST numpy buffers. A donating dispatch
            # (the bf16_mixed feed/stream step donates (params, opt_state,
            # scale); the scan path donates (params, opt_state)) would
            # hand a zero-copy-aliased host buffer to XLA as scratch on
            # the CPU backend — the corruption class the arrow-fitstream
            # donation fix covered (see _make_train_step), surfacing as
            # nondeterministic NaN right after a resume. A jitted copy
            # materializes the restored state as XLA-owned output
            # buffers, donation-safe on every backend.
            params, opt_state = jax.jit(
                lambda t: jax.tree_util.tree_map(jnp.copy, t))(
                    (params, opt_state))
        epoch, step = resume
        if step is None:
            log.info("resumed from checkpoint epoch %d", epoch)
            return params, opt_state, epoch + 1, 0, resume, scale_state
        log.info("resumed from checkpoint epoch %d step %d", epoch, step)
        return params, opt_state, epoch, step + 1, resume, scale_state

    # ---- training ----
    def _cfg_with_precision(self, cfg: dict) -> dict:
        """Reflect the ``precision`` param into the model's compute
        dtype. The model families default to bf16 compute already
        (modules.py), so 'bf16' leaves the config untouched (bit-
        identical to every fit before the param existed); 'f32' and
        'bf16_mixed' pin the dtype explicitly — an explicit user
        ``dtype`` in the config always wins."""
        mode = self.getPrecision()
        if mode != "bf16" and "dtype" not in cfg:
            cfg["dtype"] = "float32" if mode == "f32" else "bfloat16"
        return cfg

    def _precision_setup(self):
        """(mixed, grad_clip, scale_state) for this fit."""
        mixed = self.getPrecision() == "bf16_mixed"
        if mixed:
            from .precision import init_scale_state
            scale_state = init_scale_state(self.getLossScaleInit())
        else:
            scale_state = None
        return mixed, self.getGradClipNorm(), scale_state

    def _slo_session(self):
        """Fit-scoped SLO evaluation (the ``sloConfig`` param): a private
        time-series sampler + SLOEngine run for the duration of the fit
        and the final per-objective verdicts land on
        ``self._last_slo_report``. Returns a context manager yielding the
        engine (or None when the param is unset)."""
        import contextlib

        @contextlib.contextmanager
        def session():
            cfg = self.getSloConfig()
            if not cfg:
                yield None
                return
            from ..telemetry.slo import SLOEngine
            from ..telemetry.timeseries import TimeSeriesSampler
            cfg = dict(cfg)
            if "objectives" not in cfg:
                # shorthand: a mean-step-time budget over the trainer's
                # step histogram
                budget = float(cfg.get("stepTimeBudget", 0) or 0)
                if budget <= 0:
                    raise ValueError(
                        "sloConfig needs an 'objectives' list or a "
                        "positive 'stepTimeBudget'")
                cfg = {"objectives": [{
                    "name": "fit-step-time", "kind": "step_time",
                    "hist": "mmlspark_trainer_step_seconds",
                    "budget_s": budget,
                    "windows": cfg.get("windows", [5.0, 30.0]),
                    "burn_threshold": cfg.get("burnThreshold", 1.0)}],
                    "interval": cfg.get("interval", 0.25)}
            interval = float(cfg.get("interval") or 0.25)
            sampler = TimeSeriesSampler(interval=interval)
            engine = SLOEngine.from_config(cfg, sampler=sampler)
            sampler.start(interval)   # also enables telemetry
            engine.start()
            try:
                yield engine
            finally:
                engine.stop()
                sampler.stop()
                sampler.tick()        # final sample + verdict pass
                final = engine.evaluate()
                breached = sorted(engine.breached_ever())
                self._last_slo_report = {"objectives": final,
                                         "breached": breached}
                if breached:
                    telemetry.flight.note("slo/fit_summary",
                                          breached=",".join(breached))
                    log.warning("fit SLO summary: objective(s) %s "
                                "breached their budget", breached)

        return session()

    def _elastic_coordinator(self):
        from ..resilience.elastic import ElasticFitCoordinator
        return ElasticFitCoordinator(
            self, n_hosts=self.getElasticHosts(),
            min_hosts=self.getElasticMinHosts(),
            grace=self.getElasticGraceSeconds() or None,
            max_failures=self.getElasticMaxFailures(),
            max_hosts=self.getElasticMaxHosts(),
            evict_after=self.getStragglerEvictAfter())

    # ---- fit-side pipeline fusion (core/capture.py) ----
    def _fit_captured(self, df: DataFrame, plan) -> Optional[TpuModel]:
        """The fused-fit hook ``Pipeline.fit(fusePipeline=True)`` calls:
        train with ``plan`` (a :class:`~..core.capture.FitCapturePlan`)
        folded into the per-step program, or return None to decline (the
        pipeline then falls back to the staged fit). Declines the model
        families whose input is not a featurized vector batch (token
        models) and the mesh axes the fused window does not thread
        (seq/expert/pipe)."""
        cfg = dict(self.getModelConfig() or {})
        if (cfg.get("type") in TOKEN_MODELS
                or self.getSequenceParallel() > 1
                or self.getExpertParallel() > 1
                or self.getPipelineParallel() > 1):
            return None
        self._featurize_plan = plan
        try:
            return self.fit(df)
        finally:
            self._featurize_plan = None

    def fitStreamCaptured(self, batches_fn, plan) -> TpuModel:
        """:meth:`fitStream` with a fit-side capture plan: every item
        ``batches_fn()`` yields is a tuple of RAW column arrays aligned
        with ``plan.in_names`` (wire dtypes; featurization runs inside
        the jitted step). Single-process only — the fused stream does
        not implement the multi-host signature lockstep."""
        if meshlib.effective_process_count() > 1:
            raise ValueError("fitStreamCaptured is single-process; "
                             "multi-host streams run staged fitStream")
        cfg = dict(self.getModelConfig() or {})
        if cfg.get("type") in TOKEN_MODELS:
            raise ValueError("fused stream fit needs a featurized-vector "
                             "model family, not a token model")
        self._featurize_plan = plan
        try:
            return self.fitStream(batches_fn)
        finally:
            self._featurize_plan = None

    def _featurize_fn(self, plan, cfg: dict):
        """The traced featurize adapter folded into the step program:
        ``plan.body`` plus the staged path's input conventions
        (f32 features, inputShape reshape to NHWC, loss-dtype labels) so
        fused and staged fits see identical (xb, yb)."""
        shape = tuple(self.getInputShape())
        loss_name = self.getLoss()

        def feat(fparams, raw_arrays):
            xb, yb = plan.body(fparams, raw_arrays)
            xb = xb.astype(jnp.float32)
            if xb.ndim == 1:
                xb = xb[:, None]
            if shape:
                c, h, w = shape
                xb = xb.reshape(-1, c, h, w).transpose(0, 2, 3, 1)
            yb = (yb.astype(jnp.int32) if loss_name == "cross_entropy"
                  else yb.astype(jnp.float32))
            return xb, yb

        return feat

    def _fused_program(self, kind: str, plan, factory, extra_key=()):
        """Cache of fused step/scan programs, keyed on everything that
        pins the traced structure (learner params + plan identity + the
        caller's shape/mesh key) and kept ON THE LEARNER: a kill-and-
        resume re-enters fit() on the same instance, and reusing the
        same :class:`~..telemetry.profiler.ProfiledFunction` (aot mode)
        is what makes "zero recompiles across a resume" an assertable
        metric — a rebuilt jit callable would recompile even for an
        identical trace."""
        cache = getattr(self, "_fused_programs", None)
        if cache is None:
            cache = self._fused_programs = {}
        key = (kind, plan.key(),
               repr(sorted(self._jsonParams().items())), tuple(extra_key))
        pf = cache.get(key)
        if pf is None:
            pf = telemetry.profiler.wrap(factory(), f"trainer.{kind}",
                                         aot=True)
            cache[key] = pf
        return pf

    def fit(self, df: DataFrame) -> TpuModel:
        with self._slo_session():
            if self.getElastic():
                return self._elastic_coordinator().fit(df)
            return self._fit_core(df)

    def _fit_core(self, df: DataFrame, devices=None,
                  elastic_ctx=None) -> TpuModel:
        """One fit attempt. ``devices`` restricts the mesh to a subset of
        the visible devices (the elastic coordinator passes the surviving
        hosts' pool after a re-mesh); ``elastic_ctx`` threads the per-step
        host-loss check and the committed-step/resume journal through the
        dispatch loop."""
        # fit/init: from here to where the epochs begin (a fit that fails
        # before then records none)
        init = contextlib.ExitStack()
        init.enter_context(telemetry.trace.span("fit/init", path="fit"))
        # rendezvous-armed fleets: snapshots go to the writer thread and
        # stalled writers are abandoned (see _save_checkpoint/_ckpt_barrier)
        self._elastic_multiproc = bool(
            elastic_ctx is not None
            and getattr(elastic_ctx._coord, "_multiproc", False))
        cfg = self._cfg_with_precision(dict(self.getModelConfig()))
        # fit-side pipeline fusion: when Pipeline.fit composed the
        # featurize prefix into a capture plan (_fit_captured), training
        # consumes RAW wire-dtype columns and featurization runs inside
        # the per-step program — the staged (x, y) materialization below
        # is skipped entirely
        plan = getattr(self, "_featurize_plan", None)
        raws = feat_fn = None
        if plan is not None:
            raws = plan.encode(df)
            if raws is None:
                from ..core import capture as capturelib
                capturelib._m_fit_fallbacks.inc()
                log.warning("fused fit fell back to staged featurization:"
                            " a raw input column is not device-encodable")
                df = plan.apply_staged(df)
                plan = None
            else:
                feat_fn = self._featurize_fn(plan, cfg)
        if plan is None:
            x = _prep_input(df, self.getFeaturesCol(),
                            tuple(self.getInputShape()))
            if cfg.get("type") in TOKEN_MODELS:
                x = x.astype(np.int32)
            y = np.asarray(df.col(self.getLabelCol()))
            y = (y.astype(np.int32) if self.getLoss() == "cross_entropy"
                 else y.astype(np.float32))
        else:
            x = y = None

        tp = self.getTensorParallel()
        sp = self.getSequenceParallel()
        ep = self.getExpertParallel()
        pp = self.getPipelineParallel()
        mixed, grad_clip, scale_state = self._precision_setup()
        if mixed and pp > 1:
            raise ValueError(
                "precision='bf16_mixed' composes with data/tensor/seq/"
                "expert parallelism; the pipeline step body does not "
                "thread the loss-scale state — run pipelineParallel fits "
                "with precision='bf16' or 'f32'")
        attn_fn = None
        if elastic_ctx is not None and (sp > 1 or ep > 1 or pp > 1):
            raise ValueError(
                "elastic fit composes with data(+tensor) parallelism only "
                "(a seq/expert/pipe axis cannot shrink mid-run); run "
                "sp/ep/pp fits without elastic")
        if sp > 1 and ep > 1:
            raise ValueError("sequenceParallel and expertParallel cannot both "
                             "exceed 1 (compose dp x sp or dp x ep meshes)")
        if pp > 1 and (sp > 1 or ep > 1 or tp > 1):
            raise ValueError("pipelineParallel currently composes with data "
                             "parallelism only (dp x pp mesh); run tp/sp/ep "
                             "without pp")
        if sp > 1:
            if cfg.get("type") != "transformer":
                raise ValueError("sequenceParallel>1 requires a transformer "
                                 f"model, got {cfg.get('type')!r}")
            n_dev = len(jax.devices())
            if n_dev % (sp * tp) != 0 or sp * tp > n_dev:
                raise ValueError(
                    f"sequenceParallel*tensorParallel = {sp}*{tp} must divide "
                    f"the device count ({n_dev})")
            if x.shape[1] % sp != 0:
                raise ValueError(
                    f"sequence length {x.shape[1]} must be divisible by "
                    f"sequenceParallel ({sp})")
            mesh = meshlib.make_mesh({"data": n_dev // (sp * tp),
                                      "seq": sp, "model": tp})
            attn_fn = sequence.make_sp_attention(
                mesh, axis_name="seq", mode=self.getSpMode(),
                causal=cfg.get("causal", False))
        elif ep > 1:
            if cfg.get("type") != "transformer" or not cfg.get("num_experts"):
                raise ValueError("expertParallel>1 requires a transformer "
                                 "model with num_experts set")
            if cfg["num_experts"] % ep != 0:
                raise ValueError(f"num_experts ({cfg['num_experts']}) must be "
                                 f"divisible by expertParallel ({ep})")
            n_dev = len(jax.devices())
            if n_dev % (ep * tp) != 0 or ep * tp > n_dev:
                raise ValueError(
                    f"expertParallel*tensorParallel = {ep}*{tp} must divide "
                    f"the device count ({n_dev})")
            mesh = meshlib.make_mesh({"data": n_dev // (ep * tp),
                                      "expert": ep, "model": tp})
        elif pp > 1:
            if cfg.get("type") != "transformer":
                raise ValueError("pipelineParallel>1 requires a transformer "
                                 f"model, got {cfg.get('type')!r}")
            if cfg.get("num_experts", 0) > 0:
                raise ValueError("pipelineParallel with MoE blocks is not "
                                 "supported (expert routing state does not "
                                 "pipeline); use expertParallel instead")
            if cfg.get("layers", 2) % pp != 0:
                raise ValueError(f"layers ({cfg.get('layers', 2)}) must be "
                                 f"divisible by pipelineParallel ({pp})")
            n_dev = len(jax.devices())
            if n_dev % pp != 0:
                raise ValueError(f"pipelineParallel ({pp}) must divide the "
                                 f"device count ({n_dev})")
            if meshlib.effective_process_count() > 1:
                _require_inner_block_local({"pipelineParallel": pp})
            mesh = meshlib.make_mesh({"data": n_dev // pp, "pipe": pp})
        else:
            mesh = meshlib.create_mesh(model=tp, devices=devices)
        module = build_model(cfg, attn_fn=attn_fn, mesh=mesh)
        rng = jax.random.PRNGKey(self.getSeed())
        # init batch must satisfy the shard_map divisibility of the sp
        # attention (batch % data-axis == 0); data-axis size always works
        init_b = dict(mesh.shape).get("data", 1) if sp > 1 else 2
        if plan is not None:
            # the featurized batch never exists on host: derive its
            # abstract shape through the traced featurize body and init
            # from zeros of that shape (flax initializers draw from rng
            # + shape only, so the params match a staged init exactly)
            xb_s, _ = jax.eval_shape(
                feat_fn, plan.params,
                tuple(jax.ShapeDtypeStruct((init_b,) + r.shape[1:],
                                           r.dtype) for r in raws))
            params = module.init(rng, jnp.zeros(xb_s.shape, xb_s.dtype))
        elif attn_fn is not None and meshlib.effective_process_count() > 1:
            # the sp attention is a shard_map over a process-spanning mesh —
            # flax's EAGER init cannot execute that collectively. The
            # attention callable holds no params (projections are separate
            # Dense modules), so a plain-attention twin inits the identical
            # tree; the shard_map module only ever runs inside the jitted
            # step, where global arrays make it legal.
            params = build_model(cfg).init(rng, jnp.asarray(x[:init_b]))
        else:
            params = module.init(rng, jnp.asarray(x[:init_b]))
        tx = make_optimizer(self.getOptimizer(), self.getLearningRate(),
                            self.getMomentum(), self.getWeightDecay())
        loss_fn = make_loss(self.getLoss(), per_example=True)

        # placement: params/opt replicated (TP rules shard wide dense kernels
        # over `model`; EP rules shard stacked expert weights over `expert`);
        # batch sharded over `data`. XLA derives the gradient all-reduce +
        # any TP/EP collectives from these shardings alone.
        nproc = meshlib.effective_process_count()
        if nproc > 1:
            # multi-host composes dp (across hosts) with the inner axes
            # (tp/sp/ep — across each host's chips). The inner-axis block
            # must be process-local: make_mesh puts `data` outermost, so
            # inner axes span contiguous device ranges — requiring the
            # block to divide the LOCAL device count keeps every seq/expert/
            # model collective on within-host ICI while only the dp
            # all-reduce crosses hosts, and keeps checkpointing and model
            # export reading process-locally-complete params (_host_tree).
            _require_inner_block_local({"sequenceParallel": sp,
                                        "expertParallel": ep,
                                        "tensorParallel": tp})
        params, opt_state = _place_params(params, mesh, tx, tp=tp, ep=ep)

        is_moe = has_experts(cfg)
        moe_aux = self.getMoeAuxWeight() if is_moe else 0.0

        # multi-host: this process's df is its LOCAL shard of the dataset
        # (the Spark-partition analog); batchSize stays the GLOBAL batch.
        # SPMD demands identical shapes and step counts everywhere, so both
        # are derived from GLOBAL quantities: every process contributes
        # exactly bs rows per step (short shards wrap around their rows).
        n = len(x) if plan is None else len(raws[0])
        if nproc > 1:
            from jax.experimental import multihost_utils
            n_global = int(multihost_utils.process_allgather(
                np.asarray(n)).sum())
        else:
            n_global = n
        bs_global = max(1, min(self.getBatchSize(), n_global))
        bs = max(1, bs_global // nproc)
        steps = max(1, n_global // (bs * nproc))

        pp_body = (None if pp <= 1 else
                   _make_pp_step_body(cfg, mesh, tx, loss_fn, n_micro=pp))
        train_step = None
        scan_fn = None
        data_cap = self.getDeviceDataCap() or _device_data_cap()
        if self.getProfile():
            telemetry.profiler.enable()
        # elastic fits stay on the per-step feed path: step-interval
        # checkpoints and the per-dispatch host-loss check both need the
        # host in the loop between steps (the scan path's whole-epoch
        # dispatch would turn a mid-epoch host loss into a lost epoch)
        data_bytes = (x.nbytes + y.nbytes if plan is None
                      else sum(r.nbytes for r in raws))
        mesh_key = tuple(sorted(dict(mesh.shape).items()))
        if nproc == 1 and elastic_ctx is None and data_bytes <= data_cap:
            if plan is not None:
                bs_pad = _scan_batch(bs_global, mesh, pp)
                scan_fn = self._fused_program(
                    "scan_epoch_fused", plan,
                    lambda: _make_scan_epoch_fn(
                        module, tx, loss_fn, is_moe, moe_aux, mesh,
                        bs_pad, step_body=pp_body, mixed=mixed,
                        grad_clip=grad_clip, featurize=feat_fn),
                    extra_key=(mesh_key, bs_pad))
            else:
                scan_fn = telemetry.profiler.wrap(_make_scan_epoch_fn(
                    module, tx, loss_fn, is_moe, moe_aux, mesh,
                    _scan_batch(bs_global, mesh, pp), step_body=pp_body,
                    mixed=mixed, grad_clip=grad_clip),
                    "trainer.scan_epoch")
        else:
            # multi-host (per-process shards feed put_global_batch) or a
            # dataset too big for HBM residency: per-step host feed
            if plan is not None:
                train_step = self._fused_program(
                    "step_fused", plan,
                    lambda: _make_train_step(
                        module, tx, loss_fn, is_moe, moe_aux,
                        step_body=pp_body, mixed=mixed,
                        grad_clip=grad_clip, featurize=feat_fn),
                    extra_key=(mesh_key,))
            else:
                train_step = telemetry.profiler.wrap(
                    _make_train_step(module, tx, loss_fn, is_moe,
                                     moe_aux, step_body=pp_body,
                                     mixed=mixed, grad_clip=grad_clip),
                    "trainer.step")
        # per-process batch orders only matter when processes feed distinct
        # dp shards; in local-fit mode (fleet tuner trials/refits) every
        # process must draw the IDENTICAL order or the replicated-model
        # guarantee breaks
        rng_np = np.random.default_rng(
            self.getSeed() + (0 if meshlib.in_local_fit()
                              else jax.process_index()))
        params, opt_state, start_epoch, start_step, resume_pos, \
            scale_state = self._resume_training_state(
                params, opt_state, nproc, scale_state)
        if elastic_ctx is not None:
            # bit-exact-resume evidence for the coordinator's journal: the
            # digest of the restored params (None on a fresh start)
            elastic_ctx.resumed(
                resume_pos,
                _params_digest(params) if resume_pos is not None else None)

        # concurrent fits from a thread pool (TuneHyperparameters) must not
        # interleave collective programs across the same devices — same
        # deadlock guard as the GBDT fit path (parallel/mesh.py)
        # elastic multi-process attempts run on abandonable threads; an
        # orphaned (pinned-in-dead-collective) attempt may still hold the
        # reentrant fit lock, and it can never issue a collective on the
        # NEW backend — skip the lock there, keep it everywhere else
        guard = (contextlib.nullcontext()
                 if getattr(self, "_elastic_multiproc", False)
                 else (meshlib.collective_fit_lock if mesh.size > 1
                       else contextlib.nullcontext()))
        # one fused featurize->train segment per fit (the fit-side twin
        # of the transform path's pipeline/segment span)
        seg_span = (telemetry.trace.span(
            "pipeline/fit_segment", stages=len(plan.pairs), rows=n,
            path="scan" if scan_fn is not None else "feed")
            if plan is not None else contextlib.nullcontext())
        init.close()
        try:
            with guard, telemetry.trace.span(
                    "fit", model=cfg.get("type"), rows=n,
                    path="scan" if scan_fn is not None else "feed"), \
                    seg_span:
                params, opt_state, last_loss = self._run_epochs(
                    start_epoch, x, y, n, bs, steps, order_rng=rng_np,
                    mesh=mesh, nproc=nproc, train_step=train_step,
                    params=params, opt_state=opt_state, scan_fn=scan_fn,
                    start_step=start_step, elastic_ctx=elastic_ctx,
                    scale_state=scale_state,
                    fused=(None if plan is None
                           else (raws, plan.device_params())))
        finally:
            # fit-exit barrier: an async checkpoint still in flight must
            # land before the caller (or an elastic re-entry) reads the
            # directory — and before a raised error looks "handled"
            self._ckpt_barrier()

        return self._package_model(cfg, params, last_loss)

    def _package_model(self, cfg, params, last_loss) -> TpuModel:
        model = (TpuModel()
                 .setInputCol(self.getFeaturesCol())
                 .setModelConfig(cfg)
                 .setModelParams(_host_tree(params))
                 .setInputShape(tuple(self.getInputShape())))
        model._final_loss = last_loss
        return model

    def fitStream(self, batches_fn) -> TpuModel:
        """Out-of-core training: ``batches_fn()`` returns a FRESH iterator
        of ``(features, labels)`` host numpy batches for every epoch — e.g.
        wrapping ``io.loader.image_batches`` over a file corpus, or any
        generator whose dataset doesn't fit host memory. The reference
        streams training data from files too (CNTKLearner writes CNTK text
        format, then CNTK reads it back; DataConversion.scala:89-132); here
        the stream feeds the jitted step directly, one device batch in
        flight.

        Data(+tensor)-parallel, single- or multi-host. Ragged generator
        batches bucket to powers of two (weight-masked), so batch-size
        drift never recompiles. Checkpoint/resume and divergence halt work
        as in fit().

        Multi-host: every process streams its OWN batches_fn() (its local
        shard of the corpus — the Spark-partition analog). SPMD needs
        identical dispatch shapes and counts everywhere, so each step the
        fleet agrees host-side on (any-stream-has-data, bucket size);
        exhausted streams contribute zero-weight dummy batches until the
        longest stream drains — unequal shard sizes never deadlock.

        ``elastic=True`` routes the stream fit through the same
        :class:`~..resilience.elastic.ElasticFitCoordinator` as fit():
        a host loss mid-stream re-meshes over the survivors and re-enters
        from the checkpointed optimizer state (the epoch restarts — a
        generator cannot seek — so some stream batches are re-seen).
        """
        with self._slo_session():
            if self.getElastic():
                return self._elastic_coordinator().fit_stream(batches_fn)
            return self._fit_stream_core(batches_fn)

    def _fit_stream_core(self, batches_fn, devices=None,
                         elastic_ctx=None) -> TpuModel:
        # fit/init: from here to where the step loop begins (a fit that
        # fails before then records none)
        init = contextlib.ExitStack()
        init.enter_context(telemetry.trace.span("fit/init", path="stream"))
        self._elastic_multiproc = bool(
            elastic_ctx is not None
            and getattr(elastic_ctx._coord, "_multiproc", False))
        cfg = self._cfg_with_precision(dict(self.getModelConfig()))
        if (self.getSequenceParallel() > 1 or self.getExpertParallel() > 1
                or self.getPipelineParallel() > 1):
            raise ValueError(
                "fitStream is data(+tensor)-parallel; use fit() for "
                "sequence/expert/pipeline parallelism")
        tp = self.getTensorParallel()
        nproc = meshlib.effective_process_count()
        if nproc > 1:
            _require_inner_block_local({"tensorParallel": tp})
        mesh = meshlib.create_mesh(model=tp, devices=devices)
        # fit-side pipeline fusion (fitStreamCaptured): stream batches ship
        # as RAW wire-dtype columns and featurize inside the step program
        plan = getattr(self, "_featurize_plan", None)
        raw0 = None
        first_iter = iter(batches_fn())
        first = next(first_iter, None)
        x0 = y0 = None
        if first is not None:
            if plan is not None:
                raw0 = self._stream_raw_batch(first, plan)
            else:
                x0, y0 = _stream_batch(first, cfg, self.getLoss())
        if nproc > 1:
            # a process whose shard is EMPTY from the start (no files at
            # all) must still join every collective: agree the batch
            # signature host-side so it can init identical params and feed
            # zero-weight dummies while the non-empty streams drain
            from ..parallel import dataplane
            sig = (None if first is None else
                   ((x0.shape[1:], x0.dtype.str), (y0.dtype.str,)))
            sigs = [s for s in dataplane.allgather_pyobj(sig)
                    if s is not None]
            if first is None and sigs:
                (xsh, xdt), (ydt,) = sigs[0]
                x0 = np.zeros((1,) + tuple(xsh), np.dtype(xdt))
                y0 = np.zeros((1,), np.dtype(ydt))
            if not sigs:
                raise ValueError("batches_fn() yielded no batches on any "
                                 "process")
        elif first is None:
            raise ValueError("batches_fn() yielded no batches")

        module = build_model(cfg, mesh=mesh)
        feat_fn = None
        if plan is not None:
            # init from the featurized batch SHAPE (eval_shape — nothing
            # runs): flax init draws from rng + shapes only, so this
            # matches the staged init on real featurized rows exactly
            feat_fn = self._featurize_fn(plan, cfg)
            xb_s, _ = jax.eval_shape(
                feat_fn, plan.params,
                tuple(jax.ShapeDtypeStruct((1,) + r.shape[1:], r.dtype)
                      for r in raw0))
            params = module.init(jax.random.PRNGKey(self.getSeed()),
                                 jnp.zeros(xb_s.shape, xb_s.dtype))
        else:
            params = module.init(jax.random.PRNGKey(self.getSeed()),
                                 jnp.asarray(x0[:1]))
        tx = make_optimizer(self.getOptimizer(), self.getLearningRate(),
                            self.getMomentum(), self.getWeightDecay())
        loss_fn = make_loss(self.getLoss(), per_example=True)
        is_moe = has_experts(cfg)
        if self.getProfile():
            telemetry.profiler.enable()
        mixed, grad_clip, scale_state = self._precision_setup()
        # the step's own counts (tokens routed, ...) are asked of the
        # program only in a fit that started with telemetry on
        step_stats = (telemetry.enabled() and plan is None and not mixed
                      and bool(getattr(module, "step_stat_names", ())))
        if plan is not None:
            # same program as the feed path's fused step — the instance
            # cache (zero recompiles across resume) is shared with it
            mesh_key = tuple(sorted(dict(mesh.shape).items()))
            train_step = self._fused_program(
                "step_fused", plan,
                lambda: _make_train_step(
                    module, tx, loss_fn, is_moe,
                    self.getMoeAuxWeight() if is_moe else 0.0,
                    mixed=mixed, grad_clip=grad_clip, featurize=feat_fn),
                extra_key=(mesh_key,))
        else:
            train_step = telemetry.profiler.wrap(_make_train_step(
                module, tx, loss_fn, is_moe,
                self.getMoeAuxWeight() if is_moe else 0.0, mixed=mixed,
                grad_clip=grad_clip, step_stats=step_stats), "trainer.step")
        params, opt_state = _place_params(params, mesh, tx, tp=tp)

        params, opt_state, start_epoch, start_step, resume_pos, \
            scale_state = self._resume_training_state(params, opt_state,
                                                      nproc, scale_state)
        if elastic_ctx is not None:
            elastic_ctx.resumed(
                resume_pos,
                _params_digest(params) if resume_pos is not None else None)
        if start_step:
            # a stream cannot skip deterministically to step N (the
            # generator is opaque); restart the epoch — the checkpointed
            # optimizer state is kept, some stream batches are re-seen
            log.warning("step checkpoint (epoch %d, step %d) resumes at "
                        "the epoch start on the stream path", start_epoch,
                        start_step - 1)

        from ..parallel import prefetch as prefetchlib
        axis = mesh.shape["data"]
        # elastic multi-process attempts run on abandonable threads; an
        # orphaned (pinned-in-dead-collective) attempt may still hold the
        # reentrant fit lock, and it can never issue a collective on the
        # NEW backend — skip the lock there, keep it everywhere else
        guard = (contextlib.nullcontext()
                 if getattr(self, "_elastic_multiproc", False)
                 else (meshlib.collective_fit_lock if mesh.size > 1
                       else contextlib.nullcontext()))
        last_loss = None
        skipped_seen = 0
        plan_dev = plan.device_params() if plan is not None else None
        seg_span = (telemetry.trace.span("pipeline/fit_segment",
                                         stages=len(plan.pairs),
                                         path="stream")
                    if plan is not None else contextlib.nullcontext())
        # steps dispatched in this fit, all epochs: the `step` that joins
        # one step's spans across the loop and the prefetch thread
        step_no = 0
        in_flight = _StepsInFlight() if telemetry.enabled() else None
        init.close()
        with guard, seg_span:
            for epoch in range(start_epoch, self.getEpochs()):
                it = first_iter if epoch == start_epoch and first is not None \
                    else iter(batches_fn())
                batches = ([first] if epoch == start_epoch else [])
                first = None  # only replayed once
                import itertools
                stream = itertools.chain(batches, it)
                # per-step row quota: the whole data axis single-host, this
                # process's slice of it multi-host
                share = max(1, axis // nproc)
                n_batches = 0
                steps_run = 0
                # single-process streams prefetch the normalize/bucket/pad/
                # upload work behind the device step; multi-host stays
                # synchronous — the per-step bucket-size allgather is a host
                # collective, and issuing it from a prefetch thread while
                # the main thread dispatches train steps could interleave
                # collective order differently across processes (deadlock)
                depth = self.getPrefetchDepth() if nproc == 1 else 0
                steps_it = prefetchlib.prefetched(
                    lambda s=stream: self._stream_epoch_steps(
                        s, cfg, x0, y0, share, nproc, mesh, plan=plan),
                    depth=depth, name="fit-stream", span="fit/prefetch",
                    first_item=step_no)
                ckpt_every = (self.getCheckpointEverySteps()
                              if self.getCheckpointDir() else 0)
                try:
                    while True:
                        with telemetry.trace.span("fit/step",
                                                  step=step_no) as step_sp:
                            with telemetry.trace.span("fit/feed_wait",
                                                      step=step_no) as sp:
                                item = next(steps_it, None)
                                if item is None:
                                    sp.discard()
                            if item is None:
                                step_sp.discard()   # no such step
                                break
                            n, xb, yb, wb = item
                            params, opt_state, scale_state, loss = \
                                _dispatch_step(
                                    train_step, params, opt_state,
                                    scale_state, xb, yb, wb, step=step_no,
                                    in_flight=in_flight, fused=plan_dev,
                                    elastic_ctx=elastic_ctx,
                                    step_stats=step_stats)
                            step_no += 1
                            steps_run += 1
                            if n:
                                n_batches += 1
                            if elastic_ctx is not None:
                                elastic_ctx.step_committed(epoch,
                                                           steps_run - 1)
                            if ckpt_every and steps_run % ckpt_every == 0 \
                                    and self._ckpt_should_write():
                                self._save_checkpoint(
                                    epoch, params, opt_state,
                                    step=steps_run - 1,
                                    scale_state=scale_state,
                                    elastic_ctx=elastic_ctx)
                finally:
                    steps_it.close()
                if steps_run == 0:
                    raise ValueError(f"batches_fn() yielded no batches in "
                                     f"epoch {epoch}")
                last_loss = float(loss)
                if in_flight is not None:
                    in_flight.clear()    # the epoch's last step is in
                from .precision import observe_scale_state
                skipped_seen = observe_scale_state(scale_state,
                                                   skipped_seen)
                # the enclosing `with guard:` is the fit-serialization
                # lock, held for the whole fit BY DESIGN (it serializes
                # collective fits); logging under it is inherent, not a
                # contention bug  # graftlint: disable=lock-blocking-call
                log.info("epoch %d loss %.4f (%d stream batches)",
                         epoch, last_loss, n_batches)
                if self.getHaltOnNonFinite() and not np.isfinite(last_loss):
                    raise RuntimeError(
                        f"training diverged: epoch {epoch} loss {last_loss} "
                        f"(lr={self.getLearningRate()})")
                if self.getCheckpointDir() and self._ckpt_should_write():
                    self._save_checkpoint(epoch, params, opt_state,
                                          scale_state=scale_state,
                                          elastic_ctx=elastic_ctx)

        self._ckpt_barrier()
        return self._package_model(cfg, params, last_loss)

    def _stream_raw_batch(self, b, plan):
        """A fitStreamCaptured batch as raw wire-dtype column arrays in
        ``plan.in_names`` order — either a DataFrame carrying those
        columns, or an already-aligned tuple/list of arrays."""
        from ..core.dataframe import DataFrame
        if isinstance(b, DataFrame):
            raws = plan.encode(b)
            if raws is None:
                raise ValueError(
                    "fitStreamCaptured batch is missing (or cannot encode) "
                    f"one of the captured input columns {plan.in_names}")
            return raws
        arrs = [np.asarray(a) for a in b]
        if len(arrs) != len(plan.in_names):
            raise ValueError(
                f"fitStreamCaptured batch has {len(arrs)} arrays; the "
                f"capture plan needs {len(plan.in_names)} "
                f"({plan.in_names})")
        return arrs

    def _stream_epoch_steps(self, stream, cfg, x0, y0, share, nproc, mesh,
                            plan=None):
        """One epoch of fitStream's per-step host work as a generator:
        normalize -> pow2 bucket -> (multi-host size lockstep) -> pad ->
        weight mask -> device placement. Yields ``(n_real, xb, yb, wb)``
        with the batch already placed, so the consuming loop (optionally a
        DevicePrefetcher running this ahead of the device step) only
        dispatches ``train_step``.

        With a fit-side capture ``plan`` (fitStreamCaptured,
        single-process only) the batch stays RAW: each wire-dtype column
        buckets/pads independently and ``xb`` is the placed column tuple
        (``yb`` None) — featurization happens inside the step program."""
        from ..core import capture as capturelib
        from .tpu_model import _next_pow2
        if nproc > 1:
            from jax.experimental import multihost_utils
        while plan is not None:
            b = next(stream, None)
            if b is None:
                return
            raws = self._stream_raw_batch(b, plan)
            n = len(raws[0])
            target = -(-max(_next_pow2(n), share) // share) * share
            if n < target:
                raws = [np.concatenate(
                    [r, np.zeros((target - n,) + r.shape[1:], r.dtype)])
                    for r in raws]
            wb = np.zeros(target, dtype=np.float32)
            wb[:n] = 1.0
            nbytes = int(sum(r.nbytes for r in raws))
            if telemetry.enabled():
                _note_step_signature("stream_fused", *raws, wb)
                _m_transfer_bytes.inc(nbytes + wb.nbytes)
            capturelib.count_fit_transfer("in", nbytes)
            yield (n,
                   tuple(meshlib.put_global_batch(r, mesh) for r in raws),
                   None,
                   meshlib.put_global_batch(wb, mesh))
        while True:
            b = next(stream, None)
            if b is None:
                xb = yb = None
                n = local_target = 0
            else:
                xb, yb = _stream_batch(b, cfg, self.getLoss())
                n = len(xb)
                # pow2 bucket, rounded up to a share multiple (a
                # 6-device axis doesn't divide pow2 buckets)
                local_target = (-(-max(_next_pow2(n), share)
                                  // share) * share)
            if nproc > 1:
                # host-side lockstep: the fleet agrees on the bucket
                # size each step; a drained stream reports 0 and
                # keeps feeding zero-weight dummies until the
                # longest stream finishes — no deadlock on unequal
                # shards
                target = int(multihost_utils.process_allgather(
                    np.asarray([local_target])).max())
            else:
                target = local_target
            if target == 0:
                return
            if xb is None:
                xb = np.zeros((target,) + x0.shape[1:], x0.dtype)
                yb = np.zeros(target, y0.dtype)
            elif n < target:
                fx = np.zeros((target - n,) + xb.shape[1:], xb.dtype)
                xb = np.concatenate([xb, fx])
                yb = np.concatenate(
                    [yb, np.zeros(target - n, yb.dtype)])
            wb = np.zeros(target, dtype=np.float32)
            wb[:n] = 1.0
            if telemetry.enabled():
                _note_step_signature("stream", xb, yb, wb)
                _m_transfer_bytes.inc(xb.nbytes + yb.nbytes + wb.nbytes)
            yield (n,
                   meshlib.put_global_batch(xb, mesh),
                   meshlib.put_global_batch(yb, mesh),
                   meshlib.put_global_batch(wb, mesh))

    def _run_epochs(self, start_epoch, x, y, n, bs, steps, *, order_rng,
                    mesh, nproc, train_step, params, opt_state,
                    scan_fn=None, start_step=0, elastic_ctx=None,
                    scale_state=None, fused=None):
        # ``fused`` = (raw host column arrays, device-put capture params)
        # when this fit runs a fit-side capture plan (x/y are None then):
        # batches ship as raw wire-dtype columns and the step program
        # featurizes them on device (_make_train_step featurize=)
        from ..core import capture as capturelib
        if scan_fn is not None:
            if start_step:
                # the scan path cannot enter an epoch mid-way (one dispatch
                # covers the whole window set); restart the epoch — params
                # already contain the checkpointed steps, so nothing is
                # lost, some rows are just seen again this epoch
                log.warning("step checkpoint (epoch %d, step %d) resumes "
                            "at the epoch start on the scan path",
                            start_epoch, start_step - 1)
            return self._run_epochs_scan(start_epoch, x, y, n, bs, steps,
                                         order_rng=order_rng, mesh=mesh,
                                         scan_fn=scan_fn, params=params,
                                         opt_state=opt_state,
                                         scale_state=scale_state,
                                         fused=fused)
        import time
        from ..parallel import prefetch as prefetchlib
        if steps <= 0:
            # an epoch with no steps would leave the loss unbound; there is
            # nothing to train on, so skip the epoch loop entirely
            log.warning("zero steps per epoch (n=%d, bs=%d) — skipping "
                        "training loop", n, bs)
            return params, opt_state, None
        micro = self.getPipelineParallel()
        pad = (meshlib.pad_batch_to_local_devices if nproc > 1
               else meshlib.pad_batch_to_devices)
        # the weight mask is identical for every (rows, n_real) signature —
        # on the feed path that is EVERY full batch — so build + upload it
        # once per signature and reuse the placed array instead of shipping
        # bs float32s again each step. Reuse is why _make_train_step does
        # not donate wb.
        wb_cache: dict = {}

        def placed_mask(rows: int, nb: int):
            wb = wb_cache.get((rows, nb))
            if wb is None:
                host = np.zeros(rows, dtype=np.float32)
                host[:nb] = 1.0
                if telemetry.enabled():
                    _m_transfer_bytes.inc(host.nbytes)
                wb = wb_cache[(rows, nb)] = meshlib.put_global_batch(
                    host, mesh)
            return wb

        # replay completed epochs' permutation draws so a resumed fit
        # replays the uninterrupted fit's data orders bit-for-bit
        if self.getShuffle():
            for _ in range(start_epoch):
                order_rng.permutation(n)

        def produce():
            """Per-step host work + H2D placement, run `prefetchDepth`
            steps ahead of the consuming loop on the prefetch thread
            (device placement is per-process work — no collectives — so
            producing from a thread is safe even multi-host)."""
            for epoch in range(start_epoch, self.getEpochs()):
                order = (order_rng.permutation(n) if self.getShuffle()
                         else np.arange(n))
                # a step-checkpoint resume re-enters its epoch at the next
                # step (fresh permutation — best-effort data order, exact
                # optimizer state)
                s0 = start_step if epoch == start_epoch else 0
                for s in range(s0, steps):
                    # cyclic slice: a process whose shard is shorter than
                    # its share of the global batch wraps (repeats) its rows
                    # so every process contributes exactly bs rows —
                    # identical shapes
                    idx = order[(s * bs + np.arange(bs)) % n]
                    if fused is not None:
                        # raw wire-dtype columns: smaller H2D than the
                        # f32-widened features the staged feed ships
                        cols, nb = [], 0
                        for r in fused[0]:
                            rb, nb = pad(r[idx], mesh)
                            cols.append(rb)
                        wb = placed_mask(len(cols[0]), nb)
                        nbytes = sum(c.nbytes for c in cols)
                        if telemetry.enabled():
                            _note_step_signature("feed_fused", *cols)
                            _m_transfer_bytes.inc(nbytes)
                        capturelib.count_fit_transfer("in", nbytes)
                        yield (epoch, s,
                               tuple(meshlib.put_global_batch(c, mesh)
                                     for c in cols), None, wb)
                        continue
                    xb, nb = pad(x[idx], mesh)
                    yb, _ = pad(y[idx], mesh)
                    if micro > 1:
                        # pipeline steps also need microbatch divisibility —
                        # per PROCESS: each feeds its 1/nproc slice of the
                        # global batch, so rounding local rows to the GLOBAL
                        # data*micro multiple would inflate the assembled
                        # batch nproc-fold (the dp axis size is
                        # nproc-divisible by the inner-block locality rule,
                        # so this is integral)
                        mult = (mesh.shape["data"] // nproc) * micro
                        tgt = -(-len(xb) // mult) * mult
                        xb = _wrap_rows(xb, tgt)
                        yb = _wrap_rows(yb, tgt)
                    wb = placed_mask(len(xb), nb)
                    if telemetry.enabled():
                        _note_step_signature("feed", xb, yb)
                        _m_transfer_bytes.inc(xb.nbytes + yb.nbytes)
                    yield (epoch, s,
                           meshlib.put_global_batch(xb, mesh),
                           meshlib.put_global_batch(yb, mesh), wb)

        last_loss = None
        skipped_seen = 0
        t_epoch = time.perf_counter()
        it = prefetchlib.prefetched(produce, depth=self.getPrefetchDepth(),
                                    name="fit-feed", span="fit/prefetch")
        # steps dispatched in this fit: the `step` that joins one step's
        # spans across the loop and the prefetch thread (`item`)
        step_no = 0
        in_flight = _StepsInFlight() if telemetry.enabled() else None
        try:
            ckpt_every = (self.getCheckpointEverySteps()
                          if self.getCheckpointDir() else 0)
            while True:
                with telemetry.trace.span("fit/step",
                                          step=step_no) as step_sp:
                    with telemetry.trace.span("fit/feed_wait",
                                              step=step_no) as sp:
                        item = next(it, None)
                        if item is None:
                            sp.discard()
                    if item is None:
                        step_sp.discard()   # no such step
                        break
                    epoch, s, xb, yb, wb = item
                    params, opt_state, scale_state, loss = _dispatch_step(
                        train_step, params, opt_state, scale_state, xb, yb,
                        wb, step=step_no, in_flight=in_flight,
                        fused=None if fused is None else fused[1],
                        elastic_ctx=elastic_ctx)
                    step_no += 1
                    if elastic_ctx is not None:
                        elastic_ctx.step_committed(epoch, s)
                    if s < steps - 1:
                        if ckpt_every and (s + 1) % ckpt_every == 0 \
                                and self._ckpt_should_write():
                            self._save_checkpoint(epoch, params, opt_state,
                                                  step=s,
                                                  scale_state=scale_state,
                                                  elastic_ctx=elastic_ctx)
                        continue
                # ---- epoch finalize (an early exit below must stop the
                # producer promptly: the finally closes the prefetcher) ----
                last_loss = float(loss)
                if in_flight is not None:
                    in_flight.clear()    # the epoch's last step is in
                _m_rows_per_sec.set(
                    steps * bs / max(time.perf_counter() - t_epoch, 1e-9))
                t_epoch = time.perf_counter()
                from .precision import observe_scale_state
                skipped_seen = observe_scale_state(scale_state,
                                                   skipped_seen)
                log.info("epoch %d loss %.4f", epoch, last_loss)
                if self.getHaltOnNonFinite() and not np.isfinite(last_loss):
                    last_good = self._latest_checkpoint() \
                        if self.getCheckpointDir() else None
                    raise RuntimeError(
                        f"training diverged: epoch {epoch} loss is "
                        f"{last_loss} (lr={self.getLearningRate()}). "
                        + (f"Last good checkpoint: {_fmt_pos(last_good)} "
                           f"in {self.getCheckpointDir()!r}; refit "
                           f"resumes there." if last_good is not None
                           else "Set checkpointDir to make divergence "
                                "resumable."))
                if self.getCheckpointDir() and self._ckpt_should_write():
                    self._save_checkpoint(epoch, params, opt_state,
                                          scale_state=scale_state,
                                          elastic_ctx=elastic_ctx)
        finally:
            it.close()
        return params, opt_state, last_loss

    def _run_epochs_scan(self, start_epoch, x, y, n, bs, steps, *,
                         order_rng, mesh, scan_fn, params, opt_state,
                         scale_state=None, fused=None):
        """Single-host fast path: the epoch data lives in HBM (padded to
        ``steps*bs_pad`` rows, pad rows weight 0) and every epoch is one
        XLA dispatch — a random rotation plus a random permutation of the
        contiguous bs-sized windows, scanned with donated state.

        ``fused`` (fit-side pipeline fusion) keeps the epoch resident as
        RAW wire-dtype columns instead of (x, y): every window
        featurizes inside the scan body, so the upload is the raw bytes
        and the featurized epoch never exists — not on host, not in
        HBM."""
        from ..core import capture as capturelib
        bs_pad = _scan_batch(bs, mesh, self.getPipelineParallel())
        # ceil instead of the feed path's floor: window tiling must cover
        # every row (the feed path re-slices a fresh permutation per step;
        # here rows outside the tiling would never be seen)
        steps = max(1, -(-n // bs_pad))
        n_pad = steps * bs_pad
        arrs = list(fused[0]) if fused is not None else None
        data_nbytes = (sum(int(a.nbytes) for a in arrs)
                       if fused is not None else x.nbytes + y.nbytes)
        # Windows slice the RESIDENT order, so it must be random: datasets
        # often arrive sorted by class, and class-pure batches wreck SGD.
        # Small datasets get a TRUE fresh permutation per epoch (re-upload
        # is cheaper than one train step at this size); big ones permute
        # once at upload and vary per epoch by rotation + window order.
        reshuffle = (self.getShuffle()
                     and data_nbytes <= (self.getEpochReshuffleCap()
                                         or _EPOCH_RESHUFFLE_CAP))
        if self.getShuffle() and not reshuffle:
            perm0 = order_rng.permutation(n)
            if fused is not None:
                arrs = [a[perm0] for a in arrs]
            else:
                x, y = x[perm0], y[perm0]
        # wrap-pad so windows tile exactly (wrapped rows carry weight 0 —
        # each real row counts once per epoch), plus a bs-row wrap margin
        # so rotated windows never wrap
        w_all = np.zeros(n_pad, dtype=np.float32)
        w_all[:n] = 1.0

        def margin(a):
            ap = _wrap_rows(a, n_pad)
            return np.concatenate([ap, ap[:bs_pad]], axis=0)

        def upload(*host_arrs):
            nbytes = int(sum(a.nbytes for a in host_arrs))
            if telemetry.enabled():
                _m_transfer_bytes.inc(nbytes)
            if fused is not None:
                capturelib.count_fit_transfer("in", nbytes)
            with telemetry.trace.span("fit/upload", bytes=nbytes):
                return tuple(meshlib.shard_batch(margin(a), mesh)
                             for a in host_arrs)
        data_dev = x_dev = y_dev = None
        if not reshuffle:
            if fused is not None:
                data_dev = upload(*arrs)
            else:
                x_dev, y_dev = upload(x, y)
        w_dev = meshlib.shard_batch(margin(w_all), mesh)
        kpd = self.getStepsPerDispatch() or steps
        base = np.arange(steps, dtype=np.int32) * bs_pad
        # replay the rng draws of already-completed epochs so a resumed
        # fit sees the SAME per-epoch orders the uninterrupted fit would
        # — kill-and-resume stays bit-exact even with shuffle on
        for _ in range(start_epoch):
            if reshuffle:
                order_rng.permutation(n)
            elif self.getShuffle():
                order_rng.permutation(steps)
                order_rng.integers(0, n_pad)
        last_loss = None
        skipped_seen = 0
        import time
        for epoch in range(start_epoch, self.getEpochs()):
            t_epoch = time.perf_counter()
            if reshuffle:
                perm = order_rng.permutation(n)
                if fused is not None:
                    data_dev = upload(*[a[perm] for a in arrs])
                else:
                    x_dev, y_dev = upload(x[perm], y[perm])
                starts = base
            elif self.getShuffle():
                starts = ((base[order_rng.permutation(steps)]
                           + order_rng.integers(0, n_pad)) % n_pad) \
                    .astype(np.int32)
            else:
                starts = base
            with telemetry.trace.span("fit/epoch", epoch=epoch,
                                      path="scan") as ep_sp:
                for lo in range(0, steps, kpd):
                    t_disp = time.perf_counter()
                    with telemetry.trace.span(
                            "fit/step", epoch=epoch, first_step=lo,
                            steps=min(kpd, steps - lo)) as sp:
                        def dispatch(_a, p=params, o=opt_state,
                                     ss=scale_state, lo=lo):
                            faults.inject("trainer.step")
                            if fused is not None:
                                if ss is None:
                                    p2, o2, loss = scan_fn(
                                        p, o, fused[1], data_dev, w_dev,
                                        starts[lo:lo + kpd])
                                    return p2, o2, None, loss
                                return scan_fn(p, o, ss, fused[1],
                                               data_dev, w_dev,
                                               starts[lo:lo + kpd])
                            if ss is None:
                                p2, o2, loss = scan_fn(
                                    p, o, x_dev, y_dev, w_dev,
                                    starts[lo:lo + kpd])
                                return p2, o2, None, loss
                            return scan_fn(p, o, ss, x_dev, y_dev, w_dev,
                                           starts[lo:lo + kpd])
                        params, opt_state, scale_state, loss = \
                            _STEP_RETRY.run(dispatch)
                        if fused is not None:
                            capturelib._m_fit_fused.inc(
                                min(kpd, steps - lo))
                        sp.set_sync(loss)
                    _m_step_time.observe(time.perf_counter() - t_disp)
                ep_sp.set_sync(loss)
            last_loss = float(loss)
            _m_rows_per_sec.set(steps * bs_pad
                                / max(time.perf_counter() - t_epoch, 1e-9))
            from .precision import observe_scale_state
            skipped_seen = observe_scale_state(scale_state, skipped_seen)
            log.info("epoch %d loss %.4f (%d-step dispatches)",
                     epoch, last_loss, min(kpd, steps))
            if self.getHaltOnNonFinite() and not np.isfinite(last_loss):
                last_good = self._latest_checkpoint() \
                    if self.getCheckpointDir() else None
                raise RuntimeError(
                    f"training diverged: epoch {epoch} loss is {last_loss} "
                    f"(lr={self.getLearningRate()}). "
                    + (f"Last good checkpoint: {_fmt_pos(last_good)} in "
                       f"{self.getCheckpointDir()!r}; refit resumes there."
                       if last_good is not None
                       else "Set checkpointDir to make divergence resumable."))
            if self.getCheckpointDir():
                # the scan dispatch donates (params, opt_state): the save
                # must snapshot inline before the next epoch's dispatch
                self._save_checkpoint(epoch, params, opt_state,
                                      scale_state=scale_state,
                                      state_donated=True)
        return params, opt_state, last_loss
