"""Gradient-boosted decision trees as pure XLA programs.

The LightGBM replacement (reference: src/lightgbm — LGBM_BoosterUpdateOneIter
loop at TrainUtils.scala:63-77, socket all-reduce ring at :141-142). The
reference ships rows into native C buffers and lets LightGBM's C++ build
255-bin histograms with a socket collective between workers. Here the whole
algorithm is data-parallel XLA:

  * features are quantile-binned once to uint8 bins (maxBin=255);
  * trees grow LEVEL-WISE to a fixed depth — every level is one batched
    histogram build (`segment_sum` over node*feature*bin ids, an MXU/VPU-
    friendly scatter-add) + a vectorized split-gain argmax. Static shapes,
    no per-node recursion: XLA sees a fixed program per level;
  * with the bin matrix sharded over the mesh's ``data`` axis the histogram
    sum becomes a cross-device all-reduce inserted by XLA — the moral
    equivalent of LightGBM's `tree_learner=data` ring, but over ICI;
  * multiclass trains K trees per iteration via vmap over class gradients.

Trees are stored heap-ordered in dense arrays (node i -> children 2i+1/2i+2),
so prediction is `depth` gathers — no pointer chasing, fully vectorized.
"""

from __future__ import annotations

import functools
import time
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ... import telemetry
from ...core.env import on_tpu

# boosting-loop telemetry (no-ops unless MMLSPARK_TPU_TELEMETRY=1). The
# hist+split work runs inside ONE jitted program per iteration, so the
# host-visible breakdown is grad / build / apply (+ the early-stop eval);
# spans carry block_until_ready sync points so enabled traces show real
# device time, not enqueue time.
_m_iters = telemetry.registry.counter(
    "mmlspark_gbdt_iterations", "boosting iterations dispatched")
_m_iter_time = telemetry.registry.histogram(
    "mmlspark_gbdt_iter_seconds",
    "wall time per boosting iteration (excl. early-stop eval)")
_m_eval_time = telemetry.registry.histogram(
    "mmlspark_gbdt_eval_seconds",
    "wall time per early-stopping validation eval")
_m_bin_time = telemetry.registry.histogram(
    "mmlspark_gbdt_bin_seconds", "feature binning wall time per fit")
_m_predict_table_bytes = telemetry.registry.gauge(
    "mmlspark_gbdt_predict_table_bytes",
    "estimated peak bytes of the per-chunk node-test table during the "
    "last ensemble predict")
_m_auto_depthwise = telemetry.registry.counter(
    "mmlspark_gbdt_auto_depthwise_reroutes",
    "fits the growthPolicy='auto' heuristic rerouted to depthwise growth")
_m_predict_bytes_per_row = telemetry.registry.gauge(
    "mmlspark_gbdt_predict_bytes_per_row",
    "estimated device-traffic bytes per scored row of the last ensemble "
    "predict (uint8 bin row + staged node tests + amortized tree "
    "tables); the quantized pallas path drops the test-table term and "
    "shrinks the tables to uint8/bf16")


class GBDTParams(NamedTuple):
    num_iterations: int = 100
    learning_rate: float = 0.1
    max_depth: int = 5              # numLeaves ~ 2^max_depth (level-wise)
    max_bin: int = 255
    lambda_l2: float = 1.0
    lambda_l1: float = 0.0
    min_child_weight: float = 1e-3
    min_split_gain: float = 0.0
    bagging_fraction: float = 1.0
    bagging_freq: int = 0
    feature_fraction: float = 1.0
    objective: str = "binary"       # binary|regression|quantile|mae|multiclass
    alpha: float = 0.9              # quantile level
    num_class: int = 1
    seed: int = 0
    early_stopping_round: int = 0
    boosting_type: str = "gbdt"     # gbdt | rf (bagged trees, LightGBM rf mode)
    hist_impl: str = "auto"   # auto | mxu | compare | segment | pallas
                              # (auto = mxu kernel on TPU, compare hybrid off)
    # LightGBM tree_learner (TrainParams.scala `parallelism`):
    #   data    — rows sharded, per-device histograms psum'ed over ICI
    #             (shard_map; the socket-allreduce ring of TrainUtils.scala:141)
    #   feature — full rows everywhere, histogram WORK split by feature,
    #             split candidates all_gather'ed (LightGBM feature-parallel
    #             keeps the full dataset on every worker too)
    #   auto    — shard rows and let XLA's auto-SPMD place the collectives
    #   serial  — single-device program even if a mesh is passed
    tree_learner: str = "data"      # data | feature | auto | serial
    # LEAF-WISE growth (LightGBM's native policy, numLeaves default 31 at
    # LightGBMParams.scala:34): num_leaves > 0 grows best-first via
    # leafwise.grow_tree_leafwise; 0 keeps the level-wise engine above.
    # max_depth still caps leaf depth when > 0 in leaf-wise mode.
    num_leaves: int = 0
    # feature ids treated as categorical (bins = category ids; splits are
    # category SETS found by sorted-ratio prefix scan). Leaf-wise only.
    categorical_feature: tuple = ()
    cat_smooth: float = 10.0        # LightGBM cat_smooth default


class TreeEnsemble(NamedTuple):
    """All trees of a fitted booster, dense heap layout.

    feature:  (T, K, 2^depth-1) int32 — split feature per internal node
    threshold:(T, K, 2^depth-1) int32 — split bin (go right if bin > thr)
    leaf:     (T, K, 2^depth)   f32   — leaf values (learning rate applied)
    bin_edges:(d, max_bin-1)    f32   — quantile edges for binning new data
    base:     (K,)              f32   — initial raw score
    objective: str
    """
    feature: jnp.ndarray
    threshold: jnp.ndarray
    leaf: jnp.ndarray
    bin_edges: np.ndarray
    base: np.ndarray
    objective: str


# ------------------------------------------------------------------ binning

def compute_bin_edges(x: np.ndarray, max_bin: int,
                      sample_cap: int = 200_000, seed: int = 0) -> np.ndarray:
    """Per-feature quantile edges, shape (d, max_bin-1). NaNs ignored.

    Edges come from a seeded row sample above ``sample_cap`` rows — the same
    trade LightGBM makes (bin_construct_sample_cnt=200k): quantiles of a 200k
    sample are statistically indistinguishable for 255 bins, and the exact
    nanquantile over tens of millions of rows would dominate fit time."""
    if x.shape[0] > sample_cap:
        idx = np.random.default_rng(seed).choice(x.shape[0], sample_cap,
                                                 replace=False)
        x = x[idx]
    qs = np.linspace(0, 1, max_bin + 1)[1:-1]
    edges = np.nanquantile(x.astype(np.float64), qs, axis=0).T  # (d, B-1)
    # strictly increasing edges are unnecessary; searchsorted handles ties
    return np.ascontiguousarray(edges.astype(np.float32))


def bin_data(x: np.ndarray, edges: np.ndarray,
             cat_features: Optional[np.ndarray] = None,
             max_bin: int = 256) -> np.ndarray:
    """(n, d) floats -> (n, d) uint8 bin ids in [0, max_bin). NaN -> bin 0.

    Categorical columns (``cat_features`` (d,) bool) bin by IDENTITY —
    the category code IS the bin (clipped to the bin range), so category-set
    splits see the original categories, not quantile buckets.

    uint8 is the wire format (ids top out at max_bin-1 <= 255; fit_gbdt
    enforces max_bin <= 256): the bin matrix is the one large host->HBM
    transfer the fit makes, and shipping bytes moves 4x less than int32 —
    kernels upcast on device.

    Large matrices route through the native C++ kernel (one row-major
    pass, branchless lower_bound, threaded over rows — 5.9x the numpy
    column loop single-core at 10M x 28 and scales with cores; see
    native/csrc/gbdt.cc), falling back to the numpy loop wherever the
    native runtime is unavailable."""
    n, d = x.shape
    if n * d >= 1_000_000:
        from ...native import bin_data_native
        nat = bin_data_native(x, edges,
                              cat_features if cat_features is not None
                              and np.asarray(cat_features).any() else None,
                              max_bin)
        if nat is not None:
            return nat
    out = np.empty((n, d), dtype=np.uint8)
    xf = x.astype(np.float32)
    for j in range(d):
        if cat_features is not None and cat_features[j]:
            with np.errstate(invalid="ignore"):
                out[:, j] = np.clip(np.nan_to_num(xf[:, j]), 0,
                                    max_bin - 1).astype(np.uint8)
        else:
            out[:, j] = np.searchsorted(edges[j], xf[:, j], side="left")
    out[np.isnan(xf)] = 0
    return out


#: rows per device binning slab — one compiled shape, ~112 MB f32 at d=28
_BIN_SLAB = 1 << 20


@functools.partial(jax.jit, static_argnames=("max_bin", "n_edges"))
def _bin_slab_device(xs, edges_t, cat_mask, *, max_bin: int, n_edges: int):
    """(m, d) f32 -> (m, d) uint8 on device. Vectorized lower-bound binary
    search over each feature's edges (8 gather/compare rounds for 255
    edges) — O(m*d) live memory, never the (m, d, bins) broadcast; exact
    searchsorted(side='left') semantics including ties and NaN->0."""
    lo = jnp.zeros(xs.shape, jnp.int32)
    hi = jnp.full(xs.shape, n_edges, jnp.int32)
    for _ in range(max(1, int(np.ceil(np.log2(n_edges + 1))))):
        active = lo < hi           # converged lanes must not move again
        mid = (lo + hi) // 2
        emid = jnp.take_along_axis(
            edges_t, jnp.clip(mid, 0, n_edges - 1), axis=0)
        right = (emid < xs) & active   # edge < x -> answer right of mid
        lo = jnp.where(right, mid + 1, lo)
        hi = jnp.where(active & ~right, mid, hi)
    out = lo.astype(jnp.uint8)
    catv = jnp.clip(jnp.nan_to_num(xs), 0, max_bin - 1).astype(jnp.uint8)
    out = jnp.where(cat_mask[None, :], catv, out)
    return jnp.where(jnp.isnan(xs), jnp.uint8(0), out)


def bin_data_device(x: np.ndarray, edges: np.ndarray,
                    cat_features: Optional[np.ndarray] = None,
                    max_bin: int = 256,
                    slab: int = _BIN_SLAB) -> np.ndarray:
    """``bin_data`` computed ON DEVICE in fixed-shape slabs: the host loop
    is a fixed cost of every large fit while the edges are tiny and the
    rows stream to HBM anyway. A 2-deep pending
    window lets JAX async dispatch overlap slab upload with compute; the
    result returns as the uint8 wire matrix."""
    n, d = x.shape
    edges_t = jnp.asarray(np.ascontiguousarray(edges.T))
    cat = jnp.asarray(cat_features if cat_features is not None
                      else np.zeros(d, bool))
    n_edges = int(edges.shape[1])
    out = np.empty((n, d), dtype=np.uint8)
    pending: list = []

    def drain(entry):
        start, m, yd = entry
        out[start:start + m] = np.asarray(yd)[:m]

    for start in range(0, n, slab):
        xs = np.ascontiguousarray(x[start:start + slab], dtype=np.float32)
        m = len(xs)
        # pad EVERY partial slab to a power-of-two bucket (capped at the
        # slab) so varying row counts reuse a handful of compiled shapes
        # instead of paying an XLA compile per distinct tail
        target = min(1 << max(0, int(np.ceil(np.log2(max(m, 1))))), slab)
        if m < target:
            xs = np.concatenate(
                [xs, np.zeros((target - m, d), np.float32)])
        yd = _bin_slab_device(jnp.asarray(xs), edges_t, cat,
                              max_bin=max_bin, n_edges=n_edges)
        pending.append((start, m, yd))
        if len(pending) > 2:
            drain(pending.pop(0))
    for entry in pending:
        drain(entry)
    return out


def _host_bin_ns() -> float:
    """Measured single-core cost of the host path that will ACTUALLY run:
    ~30 ns/elem through the native C++ kernel (10M x 28 in 8.0 s), ~77+
    through the numpy fallback. The device trial must beat this to win."""
    from ...native import available
    return 30.0 if available() else 77.0

#: cached auto-binning verdicts keyed by feature width (the host/device
#: crossover depends on d and link state, so one wide dataset's timing must
#: not pin the backend for every later narrow one; {} = unmeasured)
_device_bin_verdict: dict = {}

#: only consider the device binner for datasets at least this large in
#: f32 bytes. Two reasons: below it the host loop is fast anyway, and a
#: trustworthy bandwidth measurement needs a transfer large enough to see
#: the link's SUSTAINED rate — small transfers ride burst buffering and
#: flatter the device path.
_DEVICE_BIN_MIN_BYTES = 96 << 20


def bin_data_auto(x: np.ndarray, edges: np.ndarray,
                  cat_features: Optional[np.ndarray] = None,
                  max_bin: int = 256) -> np.ndarray:
    """Pick the binning backend by MEASURED cost: run the first device
    slab and time it end-to-end (upload + compute + uint8 readback); if
    it beats the host path's measured per-element cost, the remaining
    slabs stay on device, otherwise they run on host. Device binning
    uploads f32 — 4x the uint8 wire — so the winner depends on the
    host->device link, and a synthetic bandwidth probe mispredicts links
    that buffer small transfers, so the decision times the real workload
    (its result is kept either way). MMLTPU_GBDT_BINNING=host|device
    overrides. A device error is the fit's error: it is not retried on
    the host."""
    import os
    import time
    mode = os.environ.get("MMLTPU_GBDT_BINNING", "auto")
    if mode not in ("auto", "host", "device"):
        raise ValueError(f"MMLTPU_GBDT_BINNING must be auto|host|device, "
                         f"got {mode!r}")
    n, d = x.shape
    if mode == "host" or (mode == "auto"
                          and n * d * 4 < _DEVICE_BIN_MIN_BYTES):
        return bin_data(x, edges, cat_features, max_bin)
    if mode == "device":
        return bin_data_device(x, edges, cat_features, max_bin)
    if d in _device_bin_verdict:
        if _device_bin_verdict[d]:
            return bin_data_device(x, edges, cat_features, max_bin)
        return bin_data(x, edges, cat_features, max_bin)

    def timed_slab(lo_i, hi_i):
        t0 = time.perf_counter()
        part = bin_data_device(x[lo_i:hi_i], edges, cat_features,
                               max_bin)   # np.asarray inside = real sync
        ns = (time.perf_counter() - t0) * 1e9 / ((hi_i - lo_i) * d)
        return part, ns

    # the trial is sized in BYTES, not rows: it must be large enough to
    # see SUSTAINED bandwidth, whatever the feature width. The 96 MB
    # dataset gate guarantees a >= 64 MB trial always fits.
    trial = min(n, -(-(64 << 20) // (4 * d)))
    head, dev_ns = timed_slab(0, trial)
    pieces = [head]
    done = trial
    host_ns = _host_bin_ns()
    if dev_ns > host_ns and (n - done) * d * 4 >= 32 << 20:
        # the first call may be compile-tainted; re-measure WARM on a
        # still-sustained-scale chunk before caching a loss (a DMA
        # host must not get pinned to the host loop by one compile).
        # When the remainder is too small to re-measure honestly the
        # loss is cached as-is — the persistent compile cache makes
        # compile taint a first-process-ever event, and
        # MMLTPU_GBDT_BINNING=device overrides a wrong pin
        second = min(done + trial, n)
        part, dev_ns = timed_slab(done, second)
        pieces.append(part)
        done = second
    _device_bin_verdict[d] = dev_ns <= host_ns
    if done < n:
        if dev_ns <= host_ns:
            pieces.append(bin_data_device(x[done:], edges,
                                          cat_features, max_bin))
        else:
            pieces.append(bin_data(x[done:], edges, cat_features,
                                   max_bin))
    return (pieces[0] if len(pieces) == 1
            else np.concatenate(pieces, axis=0))


# ------------------------------------------------------------- tree builder

def _histograms(bins, g, h, node, n_nodes: int, n_bins: int,
                hist_impl: str, bins_t=None):
    """(node, feature, bin) grad/hess histograms, several implementations:

    * ``mxu`` (round 5, the TPU default): ops.pallas_kernels.
      mxu_node_histogram — per-feature bin one-hots contracted on the MXU
      with the node axis folded into the grad operand, so cost never
      scales with the node count and is linear in rows. 14.6 ms per
      1M x 28 x 16-node build vs segment_sum's 384 ms (v5e, synced).
      ``bins_t`` (d, n) — the transposed bin matrix — is used when the
      caller precomputed it (the leaf-wise grower hoists it out of its
      scan); otherwise it is derived here (XLA CSEs the transpose across
      the levels of one tree build).
    * ``segment``: one flat segment_sum over combined ids — XLA
      scatter-add (the portable path);
    * ``compare``: scatter-free compare-reduce for uint8 id spaces;
    * ``pallas``: the v1 one-hot matmul kernel, kept for A/B.
    """
    n, d = bins.shape
    from ...ops.pallas_kernels import (compare_reduce_histogram,
                                       histogram_fused, mxu_node_histogram,
                                       segment_histogram)

    # deep levels (n_nodes > 64, i.e. level-wise depth > 7) fall back to
    # segment_sum PER LEVEL: past that the kernel's VMEM budget shrinks
    # its row blocks enough that the scatter is competitive, and the
    # shallow levels — where nearly all the time goes — still ride the MXU
    if hist_impl == "mxu" and n_nodes <= 64:
        if bins_t is None:
            bins_t = bins.T.astype(jnp.int32)
        return mxu_node_histogram(bins_t, node, g, h, n_nodes=n_nodes,
                                  n_bins=n_bins)

    # fold the node id into the bin id: ONE pass per level builds all nodes'
    # histograms as (d, n_nodes*n_bins) columns (a per-node vmap would
    # re-scan all rows 2^level times)
    comb = node[:, None] * n_bins + bins
    if hist_impl == "pallas":
        build = histogram_fused
    elif hist_impl == "compare" and n_nodes * n_bins <= 256:
        # uint8-id space (single-node builds — the root level of every
        # iteration): the scatter-free compare-reduce wins 4x on TPU;
        # wider id spaces force int32 keys and lose (pallas_kernels
        # docstring has the measured crossover). An explicit "segment"
        # never routes here, so pure segment_sum stays selectable
        build = compare_reduce_histogram
    else:
        build = segment_histogram
    hg, hh = build(comb, g, h, n_bins=n_nodes * n_bins)
    return (hg.reshape(d, n_nodes, n_bins).transpose(1, 0, 2),
            hh.reshape(d, n_nodes, n_bins).transpose(1, 0, 2))


def _best_splits(hg, hh, feat_mask, n_bins: int, lambda_l2, lambda_l1,
                 min_child_weight):
    """Vectorized split-gain argmax over (node, feature, bin) histograms.

    hg/hh (n_nodes, d, n_bins); feat_mask (d,).
    Returns (best_gain (n_nodes,), best_feat (n_nodes,), best_bin (n_nodes,)).
    """
    n_nodes, d, _ = hg.shape
    gl = jnp.cumsum(hg, axis=2)
    hl = jnp.cumsum(hh, axis=2)
    gt = gl[:, :, -1:]
    ht = hl[:, :, -1:]
    gr = gt - gl
    hr = ht - hl

    def score(gsum, hsum):
        # L1/L2-regularized leaf objective: (|g|-l1)^2 soft-thresholded
        gs = jnp.sign(gsum) * jnp.maximum(jnp.abs(gsum) - lambda_l1, 0.0)
        return gs * gs / (hsum + lambda_l2)

    gain = score(gl, hl) + score(gr, hr) - score(gt, ht)
    valid = ((hl >= min_child_weight) & (hr >= min_child_weight)
             & (feat_mask[None, :, None] > 0))
    gain = jnp.where(valid, gain, -jnp.inf)
    flat = gain.reshape(n_nodes, d * n_bins)
    best = jnp.argmax(flat, axis=1)
    best_gain = jnp.take_along_axis(flat, best[:, None], axis=1)[:, 0]
    bf = (best // n_bins).astype(jnp.int32)
    bb = (best % n_bins).astype(jnp.int32)
    return best_gain, bf, bb


def _grow_tree(bins, g, h, depth: int, n_bins: int, candidate_fn,
               lambda_l2, lambda_l1, min_split_gain,
               leaf_axis_name: Optional[str] = None,
               hist_impl: str = "segment"):
    """Shared level-wise scaffolding for every tree_learner mode.

    `bins` (n, d) is whatever each device routes its rows with (full
    features); `candidate_fn(g, h, node, n_nodes) -> (best_gain, bf, bb)`
    supplies per-node split candidates (this is where each mode's histogram
    build + collective lives). Leaf grad/hess sums are psum'ed over
    `leaf_axis_name` when rows are sharded.
    Returns (feature (2^depth-1,), threshold (2^depth-1,), leaf (2^depth,),
    node (n,) — each training row's final leaf, so the boosting loop's raw
    update is a table gather instead of replaying the tree's gathers over
    the training set every iteration; round 4 re-predicted here at ~30 ms
    per level per 1M rows).
    """
    n = bins.shape[0]
    node = jnp.zeros(n, dtype=jnp.int32)
    feat_arr = jnp.zeros(2 ** depth - 1, dtype=jnp.int32)
    thr_arr = jnp.full(2 ** depth - 1, n_bins, dtype=jnp.int32)  # default: all left

    for level in range(depth):
        n_nodes = 2 ** level
        best_gain, bf, bb = candidate_fn(g, h, node, n_nodes)
        # nodes with no usable split: route everything left (thr = n_bins)
        use = best_gain > min_split_gain
        bf = jnp.where(use, bf, 0)
        bb = jnp.where(use, bb, n_bins)

        off = 2 ** level - 1
        feat_arr = jax.lax.dynamic_update_slice(feat_arr, bf, (off,))
        thr_arr = jax.lax.dynamic_update_slice(thr_arr, bb, (off,))

        # --- route rows (local: every device routes its own row shard) ---
        nf = bf[node]
        nt = bb[node]
        go_right = bins[jnp.arange(n), nf] > nt
        node = node * 2 + go_right.astype(jnp.int32)

    # --- leaves (scatter-free reduction; see ops.pallas_kernels.node_sums;
    # hist_impl="segment" keeps the segment_sum order for bit-reproduction)
    from ...ops.pallas_kernels import node_sums
    lg, lh = node_sums(node, g, h, 2 ** depth, impl=hist_impl)
    if leaf_axis_name is not None:
        lg = jax.lax.psum(lg, leaf_axis_name)
        lh = jax.lax.psum(lh, leaf_axis_name)
    lgs = jnp.sign(lg) * jnp.maximum(jnp.abs(lg) - lambda_l1, 0.0)
    leaf = -lgs / (lh + lambda_l2)
    return feat_arr, thr_arr, leaf, node


def _build_tree_impl(bins, grad, hess, row_mask, feat_mask, depth: int,
                     n_bins: int, lambda_l2, lambda_l1, min_child_weight,
                     min_split_gain, hist_impl: str = "segment",
                     axis_name: Optional[str] = None):
    """One level-wise tree for one output class.

    bins (n, d) int32; grad/hess (n,) f32; row_mask (n,) f32 bagging mask;
    feat_mask (d,) f32 feature-fraction mask.
    With `axis_name` (inside shard_map, rows sharded over that mesh axis) the
    per-device histograms and leaf sums are `psum`'ed over ICI — LightGBM's
    `tree_learner=data` allreduce ring (TrainUtils.scala:141) as one XLA
    collective; split selection then runs replicated on every device.
    """
    g = grad * row_mask
    h = hess * row_mask

    def candidates(g, h, node, n_nodes):
        hg, hh = _histograms(bins, g, h, node, n_nodes, n_bins, hist_impl)
        if axis_name is not None:
            hg = jax.lax.psum(hg, axis_name)
            hh = jax.lax.psum(hh, axis_name)
        return _best_splits(hg, hh, feat_mask, n_bins, lambda_l2, lambda_l1,
                            min_child_weight)

    return _grow_tree(bins, g, h, depth, n_bins, candidates, lambda_l2,
                      lambda_l1, min_split_gain, leaf_axis_name=axis_name,
                      hist_impl=hist_impl)


def _build_tree_fp(bins, grad, hess, row_mask, feat_mask, *, depth: int,
                   n_bins: int, d_local: int, axis_name: str,
                   lambda_l2, lambda_l1, min_child_weight, min_split_gain,
                   hist_impl: str = "segment"):
    """Feature-parallel tree build (LightGBM `tree_learner=feature`).

    Every device holds the FULL row set (as in LightGBM, whose feature-
    parallel workers each keep the whole dataset) but builds histograms only
    for its own feature slice; per-node best splits are `all_gather`'ed and
    the winner picked identically everywhere, so only (gain, feat, bin)
    triples — not histograms — cross ICI. Row routing is local since every
    device has all features.

    bins (n, d_pad) replicated; feat_mask (d_pad,) with padding zeroed.
    """
    idx = jax.lax.axis_index(axis_name)
    f_off = idx * d_local
    lbins = jax.lax.dynamic_slice_in_dim(bins, f_off, d_local, axis=1)
    lfm = jax.lax.dynamic_slice_in_dim(feat_mask, f_off, d_local, axis=0)
    g = grad * row_mask
    h = hess * row_mask

    def candidates(g, h, node, n_nodes):
        hg, hh = _histograms(lbins, g, h, node, n_nodes, n_bins, hist_impl)
        lgain, lbf, lbb = _best_splits(hg, hh, lfm, n_bins, lambda_l2,
                                       lambda_l1, min_child_weight)
        lbf = lbf + f_off  # local slice index -> global feature id
        # --- tiny collective: (n_dev, n_nodes) candidate table everywhere ---
        cg = jax.lax.all_gather(lgain, axis_name)
        cf = jax.lax.all_gather(lbf, axis_name)
        cb = jax.lax.all_gather(lbb, axis_name)
        win = jnp.argmax(cg, axis=0)  # ties -> lowest device id: deterministic
        best_gain = jnp.take_along_axis(cg, win[None, :], axis=0)[0]
        bf = jnp.take_along_axis(cf, win[None, :], axis=0)[0]
        bb = jnp.take_along_axis(cb, win[None, :], axis=0)[0]
        return best_gain, bf, bb

    # leaves need no psum: full rows + replicated routing on every device
    return _grow_tree(bins, g, h, depth, n_bins, candidates, lambda_l2,
                      lambda_l1, min_split_gain, hist_impl=hist_impl)


def make_sharded_builder(mesh, tree_learner: str, *, depth: int, n_bins: int,
                         d_pad: int = 0, lambda_l2=1.0, lambda_l1=0.0,
                         min_child_weight=1e-3, min_split_gain=0.0,
                         hist_impl: str = "segment", axis_name: str = "data"):
    """jit(shard_map) tree builder with explicit ICI collectives.

    tree_learner="data": rows sharded over `axis_name`, histograms psum'ed.
    tree_learner="feature": inputs replicated, histogram work split by
    feature slice, split candidates all_gather'ed.
    Signature of the returned fn matches `_build_tree_multi`:
    (bins, grad (n,K), hess, row_mask, feat_mask) -> (f, t, leaf, node)
    stacked over the class axis.
    """
    from jax.sharding import PartitionSpec as P

    if tree_learner == "data":
        def body(bins, g, h, rm, fm):
            return _stack_class_axis([
                _build_tree_impl(bins, g[:, k], h[:, k], rm, fm, depth,
                                 n_bins, lambda_l2, lambda_l1,
                                 min_child_weight, min_split_gain,
                                 hist_impl, axis_name=axis_name)
                for k in range(g.shape[1])])
        in_specs = (P(axis_name, None), P(axis_name, None), P(axis_name, None),
                    P(axis_name), P(None))
    elif tree_learner == "feature":
        n_dev = mesh.shape[axis_name]
        assert d_pad % n_dev == 0, (d_pad, n_dev)
        d_local = d_pad // n_dev

        def body(bins, g, h, rm, fm):
            return _stack_class_axis([
                _build_tree_fp(bins, g[:, k], h[:, k], rm, fm, depth=depth,
                               n_bins=n_bins, d_local=d_local,
                               axis_name=axis_name, lambda_l2=lambda_l2,
                               lambda_l1=lambda_l1,
                               min_child_weight=min_child_weight,
                               min_split_gain=min_split_gain,
                               hist_impl=hist_impl)
                for k in range(g.shape[1])])
        in_specs = (P(None, None), P(None, None), P(None, None), P(None),
                    P(None))
    else:
        raise ValueError(f"unknown tree_learner {tree_learner!r}")

    # tree arrays replicate; the per-row node assignment stays sharded like
    # the rows it describes (feature mode holds full rows on every device)
    node_spec = (P(None, axis_name) if tree_learner == "data"
                 else P(None, None))
    fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=(P(None), P(None), P(None), node_spec),
                       check_vma=False)
    return jax.jit(fn)


def _stack_class_axis(builds):
    """[per-class output tuples] -> one tuple stacked over the class axis.
    A Python unroll rather than vmap: batching a pallas_call over 1D row
    operands produces block shapes Mosaic rejects, and K is 1 for every
    objective but multiclass, so the unroll is free in the common case."""
    return tuple(jnp.stack(parts) for parts in zip(*builds))


def _gather_tree_contrib(lv, node):
    """(K, L) leaf tables + (K, n) per-row leaf ids -> (n, K) raw-score
    contributions. The ONE definition of the training-raw update, shared
    by the fused serial steps and the sharded builder loop — the serial
    and distributed paths must apply the identical rule."""
    return jnp.stack([lv[k][node[k]] for k in range(lv.shape[0])], axis=1)


@functools.partial(jax.jit, static_argnames=("depth", "n_bins", "hist_impl"))
def _build_tree_multi(bins, grad, hess, row_mask, feat_mask, *, depth: int,
                      n_bins: int, lambda_l2, lambda_l1, min_child_weight,
                      min_split_gain, hist_impl: str = "segment"):
    """K trees per boosting iteration over the class axis of grad/hess
    (multiclass; K=1 otherwise)."""
    return _stack_class_axis([
        _build_tree_impl(bins, grad[:, k], hess[:, k], row_mask, feat_mask,
                         depth, n_bins, lambda_l2, lambda_l1,
                         min_child_weight, min_split_gain, hist_impl)
        for k in range(grad.shape[1])])


@functools.partial(jax.jit, static_argnames=(
    "depth", "n_bins", "hist_impl", "objective", "num_class", "update_raw"))
def _boost_step_level(bins, raw, y, row_mask, feat_mask, lr, alpha, *,
                      depth: int, n_bins: int, lambda_l2, lambda_l1,
                      min_child_weight, min_split_gain, hist_impl: str,
                      objective: str, num_class: int, update_raw: bool):
    """One FUSED serial boosting iteration: gradients + tree build + the
    training-raw update in a single dispatch: one jit per iteration is
    the cleaner contract, and it takes the per-dispatch floor out of the
    iteration whatever the depth of the runtime's dispatch queue.
    ``update_raw=False`` (rf mode) keeps raw fixed. The sharded (mesh)
    paths keep the builder-call structure."""
    g, h = _grad_hess(raw, y, objective, num_class, alpha)
    f, t, lv, node = _build_tree_multi(
        bins, g, h, row_mask, feat_mask, depth=depth, n_bins=n_bins,
        lambda_l2=lambda_l2, lambda_l1=lambda_l1,
        min_child_weight=min_child_weight, min_split_gain=min_split_gain,
        hist_impl=hist_impl)
    lv = lv * lr
    if update_raw:
        raw = raw + _gather_tree_contrib(lv, node)
    return raw, f, t, lv, node


@functools.partial(jax.jit, static_argnames=(
    "num_leaves", "n_bins", "max_depth", "hist_impl", "has_cats",
    "objective", "num_class", "update_raw"))
def _boost_step_leafwise(bins, raw, y, row_mask, feat_mask, cat_feats, lr,
                         alpha, *, num_leaves: int, n_bins: int, lambda_l2,
                         lambda_l1, min_child_weight, min_split_gain,
                         cat_smooth, max_depth: int, hist_impl: str,
                         has_cats: bool, objective: str, num_class: int,
                         update_raw: bool):
    """Leaf-wise twin of _boost_step_level: one dispatch per boosting
    iteration on the serial path."""
    from .leafwise import build_tree_leafwise_multi
    g, h = _grad_hess(raw, y, objective, num_class, alpha)
    S, f, t, W, IC, lv, node = build_tree_leafwise_multi(
        bins, g, h, row_mask, feat_mask, cat_feats,
        num_leaves=num_leaves, n_bins=n_bins, lambda_l2=lambda_l2,
        lambda_l1=lambda_l1, min_child_weight=min_child_weight,
        min_split_gain=min_split_gain, cat_smooth=cat_smooth,
        max_depth=max_depth, hist_impl=hist_impl, has_cats=has_cats)
    lv = lv * lr
    if update_raw:
        raw = raw + _gather_tree_contrib(lv, node)
    return raw, S, f, t, W, IC, lv, node


#: full precomputed node-test tables stop at this many internal nodes
#: (depth 7): past it a deep tree's (2^depth-1, n) table plus the gathered
#: rows scales geometrically — max_depth 15 at 10M rows would stage tens of
#: GB — so deeper trees compute each level's tests on the fly instead
#: (ADVICE r5). Mirrors the cnt<=64 where-chain guard below.
_TEST_TABLE_MAX_NODES = 127


@functools.partial(jax.jit, static_argnames=("depth",))
def _predict_tree_t(bins_t, feature, threshold, leaf, depth: int):
    """One level-wise tree from the TRANSPOSED bin matrix (d, n).

    Shallow trees (<= _TEST_TABLE_MAX_NODES internal nodes) precompute all
    node tests with one row-DMA (``jnp.take`` over rows of bins_t) +
    compare; the level walk then selects from the small (2^depth-1, n)
    bool table instead of doing a per-row feature gather against the full
    (n, d) matrix per level — the same round-5 scoring fix as the
    leaf-wise replay (leafwise._tree_tests_lw). rows stay uint8 (the int32
    promote fuses into the compare; thresholds carry the 256 no-split
    sentinel).

    Deeper trees never materialize the full table: levels up to the
    where-chain guard gather only THEIR 2^level rows on the fly, and
    deeper levels fall back to the per-row position gather (O(n) live
    memory — the pre-round-5 form, whose depth gathers are the memory-safe
    trade for trees this deep)."""
    n = bins_t.shape[1]
    full_table = 2 ** depth - 1 <= _TEST_TABLE_MAX_NODES
    if full_table:
        rows = jnp.take(bins_t, feature, axis=0)
        tests = rows > threshold[:, None]              # (2^depth-1, n)
    pos = jnp.zeros(n, dtype=jnp.int32)
    for level in range(depth):
        off = 2 ** level - 1
        cnt = 2 ** level
        if cnt <= 64:
            # select the row's node test with a where-chain — pure
            # elementwise VPU work; the take_along gather it replaces was
            # ~12 ms per level at 1M rows (5 gathers/tree dominated the
            # 100-tree scoring scan)
            if full_table:
                lv_tests = tests[off:off + cnt]
            else:   # this level's (cnt, n) slice only, freed next level
                lv_rows = jnp.take(bins_t, feature[off:off + cnt], axis=0)
                lv_tests = lv_rows > threshold[off:off + cnt, None]
            go_right = lv_tests[cnt - 1]
            for k in range(cnt - 2, -1, -1):
                go_right = jnp.where(pos == k, lv_tests[k], go_right)
        elif full_table:   # deep levels: the chain would unroll too far
            heap = off + pos
            go_right = jnp.take_along_axis(tests, heap[None, :],
                                           axis=0)[0]
        else:
            # deep level of a deep tree: per-row gather of each row's own
            # node test — O(n) memory, no (cnt, n) staging
            nf = feature[off + pos]
            nt = threshold[off + pos]
            vals = jnp.take_along_axis(bins_t, nf[None, :], axis=0)[0]
            go_right = vals > nt
        pos = pos * 2 + go_right.astype(jnp.int32)
    return leaf[pos]


@functools.partial(jax.jit, static_argnames=("depth",))
def _predict_tree(bins, feature, threshold, leaf, depth: int):
    """bins (n,d); tree arrays for one class -> (n,) leaf values.
    Row-major wrapper over _predict_tree_t (multi-tree scorers transpose
    once and call the _t form)."""
    return _predict_tree_t(bins.T, feature, threshold, leaf, depth)


# ------------------------------------------------------------- objectives

def _init_score(y: np.ndarray, p: GBDTParams) -> np.ndarray:
    if p.objective == "binary":
        pos = np.clip(y.mean(), 1e-6, 1 - 1e-6)
        return np.array([np.log(pos / (1 - pos))], dtype=np.float32)
    if p.objective == "multiclass":
        return np.zeros(p.num_class, dtype=np.float32)
    if p.objective == "quantile":
        return np.array([np.quantile(y, p.alpha)], dtype=np.float32)
    if p.objective == "mae":
        return np.array([np.median(y)], dtype=np.float32)
    return np.array([y.mean()], dtype=np.float32)  # regression l2


@functools.partial(jax.jit, static_argnames=("objective", "num_class"))
def _grad_hess(raw, y, objective: str, num_class: int, alpha):
    """raw (n, K), y (n,) -> grad/hess (n, K)."""
    if objective == "binary":
        prob = jax.nn.sigmoid(raw[:, 0])
        g = (prob - y)[:, None]
        h = (prob * (1 - prob))[:, None]
    elif objective == "multiclass":
        prob = jax.nn.softmax(raw, axis=1)
        onehot = jax.nn.one_hot(y.astype(jnp.int32), num_class)
        g = prob - onehot
        h = prob * (1 - prob)
    elif objective == "quantile":
        err = y - raw[:, 0]
        g = jnp.where(err >= 0, -alpha, 1.0 - alpha)[:, None]
        h = jnp.ones_like(g)
    elif objective == "mae":
        g = jnp.sign(raw[:, 0] - y)[:, None]
        h = jnp.ones_like(g)
    else:  # regression (l2)
        g = (raw[:, 0] - y)[:, None]
        h = jnp.ones_like(g)
    return g.astype(jnp.float32), h.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("objective",))
def _loss(raw, y, objective: str, alpha):
    if objective == "binary":
        z = raw[:, 0]
        return jnp.mean(jnp.logaddexp(0.0, z) - y * z)
    if objective == "multiclass":
        logp = jax.nn.log_softmax(raw, axis=1)
        return -jnp.mean(jnp.take_along_axis(
            logp, y.astype(jnp.int32)[:, None], axis=1))
    if objective == "quantile":
        err = y - raw[:, 0]
        return jnp.mean(jnp.maximum(alpha * err, (alpha - 1) * err))
    if objective == "mae":
        return jnp.mean(jnp.abs(raw[:, 0] - y))
    return 0.5 * jnp.mean((raw[:, 0] - y) ** 2)


# ------------------------------------------------------------------ fitting

def fit_gbdt(x: np.ndarray, y: np.ndarray, params: GBDTParams,
             mesh=None, sample_weight: Optional[np.ndarray] = None,
             eval_set: Optional[tuple] = None,
             elastic_ctx=None, binned: Optional[tuple] = None) -> TreeEnsemble:
    """Train a boosted ensemble. With a `mesh`, `params.tree_learner` picks
    the distributed mode: "data" shards rows and psums histograms over ICI
    (explicit shard_map — LightGBM's socket-allreduce ring), "feature"
    splits histogram work by feature with all_gather'ed split candidates,
    "auto" shards rows and lets XLA auto-SPMD place the collectives.

    ``elastic_ctx`` (an :class:`~...resilience.elastic.ElasticStepContext`)
    makes the boosting loop preemption-tolerant: every iteration passes
    the per-step host-loss/grow check, and the completed boosting state
    (trees so far, raw scores, RNG streams, early-stopping bookkeeping)
    is snapshotted host-side as the per-iteration checkpoint candidate a
    re-meshed attempt resumes from — see :func:`fit_gbdt_elastic`.

    ``binned=(bins, edges)`` supplies an ALREADY-BINNED (n, d) uint8
    matrix plus its quantile edges — the fit-side pipeline fusion path,
    where a fused featurize->bin program produced the wire matrix from
    raw columns on device and ``x`` never materialized (pass x=None).
    Edge computation and binning are skipped; the early-stopping holdout
    slices the binned matrix directly; a user ``eval_set`` (raw feature
    rows, which would need the skipped binner) is rejected."""
    n, d = (binned[0].shape if binned is not None else x.shape)
    with telemetry.trace.span("gbdt/fit", rows=int(n),
                              features=int(d),
                              objective=params.objective,
                              iterations=params.num_iterations):
        return _fit_gbdt_impl(x, y, params, mesh=mesh,
                              sample_weight=sample_weight,
                              eval_set=eval_set, elastic_ctx=elastic_ctx,
                              binned=binned)


def fit_gbdt_elastic(x: np.ndarray, y: np.ndarray, params: GBDTParams,
                     *, checkpoint_dir: str, n_hosts: int = 0,
                     min_hosts: int = 1, grace: Optional[float] = None,
                     max_failures: int = 5,
                     heartbeat_interval: Optional[float] = None,
                     max_hosts: int = 0,
                     sample_weight: Optional[np.ndarray] = None,
                     eval_set: Optional[tuple] = None) -> TreeEnsemble:
    """Elastic boosted fit: drives :func:`fit_gbdt` through the
    :class:`~...resilience.elastic.ElasticFitCoordinator` recovery loop,
    so a host lost mid-boosting raises ``HostLossError`` -> re-mesh over
    the survivors -> resume from the last completed iteration's
    boosting-state snapshot (and a relaunched host grows the mesh back
    at the next iteration boundary) instead of the fit dying.

    ``x``/``y`` are the RAW (unpadded) rows: each attempt pads to its
    own (possibly shrunk or regrown) device multiple. ``checkpoint_dir``
    hosts the heartbeat files; the boosting state itself resumes from
    the coordinator's in-memory snapshot (trees are cheap host arrays —
    msgpack durability is the trainer's problem, liveness is this one's).
    """
    from ...parallel import mesh as meshlib
    from ...resilience.elastic import ElasticFitCoordinator
    if params.tree_learner not in ("data", "auto"):
        raise ValueError(
            "elastic GBDT fits shard rows (tree_learner=data|auto), got "
            f"{params.tree_learner!r}")
    coord = ElasticFitCoordinator(
        checkpoint_dir=checkpoint_dir, n_hosts=n_hosts,
        min_hosts=min_hosts, grace=grace, max_failures=max_failures,
        heartbeat_interval=heartbeat_interval, max_hosts=max_hosts)

    def attempt(devices, ctx):
        mesh = meshlib.create_mesh(devices=devices)
        xp, n_real = meshlib.pad_batch_to_devices(x, mesh)
        yp = np.concatenate([y, np.zeros(len(xp) - n_real, y.dtype)])
        w = (np.ones(n_real, np.float32) if sample_weight is None
             else np.asarray(sample_weight, np.float32))
        w = np.concatenate([w, np.zeros(len(xp) - n_real, np.float32)])
        with meshlib.collective_fit_lock:
            return fit_gbdt(xp, yp, params, mesh=mesh, sample_weight=w,
                            eval_set=eval_set, elastic_ctx=ctx)

    return coord.run(attempt)


def _fit_gbdt_impl(x: np.ndarray, y: np.ndarray, params: GBDTParams,
                   mesh=None, sample_weight: Optional[np.ndarray] = None,
                   eval_set: Optional[tuple] = None,
                   elastic_ctx=None,
                   binned: Optional[tuple] = None) -> TreeEnsemble:
    p = params
    if binned is not None:
        if eval_set is not None:
            raise ValueError(
                "binned fits draw their early-stopping holdout from the "
                "binned matrix itself; a raw-feature eval_set would need "
                "the skipped binner — pass eval_set=None")
        bins, edges = np.asarray(binned[0]), np.asarray(binned[1])
        n, d = bins.shape
    else:
        n, d = x.shape
    if p.tree_learner not in ("serial", "data", "feature", "auto"):
        raise ValueError(f"unknown tree_learner {p.tree_learner!r}; expected "
                         "serial|data|feature|auto")
    if p.hist_impl not in ("auto", "mxu", "compare", "segment", "pallas"):
        raise ValueError(f"unknown hist_impl {p.hist_impl!r}; expected "
                         "auto|mxu|compare|segment|pallas")
    if not 2 <= p.max_bin <= 256:
        raise ValueError(f"max_bin must be in [2, 256] (uint8 bin ids; "
                         f"LightGBM's own ceiling is 255), got {p.max_bin}")
    tree_learner = p.tree_learner if mesh is not None else "serial"
    if tree_learner == "serial":
        mesh = None
    leafwise = p.num_leaves > 0
    if leafwise and not 2 <= p.num_leaves <= 4096:
        raise ValueError(f"num_leaves must be in [2, 4096], got {p.num_leaves}")
    if leafwise and tree_learner == "feature":
        raise ValueError(
            "leaf-wise growth supports tree_learner=serial|data|auto "
            "(feature-parallel candidates are level-wise only; set "
            "num_leaves=0 or tree_learner='data')")
    if p.categorical_feature and not leafwise:
        raise ValueError("categorical_feature requires leaf-wise growth "
                         "(set num_leaves > 0)")
    cat_arr = np.zeros(d, dtype=bool)
    for j in p.categorical_feature:
        if not 0 <= j < d:
            raise ValueError(f"categorical_feature index {j} out of range "
                             f"for {d} features")
        cat_arr[j] = True
        if binned is not None:
            # identity binning already clipped the codes; the raw column
            # never materialized, so the top-code warning cannot run
            continue
        with np.errstate(invalid="ignore"):
            top = float(np.nanmax(x[:, j])) if len(x) else 0.0
        if top >= p.max_bin:
            from ...core.utils import get_logger
            get_logger("gbdt").warning(
                "categorical feature %d has codes up to %d but max_bin=%d; "
                "codes >= max_bin alias into one bin — raise maxBin or "
                "re-index the column", j, int(top), p.max_bin)
    K = p.num_class if p.objective == "multiclass" else 1
    is_rf = p.boosting_type == "rf"
    if is_rf and not ((p.bagging_fraction < 1.0 and p.bagging_freq > 0)
                      or p.feature_fraction < 1.0):
        raise ValueError("boosting_type='rf' without bagging or feature "
                         "subsampling trains identical trees; set "
                         "bagging_fraction<1 + bagging_freq>=1 (LightGBM "
                         "rejects this combination too)")
    # global statistics (bin edges, init score) must come from REAL rows only
    # — mesh padding / user-masked rows are weight 0
    # histogram backend: auto = the round-5 "mxu" kernel on TPU (node axis
    # in the matmul M dim, one-hot width fixed at n_bins: 14.6 ms per
    # 1M x 28 x 16-node build vs segment_sum's 384 ms and the v1 pallas
    # one-hot's 4.0 s, all synced, on an earlier runtime — ROADMAP S1/S4);
    # on the cpu test backend the "compare" hybrid
    # (compare-reduce for uint8 id spaces, segment_sum beyond — CPU CI
    # shouldn't pay Pallas interpret-mode costs). "segment" = pure
    # segment_sum (A/B + bit-reproducing older fits); "pallas" = the v1
    # one-hot kernel (A/B); explicit values never re-route.
    hist_impl = p.hist_impl
    if hist_impl == "auto":
        hist_impl = "mxu" if on_tpu() else "compare"
    real = slice(None) if sample_weight is None else sample_weight > 0
    from ...parallel import mesh as _meshlib
    nproc = _meshlib.effective_process_count()
    if binned is not None and nproc > 1:
        raise ValueError(
            "binned fits are single-process (fit-side pipeline fusion); "
            "multi-process fits pool bin edges from raw row shards")
    if nproc > 1:
        # MULTI-PROCESS fit: `x` is THIS process's row shard (the Spark-
        # partition analog; the reference's per-partition LightGBM workers,
        # LightGBMClassifier.scala:35-47). Fitted statistics must be
        # IDENTICAL everywhere: bin edges and the init score come from a
        # pooled per-process sample (same trade as LightGBM's
        # bin_construct_sample_cnt, here split across the fleet).
        if tree_learner not in ("data", "auto"):
            raise ValueError(
                f"multi-process fits support tree_learner=data|auto (rows "
                f"are sharded across processes), got {tree_learner!r}")
        from ...parallel import dataplane
        # sample INDICES first: masking/casting the whole shard would copy
        # multi-GB transients just to keep <= cap rows
        cand = (np.arange(n) if sample_weight is None
                else np.flatnonzero(sample_weight > 0))
        # each process contributes in proportion to its REAL shard size —
        # an equal split would over-weight small shards in the pooled
        # quantile edges and init score relative to the single-process fit
        cap = dataplane.proportional_sample_cap(len(cand), 200_000)
        if len(cand) > cap:
            cand = np.random.default_rng(p.seed).choice(cand, cap,
                                                        replace=False)
        xr = x[cand].astype(np.float32)
        yr = y[cand].astype(np.float32)
        pooled = dataplane.allgather_pyobj((xr, yr))
        gx = np.concatenate([a for a, _ in pooled])
        gy = np.concatenate([b for _, b in pooled])
        edges = compute_bin_edges(gx, p.max_bin)
        base_global = _init_score(gy, p)
    elif binned is not None:
        base_global = None       # bins + edges arrived precomputed
    else:
        edges = compute_bin_edges(x[real], p.max_bin)
        base_global = None
    if binned is None:
        with telemetry.trace.span("gbdt/bin", rows=n, features=d), \
                _m_bin_time.time():
            bins = bin_data_auto(x, edges,
                                 cat_arr if cat_arr.any() else None,
                                 p.max_bin)
    d_pad = d
    if tree_learner == "feature":
        # pad the feature axis to a device multiple; padded columns carry
        # feat_mask 0 so they can never win a split
        n_dev = mesh.shape["data"]
        d_pad = -(-d // n_dev) * n_dev
        if d_pad != d:
            bins = np.pad(bins, ((0, 0), (0, d_pad - d)))
    base = base_global if base_global is not None else _init_score(y[real], p)
    raw_np = np.broadcast_to(base[None, :], (n, K)).astype(np.float32)

    shard_rows = mesh is not None and tree_learner in ("data", "auto")
    if shard_rows:
        from ...parallel import mesh as meshlib
        # single-process: one device_put sharded over `data`; multi-process:
        # each process contributes ITS rows to the global array
        bins_j = meshlib.put_global_batch(bins, mesh)
        raw = meshlib.put_global_batch(raw_np, mesh)
        yj = meshlib.put_global_batch(y.astype(np.float32), mesh)
    else:
        # nproc > 1 cannot reach here: the multi-process check above forces
        # tree_learner data|auto, which always carries a mesh
        bins_j = jnp.asarray(bins)
        raw = jnp.asarray(raw_np)
        yj = jnp.asarray(y.astype(np.float32))

    builder = None
    cat_j = jnp.asarray(cat_arr.astype(np.float32))
    if leafwise:
        from . import leafwise as lw
        # 0 or -1 = uncapped (accept LightGBM's -1 convention)
        lw_depth = max(0, p.max_depth)
        if mesh is not None:   # data/auto: rows sharded, psum per round
            builder = lw.make_sharded_builder_lw(
                mesh, num_leaves=p.num_leaves, n_bins=p.max_bin,
                lambda_l2=p.lambda_l2, lambda_l1=p.lambda_l1,
                min_child_weight=p.min_child_weight,
                min_split_gain=p.min_split_gain, cat_smooth=p.cat_smooth,
                max_depth=lw_depth, hist_impl=hist_impl,
                has_cats=bool(cat_arr.any()))
    elif mesh is not None and tree_learner in ("data", "feature"):
        builder = make_sharded_builder(
            mesh, tree_learner, depth=p.max_depth, n_bins=p.max_bin,
            d_pad=d_pad, lambda_l2=p.lambda_l2, lambda_l1=p.lambda_l1,
            min_child_weight=p.min_child_weight,
            min_split_gain=p.min_split_gain, hist_impl=hist_impl)

    # per-ROW randomness (bagging, holdout) is process-local data and may
    # diverge across processes; the FEATURE mask is replicated and must be
    # identical everywhere — separate streams
    rng = np.random.default_rng(p.seed + (jax.process_index()
                                          if nproc > 1 else 0))
    feat_rng = np.random.default_rng(p.seed ^ 0x5EED)
    feats, thrs, leaves = [], [], []
    best_loss, since_best, best_iter = np.inf, 0, None
    if is_rf:
        # rf averages a fixed-size forest; a partial average is not a
        # comparable validation series, so early stopping does not apply
        p = p._replace(early_stopping_round=0)
    # early stopping monitors a held-out set (LightGBM's valid_sets contract;
    # train loss is monotone in boosting so it can never trigger a stop)
    if p.early_stopping_round > 0 and eval_set is None:
        # draw the holdout only from real rows (weight > 0): mesh padding and
        # user-masked rows must not enter the validation metric
        candidates = (np.arange(n) if sample_weight is None
                      else np.flatnonzero(sample_weight > 0))
        idx = rng.permutation(candidates)
        n_val = max(1, len(candidates) // 5)
        # binned fits slice the wire matrix (row-wise binning is
        # deterministic, so bins[idx] == bin(x[idx]) bit-for-bit)
        eval_set = ((bins[idx[:n_val]] if binned is not None
                     else x[idx[:n_val]]), y[idx[:n_val]])
        # held-out rows must not train: zero them in the weight mask
        holdout = np.ones(n, dtype=np.float32)
        holdout[idx[:n_val]] = 0.0
        sample_weight = (holdout if sample_weight is None
                         else sample_weight * holdout)
    if eval_set is not None:
        bins_val = (jnp.asarray(eval_set[0]) if binned is not None
                    else jnp.asarray(bin_data_auto(
                        np.asarray(eval_set[0], dtype=np.float32), edges,
                        cat_arr if cat_arr.any() else None, p.max_bin)))
        # transposed once for the per-iteration eval predicts (the _t
        # scoring forms); re-transposing per class per iteration is waste
        bins_val_t = bins_val.T
        y_val = jnp.asarray(np.asarray(eval_set[1], dtype=np.float32))
        raw_val = jnp.broadcast_to(jnp.asarray(base)[None, :],
                                   (bins_val.shape[0], K)).astype(jnp.float32)

    bagging = p.bagging_fraction < 1.0 and p.bagging_freq > 0
    rm = None  # device-resident row mask; re-shipped ONLY when it changes
               # (an (n,) f32 transfer per iteration dominated 10M-row fits)

    def _ship_row_mask(row_mask):
        if shard_rows:
            from ...parallel import mesh as meshlib
            return meshlib.put_global_batch(
                np.asarray(row_mask, np.float32), mesh)
        return jnp.asarray(row_mask)

    lr_eff = 1.0 if is_rf else p.learning_rate

    # ---- elastic resume: re-enter from the latest boosting snapshot ----
    # (single-process failure domains only; a real multi-process fleet
    # uses the coordinator's detection + fail-fast + relaunch path)
    start_it = 0
    row_mask_host = None
    elastic_snap = elastic_ctx is not None and nproc == 1
    if elastic_snap:
        snap = elastic_ctx.latest_snapshot()
        if snap is not None:
            start_it = snap["it"] + 1
            feats = list(snap["feats"])
            thrs = list(snap["thrs"])
            leaves = list(snap["leaves"])
            best_loss, since_best, best_iter = snap["best"]
            # the RNG streams continue EXACTLY where the lost attempt
            # left them: bagging masks and feature fractions replay
            # deterministically from the snapshot point
            rng.bit_generator.state = snap["rng"]
            feat_rng.bit_generator.state = snap["feat_rng"]
            k = min(len(snap["raw"]), n)
            raw_host = np.broadcast_to(base[None, :], (n, K)) \
                .astype(np.float32).copy()
            raw_host[:k] = snap["raw"][:k]     # pad rows train at weight 0
            if shard_rows:
                from ...parallel import mesh as _ml
                raw = _ml.put_global_batch(raw_host, mesh)
            else:
                raw = jnp.asarray(raw_host)
            if snap.get("row_mask") is not None:
                mask = np.zeros(n, np.float32)
                mask[:k] = snap["row_mask"][:k]
                row_mask_host = mask
                rm = _ship_row_mask(mask)
            if eval_set is not None and snap.get("raw_val") is not None:
                raw_val = jnp.asarray(snap["raw_val"])
            from ...core.utils import get_logger
            get_logger("gbdt").info(
                "elastic resume: re-entering the boosting loop at "
                "iteration %d (%d trees restored)", start_it, len(leaves))
        elastic_ctx.resumed(None if snap is None else (0, snap["it"]),
                            None)

    for it in range(start_it, p.num_iterations):
        t_iter = time.perf_counter()
        if elastic_ctx is not None:
            # host-loss / grow check (site elastic.step): HostLossError /
            # HostRejoinError unwind to the coordinator's re-mesh; the
            # snapshot above is what the next attempt resumes from
            elastic_ctx.check_step()
        # rf mode (LightGBM boosting=rf): every tree fits the INITIAL
        # gradients on its own bootstrap sample; raw never moves during the
        # fit and leaves are averaged (scaled 1/T) at the end
        if builder is not None:
            # sharded paths compute gradients outside the builder; the
            # serial paths fuse grad + build + raw update into ONE
            # dispatch per iteration (_boost_step_* — measured perf-equal
            # to the multi-dispatch loop; see its docstring)
            with telemetry.trace.span("gbdt/iter/grad", tree=it) as _sp:
                g, h = _grad_hess(raw, yj, p.objective, K, p.alpha)
                _sp.set_sync(h)
        if bagging:
            if it % p.bagging_freq == 0:
                bag_mask = (rng.random(n) < p.bagging_fraction).astype(np.float32)
                # combine fresh on refresh — a reused bag mask must not
                # compound sample_weight geometrically
                row_mask = (bag_mask if sample_weight is None
                            else bag_mask * sample_weight.astype(np.float32))
                row_mask_host = row_mask
                rm = _ship_row_mask(row_mask)
            # else: reuse the device-resident mask from the last refresh
        elif rm is None:
            row_mask = (np.ones(n, dtype=np.float32) if sample_weight is None
                        else sample_weight.astype(np.float32))
            row_mask_host = row_mask
            rm = _ship_row_mask(row_mask)
        if p.feature_fraction < 1.0:
            fm = (feat_rng.random(d) < p.feature_fraction)
            if not fm.any():
                fm[feat_rng.integers(0, d)] = True
            feat_mask = fm.astype(np.float32)
        else:
            feat_mask = np.ones(d, dtype=np.float32)

        fm = jnp.asarray(np.pad(feat_mask, (0, d_pad - d)))
        if leafwise:
            from . import leafwise as lw
            if builder is not None:
                with telemetry.trace.span("gbdt/iter/build", tree=it,
                                          mode="leafwise") as _sp:
                    tree = builder(bins_j, g, h, rm, fm, cat_j)
                    _sp.set_sync(tree)
                S, f, t, W, IC, lv, node_tr = tree
                lv = lv * lr_eff
            else:
                with telemetry.trace.span("gbdt/iter/step", tree=it,
                                          mode="leafwise") as _sp:
                    raw, S, f, t, W, IC, lv, node_tr = _boost_step_leafwise(
                        bins_j, raw, yj, rm, fm, cat_j,
                        jnp.float32(lr_eff), p.alpha,
                        num_leaves=p.num_leaves, n_bins=p.max_bin,
                        lambda_l2=p.lambda_l2, lambda_l1=p.lambda_l1,
                        min_child_weight=p.min_child_weight,
                        min_split_gain=p.min_split_gain,
                        cat_smooth=p.cat_smooth, max_depth=lw_depth,
                        hist_impl=hist_impl, has_cats=bool(cat_arr.any()),
                        objective=p.objective, num_class=K,
                        update_raw=not is_rf)
                    _sp.set_sync(raw)
            feats.append((S, f, t, W, IC))
            leaves.append(lv)
            # training rows' leaves are known from the grow: the raw update
            # is a tiny-table gather, no split-sequence replay. The eval
            # `step` localizes replicated tree arrays under multi-process
            # (the val set is process-local; mixing global and local arrays
            # in one jit is undefined).
            loc = (lambda a: np.asarray(a)) if nproc > 1 else (lambda a: a)
            step = lambda bt: jnp.stack(
                [lw.predict_tree_lw_t(bt, loc(S[k]), loc(f[k]), loc(t[k]),
                                      loc(W[k]), loc(IC[k]), loc(lv[k]),
                                      has_cats=bool(cat_arr.any()))
                 for k in range(K)], axis=1)
            train_step_fn = lambda: _gather_tree_contrib(lv, node_tr)
        else:
            if builder is not None:
                with telemetry.trace.span("gbdt/iter/build", tree=it,
                                          mode="levelwise") as _sp:
                    f, t, lv, node_tr = builder(bins_j, g, h, rm, fm)
                    _sp.set_sync(node_tr)
                # rf leaves stay unscaled here; the 1/T average is applied
                # at the end over the ACTUAL forest size
                lv = lv * lr_eff
            else:
                with telemetry.trace.span("gbdt/iter/step", tree=it,
                                          mode="levelwise") as _sp:
                    raw, f, t, lv, node_tr = _boost_step_level(
                        bins_j, raw, yj, rm, fm, jnp.float32(lr_eff),
                        p.alpha,
                        depth=p.max_depth, n_bins=p.max_bin,
                        lambda_l2=p.lambda_l2, lambda_l1=p.lambda_l1,
                        min_child_weight=p.min_child_weight,
                        min_split_gain=p.min_split_gain,
                        hist_impl=hist_impl,
                        objective=p.objective, num_class=K,
                        update_raw=not is_rf)
                    _sp.set_sync(raw)
            feats.append(f)
            thrs.append(t)
            leaves.append(lv)
            loc = (lambda a: np.asarray(a)) if nproc > 1 else (lambda a: a)
            step = lambda bt: jnp.stack(
                [_predict_tree_t(bt, loc(f[k]), loc(t[k]), loc(lv[k]),
                                 depth=p.max_depth)
                 for k in range(K)], axis=1)
            # training rows' leaves came back from the build: the raw
            # update is a tiny-table gather, no tree replay (same trick
            # the leaf-wise path uses)
            train_step_fn = lambda: _gather_tree_contrib(lv, node_tr)
        if not is_rf and builder is not None:
            # serial paths already updated raw inside the fused step
            with telemetry.trace.span("gbdt/iter/apply", tree=it) as _sp:
                raw = raw + train_step_fn()
                _sp.set_sync(raw)
        _m_iters.inc()
        _m_iter_time.observe(time.perf_counter() - t_iter)
        # per-iteration HBM high-water sample (profiler on only): the
        # boosting loop's live-buffer growth is where deep/wide fits OOM
        telemetry.profiler.sample_live_buffers()

        if p.early_stopping_round > 0:
            t_eval = time.perf_counter()
            with telemetry.trace.span("gbdt/eval", tree=it) as _sp:
                raw_val = raw_val + step(bins_val_t)
                _sp.set_sync(raw_val)
            cur = float(_loss(raw_val, y_val, p.objective, p.alpha))
            _m_eval_time.observe(time.perf_counter() - t_eval)
            if nproc > 1:
                # the stop decision must be identical fleet-wide: average
                # the per-process validation losses (row-weighted)
                from ...parallel import dataplane
                tot = dataplane.allreduce_sum(
                    np.array([cur * len(y_val), float(len(y_val))]))
                cur = float(tot[0] / max(tot[1], 1.0))
            if cur < best_loss - 1e-9:
                best_loss, since_best, best_iter = cur, 0, it + 1
            else:
                since_best += 1
                if since_best >= p.early_stopping_round:
                    break

        if elastic_snap:
            # host-side boosting-state candidate (newest wins): everything
            # a re-meshed attempt needs to continue bit-exactly from
            # iteration it+1. checkpoint_saved marks the grow boundary —
            # for boosted fits the snapshot IS the checkpoint.
            import jax.tree_util as jtu
            elastic_ctx.save_snapshot({
                "it": it,
                "feats": jtu.tree_map(np.asarray, list(feats)),
                "thrs": jtu.tree_map(np.asarray, list(thrs)),
                "leaves": [np.asarray(lv) for lv in leaves],
                "raw": np.asarray(raw),
                "raw_val": (np.asarray(raw_val) if eval_set is not None
                            else None),
                "row_mask": row_mask_host,
                "rng": rng.bit_generator.state,
                "feat_rng": feat_rng.bit_generator.state,
                "best": (best_loss, since_best, best_iter)})
            elastic_ctx.step_committed(0, it)
            elastic_ctx.checkpoint_saved(0, it)

    if best_iter is not None:
        feats, thrs, leaves = (feats[:best_iter], thrs[:best_iter],
                               leaves[:best_iter])
    if is_rf:
        leaves = [lv / len(leaves) for lv in leaves]
    if leafwise:
        from .leafwise import LeafwiseEnsemble
        return LeafwiseEnsemble(
            split_leaf=jnp.stack([s for s, *_ in feats]),
            feature=jnp.stack([f for _, f, *_ in feats]),
            threshold=jnp.stack([t for _, _, t, *_ in feats]),
            cat_bitset=jnp.stack([w for _, _, _, w, _ in feats]),
            is_cat=jnp.stack([ic for *_, ic in feats]),
            leaf=jnp.stack(leaves), bin_edges=edges,
            cat_features=cat_arr, base=base, objective=p.objective)
    return TreeEnsemble(
        feature=jnp.stack(feats), threshold=jnp.stack(thrs),
        leaf=jnp.stack(leaves), bin_edges=edges, base=base,
        objective=p.objective)


#: per-chunk node-test table budget for ensemble scoring: rows batch so
#: the (table_nodes, chunk) bool staging stays under this many bytes
#: (ADVICE r5 — unbatched 10M-row deep-tree predicts staged multi-GB)
_PREDICT_TABLE_BYTES_CAP = 256 << 20


def _predict_chunk_rows(n: int, table_nodes: int) -> int:
    """Rows per scoring chunk keeping the test table under the byte cap
    (1 byte per node-test per row); small calls stay a single dispatch."""
    cap = max(4096, _PREDICT_TABLE_BYTES_CAP // max(1, table_nodes))
    return n if n <= cap else cap


def _predict_chunked(bins: np.ndarray, score_chunk, table_nodes: int
                     ) -> np.ndarray:
    """Shared row-batching driver: score fixed-size chunks (tail padded so
    the jitted program compiles for ONE shape), record the peak test-table
    estimate on the telemetry gauge."""
    n = bins.shape[0]
    chunk = _predict_chunk_rows(n, table_nodes)
    _m_predict_table_bytes.set(table_nodes * min(max(n, 1), chunk))
    telemetry.profiler.sample_live_buffers()
    if n <= chunk:
        return score_chunk(bins)
    outs = []
    for lo in range(0, n, chunk):
        part = bins[lo:lo + chunk]
        m = len(part)
        if m < chunk:   # pad the tail: one compiled shape for all chunks
            part = np.concatenate(
                [part, np.zeros((chunk - m,) + part.shape[1:], part.dtype)])
        outs.append(score_chunk(part)[:m])
    return np.concatenate(outs, axis=0)


def quantize_leaves_int8(leaf: np.ndarray):
    """f32 leaf table (T, K, L) -> per-(tree, class) symmetric int8:
    ``(q int8 (T,K,L), scale f32 (T,K,1))`` with ``q * scale ~= leaf``.

    One scale per tree per class (not global): boosting shrinks leaf
    magnitudes iteration over iteration, so a single ensemble-wide scale
    would burn the int8 range on the first trees and quantize the last
    ones to zero. Per-tree the round-off is <= scale/2 = max|leaf|/254
    of THAT tree — the summed raw-score error stays in the same band as
    the bf16 round (parity tests pin <= 1e-3, argmax exact)."""
    leaf = np.asarray(leaf, np.float32)
    amax = np.abs(leaf).max(axis=2, keepdims=True)
    scale = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
    q = np.rint(leaf / scale).astype(np.int8)
    return q, scale


def dequant_leaf(leaf):
    """Widen a stored leaf table to the f32 the predict kernels consume:
    bf16 tables widen exactly; ``(int8, scale)`` pairs dequantize."""
    if isinstance(leaf, tuple):
        q, scale = leaf
        return jnp.asarray(q, jnp.float32) * jnp.asarray(scale)
    return jnp.asarray(leaf).astype(jnp.float32)


def leaf_table_bytes(leaf) -> int:
    """Stored bytes of a quantized leaf table (the traffic-gauge term):
    2/leaf for bf16, 1/leaf + the f32 scales for int8."""
    if isinstance(leaf, tuple):
        q, scale = leaf
        return q.nbytes + scale.nbytes
    return leaf.size * 2


def quantize_ensemble(ens: TreeEnsemble, num_iteration: Optional[int] = None,
                      leaf_dtype: str = "bf16"):
    """Level-wise ensemble -> structure-of-arrays quantized test tables:
    ``(feature u8 (T,K,N), threshold u8 (T,K,N), leaf)`` where leaf is a
    bf16 (T,K,L) table (``leaf_dtype='bf16'``) or a per-tree-scaled
    ``(int8 (T,K,L), f32 scale (T,K,1))`` pair (``'int8'`` — half the
    leaf bytes again; see :func:`quantize_leaves_int8`).

    Exactness argument (the tables are lossless except the leaf round):
    feature ids live in [0, d) with d <= 256 enforced here; bin
    ids live in [0, max_bin) with max_bin <= 256 (fit_gbdt's uint8 wire
    contract), so the route test ``bin > thr`` is unchanged by clamping
    thresholds to 255 — the route-all-left sentinel (thr = n_bins) and a
    bin-255 threshold both already route nothing right against uint8
    bins. The leaf round is the one lossy step (bf16: <= 2^-9 relative
    per leaf; int8: <= max|leaf|/254 per tree — the parity bound tests
    pin <= 1e-3 on summed raw scores for both)."""
    if leaf_dtype not in ("bf16", "int8"):
        raise ValueError(f"leaf_dtype must be bf16|int8, got {leaf_dtype!r}")
    T = ens.feature.shape[0]
    T = min(T, num_iteration) if num_iteration else T
    d = ens.bin_edges.shape[0]
    if d > 256:
        raise ValueError(f"quantized predict tables need <= 256 features "
                         f"(uint8 feature ids), got {d}")
    feat = np.asarray(ens.feature[:T]).astype(np.uint8)
    thr = np.minimum(np.asarray(ens.threshold[:T]), 255).astype(np.uint8)
    if leaf_dtype == "int8":
        leaf = quantize_leaves_int8(np.asarray(ens.leaf[:T]))
    else:
        leaf = jnp.asarray(ens.leaf[:T]).astype(jnp.bfloat16)
    return feat, thr, leaf


def _resolve_predict_impl(requested: str, eligible: bool, why: str) -> str:
    """auto|dense|pallas|pallas_int8 -> the impl that will run. 'auto'
    rides the quantized pallas kernel only on TPU (interpret mode
    off-TPU is a correctness fallback, not a fast path) and only when
    the ensemble fits the kernel's unroll caps; an EXPLICIT
    'pallas'/'pallas_int8' on an ineligible ensemble is an error, not a
    silent reroute. 'pallas_int8' is the same kernel path with
    per-tree-scaled int8 leaf tables (explicit opt-in: one more lossy
    round than bf16, half the leaf bytes again)."""
    if requested not in ("auto", "dense", "pallas", "pallas_int8"):
        raise ValueError(f"predict_impl must be auto|dense|pallas|"
                         f"pallas_int8, got {requested!r}")
    if requested == "dense":
        return "dense"
    if requested in ("pallas", "pallas_int8"):
        if not eligible:
            raise ValueError(f"predict_impl={requested!r} unavailable: "
                             f"{why}")
        return requested
    return "pallas" if eligible and on_tpu() else "dense"


def _quant_eligible_levelwise(ens: TreeEnsemble, depth: int):
    from ...ops.pallas_kernels import (PREDICT_QUANT_MAX_LEAVES,
                                       PREDICT_QUANT_MAX_NODES)
    d = ens.bin_edges.shape[0]
    if d > 256:
        return False, f"{d} features exceed the uint8 feature-id space"
    if 2 ** depth - 1 > PREDICT_QUANT_MAX_NODES \
            or 2 ** depth > PREDICT_QUANT_MAX_LEAVES:
        return False, (f"depth {depth} exceeds the kernel's unroll cap "
                       f"({PREDICT_QUANT_MAX_NODES} nodes)")
    return True, ""


def _set_predict_traffic_gauge(n: int, d: int, K: int, table_bytes: int,
                               test_table_nodes: int):
    if telemetry.enabled() and n:
        _m_predict_bytes_per_row.set(
            d + 4 * K + test_table_nodes + table_bytes / n)


def _predict_quant_levelwise(ens: TreeEnsemble, bins: np.ndarray, T: int,
                             depth: int,
                             leaf_dtype: str = "bf16") -> np.ndarray:
    """The quantized pallas scoring path: SoA uint8 + bf16/int8 tables
    walked by the tile-resident kernel, chunked so per-chunk device
    staging stays under the predict byte cap (the same streaming guard
    as the dense path — here the per-row staging is the bin row + f32
    output, no test table). ``leaf_dtype='int8'`` stores per-tree-scaled
    int8 leaves (the gauge reflects the smaller table); the kernel
    always walks the f32 widening, so the traversal is identical."""
    from ...ops.pallas_kernels import gbdt_predict_quant_levelwise
    feat, thr, leaf = quantize_ensemble(ens, T, leaf_dtype=leaf_dtype)
    K = feat.shape[1]
    n, d = bins.shape
    base = jnp.asarray(ens.base)[None, :].astype(jnp.float32)
    table_bytes = feat.nbytes + thr.nbytes + leaf_table_bytes(leaf)
    _set_predict_traffic_gauge(n, d, K, table_bytes, 0)
    leaf_f32 = dequant_leaf(leaf)

    @jax.jit
    def run(part):
        contrib = gbdt_predict_quant_levelwise(part.T, feat, thr,
                                               leaf_f32, depth=depth)
        return contrib + base

    prof = telemetry.profiler.wrap(run, "gbdt.predict_quant")
    return _predict_chunked(
        np.asarray(bins), lambda part: np.asarray(prof(jnp.asarray(part))),
        d + 4 * K)


def predict_raw(ens, x: np.ndarray,
                num_iteration: Optional[int] = None,
                predict_impl: str = "auto") -> np.ndarray:
    """Raw ensemble scores (n, K). Accepts level-wise TreeEnsemble or
    leafwise.LeafwiseEnsemble. Rows batch past the test-table byte cap
    (_PREDICT_TABLE_BYTES_CAP) so deep/wide ensembles score huge inputs
    at bounded HBM. ``predict_impl`` picks the scoring backend: 'dense'
    (the f32/int32 XLA test-table path), 'pallas' (quantized SoA tables
    — uint8 feature/threshold, bf16 leaf — walked by the tile-resident
    kernel in ops/pallas_kernels.py), 'pallas_int8' (same kernel with
    per-tree-scaled int8 leaf tables — half the leaf bytes again), or
    'auto' (pallas on TPU when the ensemble fits the kernel caps, dense
    otherwise)."""
    from .leafwise import LeafwiseEnsemble, predict_raw_lw
    if isinstance(ens, LeafwiseEnsemble):
        bins = bin_data_auto(
            x, ens.bin_edges,
            ens.cat_features if ens.cat_features.any() else None,
            ens.bin_edges.shape[1] + 1)
        return predict_raw_lw(ens, bins, num_iteration,
                              predict_impl=predict_impl)
    bins = bin_data_auto(x, ens.bin_edges)
    T, K, _ = ens.feature.shape
    depth = int(np.log2(ens.leaf.shape[2]))
    T = min(T, num_iteration) if num_iteration else T
    eligible, why = _quant_eligible_levelwise(ens, depth)
    resolved = _resolve_predict_impl(predict_impl, eligible, why)
    if resolved in ("pallas", "pallas_int8"):
        return _predict_quant_levelwise(
            ens, np.asarray(bins), T, depth,
            leaf_dtype="int8" if resolved == "pallas_int8" else "bf16")

    @jax.jit
    def run(bins, feature, threshold, leaf):
        bins_t = bins.T              # once per scoring call, not per tree
        def body(raw, tree):
            f, t, lv = tree
            contrib = jnp.stack(
                [_predict_tree_t(bins_t, f[k], t[k], lv[k], depth=depth)
                 for k in range(K)], axis=1)
            return raw + contrib, None
        init = jnp.broadcast_to(jnp.asarray(ens.base)[None, :],
                                (bins.shape[0], K)).astype(jnp.float32)
        raw, _ = jax.lax.scan(body, init, (feature, threshold, leaf))
        return raw

    nodes = 2 ** depth - 1
    table_nodes = nodes if nodes <= _TEST_TABLE_MAX_NODES else 64
    d = ens.bin_edges.shape[0]
    _set_predict_traffic_gauge(
        bins.shape[0], d, K,
        int(np.asarray(ens.feature[:T]).nbytes
            + np.asarray(ens.threshold[:T]).nbytes
            + np.asarray(ens.leaf[:T]).nbytes), table_nodes)
    return _predict_chunked(
        np.asarray(bins),
        lambda part: np.asarray(run(jnp.asarray(part), ens.feature[:T],
                                    ens.threshold[:T], ens.leaf[:T])),
        table_nodes)


def traced_raw_levelwise(params: dict, x, depth: int, K: int):
    """The dense level-wise scoring body as a PURE TRACED function —
    binning included — for cross-stage pipeline fusion
    (core/capture.py): ``params = {feature, threshold, leaf, base,
    edges}`` (the boosterState arrays), ``x`` raw (n, d) features.
    Same math as :func:`predict_raw`'s dense path: per-feature
    ``searchsorted`` binning (NaN -> bin 0, the ``bin_data`` contract)
    then the per-tree test-table walk, all inside the caller's single
    jitted program — no host bin matrix, no per-call table staging."""
    xf = x.astype(jnp.float32)
    edges = params["edges"].astype(jnp.float32)
    bins = jax.vmap(lambda e, c: jnp.searchsorted(e, c, side="left"),
                    in_axes=(0, 1), out_axes=1)(edges, xf)
    bins = jnp.where(jnp.isnan(xf), 0, bins).astype(jnp.int32)
    bins_t = bins.T

    def body(raw, tree):
        f, t, lv = tree
        contrib = jnp.stack(
            [_predict_tree_t(bins_t, f[k], t[k], lv[k], depth=depth)
             for k in range(K)], axis=1)
        return raw + contrib, None

    init = jnp.broadcast_to(
        params["base"].astype(jnp.float32)[None, :],
        (x.shape[0], K))
    raw, _ = jax.lax.scan(body, init, (params["feature"],
                                       params["threshold"],
                                       params["leaf"]))
    return raw


def prob_from_raw(objective: str, raw: np.ndarray) -> np.ndarray:
    """Raw margins -> probabilities (classification) or values (regression)."""
    if objective == "binary":
        p1 = 1.0 / (1.0 + np.exp(-raw[:, 0]))
        return np.stack([1 - p1, p1], axis=1)
    if objective == "multiclass":
        e = np.exp(raw - raw.max(axis=1, keepdims=True))
        return e / e.sum(axis=1, keepdims=True)
    return raw[:, 0]


def predict(ens: TreeEnsemble, x: np.ndarray,
            predict_impl: str = "auto") -> np.ndarray:
    """Probabilities for classification, values for regression."""
    return prob_from_raw(ens.objective,
                         predict_raw(ens, x, predict_impl=predict_impl))
