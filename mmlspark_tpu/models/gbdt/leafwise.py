"""Leaf-wise (best-first) tree growth with categorical splits.

Native LightGBM grows trees best-first: repeatedly split the leaf with the
highest gain until ``num_leaves`` leaves exist (the reference exposes
``numLeaves``, default 31 — lightgbm/.../LightGBMParams.scala:34; the boost
loop that drives it is LGBM_BoosterUpdateOneIter, TrainUtils.scala:63-77).
That is inherently data-dependent control flow, which XLA can't trace — so
the TPU formulation fixes the shape of the work instead of the shape of the
tree:

  * exactly ``num_leaves - 1`` split rounds run under one ``lax.scan``;
  * each round argmaxes a per-leaf candidate cache (gain, feature,
    threshold/category-set), splits that leaf, and rebuilds candidates for
    ONLY the two fresh leaves with a single full-data histogram pass
    (rows outside the split leaf land in a discard slot — the static-shape
    equivalent of LightGBM walking just the leaf's row index list);
  * a leaf whose best gain can't clear ``min_split_gain`` is retired
    (its cache entry pinned to -inf), so exhausted trees finish early as
    no-op rounds — same result as LightGBM's early exit, fixed shapes.

Trees are recorded as the SPLIT SEQUENCE itself: round r splits leaf
``split_leaf[r]`` and the right child becomes leaf id r+1. Prediction
replays the sequence with a scan — num_leaves-1 masked updates, fully
vectorized over rows.

Categorical features split as category SETS (LightGBM's many-vs-many):
per (leaf, feature) the category bins sort by grad/hess ratio and a prefix
scan over the sorted order finds the optimal partition (the classic
exact-for-convex-loss trick LightGBM uses); the winning set is stored as a
256-bit bitmask per split. Categorical feature ids come from the column
metadata contract (core/schema.py CategoricalUtilities -> FastVectorAssembler
slot ranges), the reference's MML categorical-metadata path.

Data-parallel mode: the same grow program runs inside shard_map with rows
sharded; per-round histograms and final leaf sums psum over ICI — the
socket all-reduce ring of TrainUtils.scala:141 as XLA collectives.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

#: 256 bits of category membership per split (max_bin <= 256)
CAT_WORDS = 8


class LeafwiseEnsemble(NamedTuple):
    """Fitted leaf-wise booster. T trees x K classes; L = num_leaves.

    split_leaf: (T,K,L-1) int32 — leaf id split at round r (-1 = no-op)
    feature:    (T,K,L-1) int32 — split feature
    threshold:  (T,K,L-1) int32 — numeric split bin (right if bin > thr)
    cat_bitset: (T,K,L-1,CAT_WORDS) uint32 — category set routed right
    is_cat:     (T,K,L-1) bool
    leaf:       (T,K,L) f32 — leaf values (learning rate applied)
    """
    split_leaf: jnp.ndarray
    feature: jnp.ndarray
    threshold: jnp.ndarray
    cat_bitset: jnp.ndarray
    is_cat: jnp.ndarray
    leaf: jnp.ndarray
    bin_edges: np.ndarray
    cat_features: np.ndarray      # (d,) bool
    base: np.ndarray
    objective: str


def _soft(gsum, l1):
    return jnp.sign(gsum) * jnp.maximum(jnp.abs(gsum) - l1, 0.0)


def _leaf_score(gsum, hsum, l2, l1):
    gs = _soft(gsum, l1)
    return gs * gs / (hsum + l2)


def _candidates_2(hg, hh, feat_mask, cat_feats, n_bins, l2, l1,
                  min_child_weight, cat_smooth, has_cats: bool = True):
    """Best split per node from (2, d, B) histograms, numeric AND
    categorical forms evaluated per feature.

    Returns per node: gain (2,), feat (2,), thr (2,) (numeric bin or
    sorted-prefix length for categorical), bitset (2, CAT_WORDS) uint32.

    ``has_cats=False`` (static, the common no-categorical fit) skips the
    whole categorical arm — the per-round argsort/re-rank over
    (2, d, B) ran unconditionally and was pure overhead when
    ``cat_feats`` is all-zero.
    """
    n_nodes, d, B = hg.shape

    gt = hg.sum(axis=2, keepdims=True)
    ht = hh.sum(axis=2, keepdims=True)
    parent = _leaf_score(gt, ht, l2, l1)

    # ---- numeric: prefix over the natural (value-ordered) bin axis ----
    gl = jnp.cumsum(hg, axis=2)
    hl = jnp.cumsum(hh, axis=2)
    gain_n = (_leaf_score(gl, hl, l2, l1)
              + _leaf_score(gt - gl, ht - hl, l2, l1) - parent)
    valid_n = (hl >= min_child_weight) & (ht - hl >= min_child_weight)
    gain_n = jnp.where(valid_n, gain_n, -jnp.inf)
    gain_n = gain_n.at[:, :, -1].set(-jnp.inf)  # all-left split is no split
    bin_n = jnp.argmax(gain_n, axis=2)
    best_n = jnp.take_along_axis(gain_n, bin_n[:, :, None], axis=2)[:, :, 0]

    if not has_cats:
        gain_f = jnp.where(feat_mask[None, :] > 0, best_n, -jnp.inf)
        bf = jnp.argmax(gain_f, axis=1)
        gain = jnp.take_along_axis(gain_f, bf[:, None], axis=1)[:, 0]
        thr = jnp.take_along_axis(bin_n, bf[:, None], axis=1)[:, 0]
        return (gain, bf.astype(jnp.int32), thr.astype(jnp.int32),
                jnp.zeros((n_nodes, CAT_WORDS), dtype=jnp.uint32))

    # ---- categorical: prefix over bins sorted by grad/hess ratio ----
    ratio = hg / (hh + cat_smooth)
    order = jnp.argsort(ratio, axis=2)              # ascending
    sg = jnp.take_along_axis(hg, order, axis=2)
    sh = jnp.take_along_axis(hh, order, axis=2)
    cgl = jnp.cumsum(sg, axis=2)
    chl = jnp.cumsum(sh, axis=2)
    gain_c = (_leaf_score(cgl, chl, l2, l1)
              + _leaf_score(gt - cgl, ht - chl, l2, l1) - parent)
    valid_c = (chl >= min_child_weight) & (ht - chl >= min_child_weight)
    gain_c = jnp.where(valid_c, gain_c, -jnp.inf)
    gain_c = gain_c.at[:, :, -1].set(-jnp.inf)
    k_c = jnp.argmax(gain_c, axis=2)                # prefix END index
    best_c = jnp.take_along_axis(gain_c, k_c[:, :, None], axis=2)[:, :, 0]

    # ---- per-feature choice, then per-node argmax over features ----
    is_cat = cat_feats[None, :] > 0
    gain_f = jnp.where(is_cat, best_c, best_n)
    gain_f = jnp.where(feat_mask[None, :] > 0, gain_f, -jnp.inf)
    bf = jnp.argmax(gain_f, axis=1)                          # (2,)
    gain = jnp.take_along_axis(gain_f, bf[:, None], axis=1)[:, 0]
    thr_f = jnp.where(is_cat, k_c, bin_n)
    thr = jnp.take_along_axis(thr_f, bf[:, None], axis=1)[:, 0]

    # winner bitset: categories in the winning feature's sorted prefix
    # [0..thr] route LEFT -> the RIGHT set is ranks > thr. Store the RIGHT
    # set so numeric and categorical routing agree ("right when test hits").
    win_order = jnp.take_along_axis(
        order, bf[:, None, None], axis=1)[:, 0, :]           # (2, B)
    ranks = jnp.argsort(win_order, axis=1)                   # bin -> rank
    member = ranks > thr[:, None]                            # (2, B) bool
    bits = jnp.arange(B, dtype=jnp.uint32)
    word_id = (bits >> 5).astype(jnp.int32)
    bit_in_word = jnp.uint32(1) << (bits & jnp.uint32(31))
    bitset = jnp.zeros((n_nodes, CAT_WORDS), dtype=jnp.uint32)
    contrib = jnp.where(member, bit_in_word[None, :], jnp.uint32(0))
    # pack the membership bits into words (8-way static loop; bins within a
    # word have distinct bit values so a sum is an OR)
    for w in range(CAT_WORDS):
        in_w = (word_id == w)
        word_val = jnp.where(in_w[None, :], contrib,
                             jnp.uint32(0)).sum(axis=1, dtype=jnp.uint32)
        bitset = bitset.at[:, w].set(word_val)
    return gain, bf.astype(jnp.int32), thr.astype(jnp.int32), bitset


def _bit_test(bitset_row, rb):
    """bitset_row (CAT_WORDS,) uint32, rb (n,) int32 -> (n,) bool."""
    word = bitset_row[(rb >> 5)]
    return ((word >> (rb & 31).astype(jnp.uint32)) & jnp.uint32(1)) == 1


def grow_tree_leafwise(bins, g, h, *, num_leaves: int, n_bins: int,
                       cat_feats, feat_mask, lambda_l2, lambda_l1,
                       min_child_weight, min_split_gain, cat_smooth: float,
                       max_depth: int = 0, hist_impl: str = "segment",
                       axis_name: Optional[str] = None,
                       has_cats: bool = True):
    """One leaf-wise tree. bins (n, d) int; g/h (n,) f32 (already masked).

    Returns (split_leaf (L-1,), feature (L-1,), threshold (L-1,),
    cat_bitset (L-1, CAT_WORDS), is_cat (L-1,), leaf (L,)).
    """
    from .engine import _histograms

    n, d = bins.shape
    L = num_leaves
    cat_feats = jnp.asarray(cat_feats, jnp.float32)
    neg_inf = jnp.float32(-jnp.inf)
    # the transposed bin matrix feeds the mxu histogram kernel; hoisted out
    # of the scan so it is materialized once per tree, not once per round
    bins_t = (bins.T.astype(jnp.int32) if hist_impl == "mxu" else None)

    def hist_pair(node, a, b):
        """Histograms for leaves a and b in ONE pass; other rows discard."""
        ids = jnp.where(node == a, 0, jnp.where(node == b, 1, 2)) \
            .astype(jnp.int32)
        hg, hh = _histograms(bins, g, h, ids, 3, n_bins, hist_impl,
                             bins_t=bins_t)
        if axis_name is not None:
            hg = jax.lax.psum(hg, axis_name)
            hh = jax.lax.psum(hh, axis_name)
        return hg[:2], hh[:2]

    def cand_pair(node, a, b):
        hg, hh = hist_pair(node, a, b)
        return _candidates_2(hg, hh, feat_mask, cat_feats, n_bins,
                             lambda_l2, lambda_l1, min_child_weight,
                             cat_smooth, has_cats=has_cats)

    node0 = jnp.zeros(n, dtype=jnp.int32)
    g0, f0, t0, w0 = cand_pair(node0, 0, -1)   # root candidates (slot 0)
    cg = jnp.full(L, neg_inf).at[0].set(g0[0])
    cf = jnp.zeros(L, jnp.int32).at[0].set(f0[0])
    ct = jnp.zeros(L, jnp.int32).at[0].set(t0[0])
    cw = jnp.zeros((L, CAT_WORDS), jnp.uint32).at[0].set(w0[0])
    dep = jnp.zeros(L, jnp.int32)

    def round_fn(carry, r):
        node, cg, cf, ct, cw, dep = carry
        s = jnp.argmax(cg).astype(jnp.int32)
        ok = cg[s] > min_split_gain
        f, t, w = cf[s], ct[s], cw[s]
        rb = bins[jnp.arange(n), f].astype(jnp.int32)
        if has_cats:
            f_is_cat = cat_feats[f] > 0
            right = jnp.where(f_is_cat, _bit_test(w, rb), rb > t)
        else:
            f_is_cat = jnp.bool_(False)
            right = rb > t
        right = right & (node == s) & ok
        node = jnp.where(right, r + 1, node)

        rec = (jnp.where(ok, s, -1), f, t, w, f_is_cat & ok)

        gain2, f2, t2, w2 = cand_pair(node, s, r + 1)
        childdep = dep[s] + 1
        depth_ok = (max_depth == 0) | (childdep < max_depth)
        gain2 = jnp.where(depth_ok, gain2, neg_inf)
        cg = cg.at[s].set(jnp.where(ok, gain2[0], neg_inf))
        cg = cg.at[r + 1].set(jnp.where(ok, gain2[1], neg_inf))
        cf = cf.at[s].set(jnp.where(ok, f2[0], cf[s]))
        cf = cf.at[r + 1].set(f2[1])
        ct = ct.at[s].set(jnp.where(ok, t2[0], ct[s]))
        ct = ct.at[r + 1].set(t2[1])
        cw = cw.at[s].set(jnp.where(ok, w2[0], cw[s]))
        cw = cw.at[r + 1].set(w2[1])
        dep = dep.at[s].set(jnp.where(ok, childdep, dep[s]))
        dep = dep.at[r + 1].set(childdep)
        return (node, cg, cf, ct, cw, dep), rec

    (node, *_), (S, F, T, W, IC) = jax.lax.scan(
        round_fn, (node0, cg, cf, ct, cw, dep),
        jnp.arange(L - 1, dtype=jnp.int32))

    from ...ops.pallas_kernels import node_sums
    lg, lh = node_sums(node, g, h, L, impl=hist_impl)
    if axis_name is not None:
        lg = jax.lax.psum(lg, axis_name)
        lh = jax.lax.psum(lh, axis_name)
    leaf = -_soft(lg, lambda_l1) / (lh + lambda_l2)
    # node (each row's final leaf) goes back too: the boosting loop's raw
    # update is then a free (L,)-table gather instead of replaying the
    # whole split sequence over the training set every iteration
    return (S.astype(jnp.int32), F, T, W, IC, leaf, node)


@functools.partial(jax.jit, static_argnames=(
    "num_leaves", "n_bins", "max_depth", "hist_impl", "has_cats"))
def build_tree_leafwise_multi(bins, grad, hess, row_mask, feat_mask,
                              cat_feats, *, num_leaves, n_bins, lambda_l2,
                              lambda_l1, min_child_weight, min_split_gain,
                              cat_smooth, max_depth, hist_impl="segment",
                              has_cats=True):
    """K leaf-wise trees per boosting iter over the class axis (a Python
    unroll, not vmap — see engine._stack_class_axis; K=1 except
    multiclass)."""
    from .engine import _stack_class_axis

    def one(g, h):
        return grow_tree_leafwise(
            bins, g * row_mask, h * row_mask, num_leaves=num_leaves,
            n_bins=n_bins, cat_feats=cat_feats, feat_mask=feat_mask,
            lambda_l2=lambda_l2, lambda_l1=lambda_l1,
            min_child_weight=min_child_weight,
            min_split_gain=min_split_gain, cat_smooth=cat_smooth,
            max_depth=max_depth, hist_impl=hist_impl, has_cats=has_cats)
    return _stack_class_axis([one(grad[:, k], hess[:, k])
                              for k in range(grad.shape[1])])


def make_sharded_builder_lw(mesh, *, num_leaves, n_bins, lambda_l2,
                            lambda_l1, min_child_weight, min_split_gain,
                            cat_smooth, max_depth, hist_impl="segment",
                            axis_name: str = "data", has_cats=True):
    """Data-parallel leaf-wise builder: rows sharded over `axis_name`,
    per-round histograms + leaf sums psum'ed (the LightGBM data-parallel
    ring, TrainUtils.scala:141, as ICI collectives)."""
    from jax.sharding import PartitionSpec as P

    def body(bins, g, h, rm, fm, cat):
        from .engine import _stack_class_axis

        def one(g1, h1):
            return grow_tree_leafwise(
                bins, g1 * rm, h1 * rm, num_leaves=num_leaves,
                n_bins=n_bins, cat_feats=cat, feat_mask=fm,
                lambda_l2=lambda_l2, lambda_l1=lambda_l1,
                min_child_weight=min_child_weight,
                min_split_gain=min_split_gain, cat_smooth=cat_smooth,
                max_depth=max_depth, hist_impl=hist_impl,
                axis_name=axis_name, has_cats=has_cats)
        return _stack_class_axis([one(g[:, k], h[:, k])
                                  for k in range(g.shape[1])])

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis_name, None), P(axis_name, None), P(axis_name, None),
                  P(axis_name), P(None), P(None)),
        # tree arrays replicate; the per-row node assignment stays sharded
        # like the rows it describes
        out_specs=(P(None), P(None), P(None), P(None), P(None), P(None),
                   P(None, axis_name)),
        check_vma=False)
    return jax.jit(fn)


#: precomputed (L-1, n) test tables stop at this many splits: a 4096-leaf
#: tree scoring millions of rows would stage multi-GB tables (ADVICE r5);
#: wider trees replay with per-round on-the-fly row DMAs instead
#: (mirrors engine._TEST_TABLE_MAX_NODES).
_TEST_TABLE_MAX_SPLITS = 255


def _tree_tests_lw(bins_t, F, T, W, IC, has_cats: bool = True):
    """All of one tree's split tests in one shot: (L-1, n) bool.

    ``jnp.take(bins_t, F, axis=0)`` is L-1 contiguous row DMAs from the
    TRANSPOSED bin matrix — the round-5 scoring fix. The old replay
    gathered ``bins[arange(n), f]`` inside the scan, a per-row vector
    gather per split step: 100 trees x 30 steps of ~15 ms measured
    48.9 s for a 1M-row leaf-wise scoring pass; precomputing the tests
    turns the scan body into pure elementwise work. The working set is
    the (L-1, n) bool table (callers scoring very large n with very
    large num_leaves should batch rows — the stage transform path
    already does via miniBatchSize); rows stay uint8, upcasts fuse into
    the per-op compares. ``has_cats=False`` (static) compiles out the
    categorical bitset arm, as the training path does."""
    rows = jnp.take(bins_t, F, axis=0)                       # (L-1, n)
    num_t = rows > T[:, None]
    if not has_cats:
        return num_t
    # categorical bitset test, word selected by an 8-way compare (no
    # per-row gather): word k of each split's 256-bit set
    widx = rows >> 5
    word = jnp.zeros(rows.shape, jnp.uint32)
    for k in range(CAT_WORDS):
        word = jnp.where(widx == k, W[:, k][:, None], word)
    cat_t = ((word >> (rows & 31).astype(jnp.uint32))
             & jnp.uint32(1)) == 1
    return jnp.where(IC[:, None], cat_t, num_t)


def _replay_lw(tests, S, leaf):
    """Replay the split sequence over precomputed tests: (n,) leaves."""
    n = tests.shape[1]
    L1 = S.shape[0]

    def body(pos, xs):
        new_id, s, test_row = xs
        right = (pos == s) & (s >= 0) & test_row
        return jnp.where(right, new_id, pos), None

    pos, _ = jax.lax.scan(
        body, jnp.zeros(n, jnp.int32),
        (jnp.arange(1, L1 + 1, dtype=jnp.int32), S, tests))
    return leaf[pos]


def _replay_lw_streaming(bins_t, S, F, T, W, IC, leaf,
                         has_cats: bool = True):
    """Replay WITHOUT the test table: each round DMAs its one split
    feature's row from bins_t inside the scan — O(n) live memory however
    many leaves the tree has (the memory guard for trees past
    _TEST_TABLE_MAX_SPLITS). Still a contiguous row read per round (the
    round-5 transposed-matrix win), just not batched across rounds."""
    n = bins_t.shape[1]
    L1 = S.shape[0]

    def body(pos, xs):
        new_id, s, f, t, w, ic = xs
        rb = jnp.take(bins_t, f, axis=0).astype(jnp.int32)     # (n,)
        test = rb > t
        if has_cats:
            word = w[(rb >> 5)]
            cat_t = ((word >> (rb & 31).astype(jnp.uint32))
                     & jnp.uint32(1)) == 1
            test = jnp.where(ic, cat_t, test)
        right = (pos == s) & (s >= 0) & test
        return jnp.where(right, new_id, pos), None

    pos, _ = jax.lax.scan(
        body, jnp.zeros(n, jnp.int32),
        (jnp.arange(1, L1 + 1, dtype=jnp.int32), S, F, T, W, IC))
    return leaf[pos]


@functools.partial(jax.jit, static_argnames=("has_cats",))
def predict_tree_lw_t(bins_t, S, F, T, W, IC, leaf, has_cats: bool = True):
    """One tree's predictions from the TRANSPOSED bin matrix (d, n)."""
    if S.shape[0] > _TEST_TABLE_MAX_SPLITS:
        return _replay_lw_streaming(bins_t, S, F, T, W, IC, leaf,
                                    has_cats=has_cats)
    return _replay_lw(_tree_tests_lw(bins_t, F, T, W, IC,
                                     has_cats=has_cats), S, leaf)


@functools.partial(jax.jit, static_argnames=("has_cats",))
def predict_tree_lw(bins, S, F, T, W, IC, leaf, has_cats: bool = True):
    """Replay one tree's split sequence: bins (n,d) -> (n,) leaf values.
    Row-major convenience wrapper over predict_tree_lw_t (callers scoring
    many trees should transpose once and use the _t form)."""
    return predict_tree_lw_t(bins.T, S, F, T, W, IC, leaf,
                             has_cats=has_cats)


def quantize_ensemble_lw(ens: LeafwiseEnsemble,
                         num_iteration: Optional[int] = None,
                         leaf_dtype: str = "bf16"):
    """Leaf-wise ensemble -> SoA quantized tables: ``(split_leaf i32,
    feature u8, threshold u8, leaf)`` — leaf bf16, or a per-tree-scaled
    ``(int8, f32 scale)`` pair under ``leaf_dtype='int8'`` (see
    engine.quantize_leaves_int8). Numeric splits only (the
    caller gates categorical ensembles onto the dense path — bitset
    tests don't reduce to the uint8 compare). Same exactness argument
    as engine.quantize_ensemble: only the leaf round is lossy."""
    from .engine import quantize_leaves_int8
    if leaf_dtype not in ("bf16", "int8"):
        raise ValueError(f"leaf_dtype must be bf16|int8, got {leaf_dtype!r}")
    T = ens.feature.shape[0]
    T = min(T, num_iteration) if num_iteration else T
    d = ens.bin_edges.shape[0]
    if d > 256:
        raise ValueError(f"quantized predict tables need <= 256 features "
                         f"(uint8 feature ids), got {d}")
    leaf = (quantize_leaves_int8(np.asarray(ens.leaf[:T]))
            if leaf_dtype == "int8"
            else jnp.asarray(ens.leaf[:T]).astype(jnp.bfloat16))
    return (np.asarray(ens.split_leaf[:T]).astype(np.int32),
            np.asarray(ens.feature[:T]).astype(np.uint8),
            np.minimum(np.asarray(ens.threshold[:T]), 255).astype(np.uint8),
            leaf)


def _quant_eligible_lw(ens: LeafwiseEnsemble, has_cats: bool):
    from ...ops.pallas_kernels import (PREDICT_QUANT_MAX_LEAVES,
                                       PREDICT_QUANT_MAX_NODES)
    if has_cats:
        return False, ("categorical bitset splits stay on the dense path")
    d = ens.bin_edges.shape[0]
    if d > 256:
        return False, f"{d} features exceed the uint8 feature-id space"
    splits = int(ens.split_leaf.shape[2])
    if splits > PREDICT_QUANT_MAX_NODES \
            or splits + 1 > PREDICT_QUANT_MAX_LEAVES:
        return False, (f"{splits + 1} leaves exceed the kernel's unroll "
                       f"cap ({PREDICT_QUANT_MAX_NODES} splits)")
    return True, ""


def _predict_quant_lw(ens: LeafwiseEnsemble, bins: np.ndarray,
                      T: int, leaf_dtype: str = "bf16") -> np.ndarray:
    from .engine import (_predict_chunked, _set_predict_traffic_gauge,
                         dequant_leaf, leaf_table_bytes)
    from ...ops.pallas_kernels import gbdt_predict_quant_leafwise
    from ... import telemetry
    S, F, Th, leaf = quantize_ensemble_lw(ens, T, leaf_dtype=leaf_dtype)
    K = F.shape[1]
    n, d = bins.shape
    base = jnp.asarray(ens.base)[None, :].astype(jnp.float32)
    table_bytes = S.nbytes + F.nbytes + Th.nbytes + leaf_table_bytes(leaf)
    _set_predict_traffic_gauge(n, d, K, table_bytes, 0)
    leaf_f32 = dequant_leaf(leaf)

    @jax.jit
    def run(part):
        contrib = gbdt_predict_quant_leafwise(part.T, S, F, Th, leaf_f32)
        return contrib + base

    prof = telemetry.profiler.wrap(run, "gbdt.predict_quant")
    return _predict_chunked(
        np.asarray(bins), lambda part: np.asarray(prof(jnp.asarray(part))),
        d + 4 * K)


def predict_raw_lw(ens: LeafwiseEnsemble, bins,
                   num_iteration: Optional[int] = None,
                   predict_impl: str = "auto") -> np.ndarray:
    """Raw scores (n, K) for a leaf-wise ensemble from binned features.
    Rows batch past the test-table byte cap (engine._predict_chunked) so
    wide-leaf ensembles score huge inputs at bounded HBM. ``predict_impl``
    mirrors engine.predict_raw: dense | pallas (quantized SoA tables +
    the tile-resident kernel; numeric splits only) | auto."""
    from .engine import _predict_chunked, _resolve_predict_impl
    T, K = ens.feature.shape[:2]
    T = min(T, num_iteration) if num_iteration else T

    has_cats = bool(np.asarray(ens.cat_features).any())
    eligible, why = _quant_eligible_lw(ens, has_cats)
    resolved = _resolve_predict_impl(predict_impl, eligible, why)
    if resolved in ("pallas", "pallas_int8"):
        return _predict_quant_lw(
            ens, np.asarray(bins), T,
            leaf_dtype="int8" if resolved == "pallas_int8" else "bf16")

    @jax.jit
    def run(bins, S, F, Th, W, IC, leaf):
        bins_t = bins.T              # once per scoring call, not per tree
        def body(raw, tree):
            s, f, t, w, ic, lv = tree
            contrib = jnp.stack(
                [predict_tree_lw_t(bins_t, s[k], f[k], t[k], w[k], ic[k],
                                   lv[k], has_cats=has_cats)
                 for k in range(K)], axis=1)
            return raw + contrib, None
        init = jnp.broadcast_to(jnp.asarray(ens.base)[None, :],
                                (bins.shape[0], K)).astype(jnp.float32)
        raw, _ = jax.lax.scan(body, init, (S, F, Th, W, IC, leaf))
        return raw

    splits = int(ens.split_leaf.shape[2])
    table_nodes = splits if splits <= _TEST_TABLE_MAX_SPLITS else 1
    from .engine import _set_predict_traffic_gauge
    _set_predict_traffic_gauge(
        bins.shape[0], ens.bin_edges.shape[0], K,
        int(sum(np.asarray(a[:T]).nbytes
                for a in (ens.split_leaf, ens.feature, ens.threshold,
                          ens.cat_bitset, ens.is_cat, ens.leaf))),
        table_nodes)
    return _predict_chunked(
        np.asarray(bins),
        lambda part: np.asarray(run(jnp.asarray(part), ens.split_leaf[:T],
                                    ens.feature[:T], ens.threshold[:T],
                                    ens.cat_bitset[:T], ens.is_cat[:T],
                                    ens.leaf[:T])),
        table_nodes)
