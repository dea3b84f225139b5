"""Mixture-of-Experts FFN with expert parallelism.

The reference has no expert parallelism (SURVEY.md §2.7: data parallelism
only). This module designs it in TPU-first: token-choice top-k routing with a
static capacity bound, dense one-hot dispatch/combine einsums (Mesh-TF /
Switch-Transformer formulation) — every shape static, every op an MXU matmul,
so XLA can partition the expert dimension over an ``expert`` mesh axis and
insert the dispatch all-to-alls itself when expert weights carry
``P("expert", ...)`` shardings (see models.trainer EP rules).

Routing/auxiliary math runs in float32; expert matmuls in bfloat16.
"""

from __future__ import annotations

import functools
from fractions import Fraction
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from .. import telemetry


class MoEMLP(nn.Module):
    """Capacity-bounded top-k MoE feed-forward block: (B, T, d) -> (B, T, d).

    Tokens overflowing an expert's capacity ``C = capacity_factor * S * k / E``
    are dropped (their combine weight is 0 — residual connections carry them),
    the standard Switch/GShard behavior that keeps shapes static for XLA.

    Sows the Switch load-balancing auxiliary loss under
    ``intermediates/moe_aux_loss``; callers that train MoE models should add
    it to the objective (models.trainer does when ``moeAuxWeight`` > 0).
    """
    num_experts: int
    d_hidden: int
    top_k: int = 2
    capacity_factor: float = 1.25
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, row_mask=None):
        """row_mask: optional (B,) weights; 0-rows (mesh padding, see
        parallel.mesh.pad_batch_to_devices) neither claim expert capacity nor
        contribute to the balancing statistics."""
        B, T, d = x.shape
        S = B * T
        E = self.num_experts
        k = min(self.top_k, E)
        C = max(1, int(self.capacity_factor * S * k / E))
        xf = x.reshape(S, d)
        tok_w = (jnp.repeat(row_mask.astype(jnp.float32), T)
                 if row_mask is not None else jnp.ones((S,), jnp.float32))

        gate_w = self.param("gate", nn.initializers.lecun_normal(), (d, E),
                            jnp.float32)
        # expert weight stacks: leading E axis is what EP shards
        w1 = self.param("expert_w1", nn.initializers.lecun_normal(),
                        (E, d, self.d_hidden), jnp.float32)
        b1 = self.param("expert_b1", nn.initializers.zeros, (E, self.d_hidden),
                        jnp.float32)
        w2 = self.param("expert_w2", nn.initializers.lecun_normal(),
                        (E, self.d_hidden, d), jnp.float32)
        b2 = self.param("expert_b2", nn.initializers.zeros, (E, d),
                        jnp.float32)

        logits = jnp.einsum("sd,de->se", xf.astype(jnp.float32), gate_w)
        probs = jax.nn.softmax(logits, axis=-1)              # (S, E) f32
        gate_vals, sel = lax.top_k(probs, k)                 # (S, k)
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)          # renormalize

        # Switch aux loss: E * sum_e fraction_routed_e * mean_prob_e
        # (fraction from top-1 assignments, prob from the full softmax),
        # averaged over VALID tokens only
        denom = jnp.maximum(tok_w.sum(), 1.0)
        top1 = jax.nn.one_hot(sel[:, 0], E, dtype=jnp.float32)
        frac = (top1 * tok_w[:, None]).sum(0) / denom
        mean_prob = (probs * tok_w[:, None]).sum(0) / denom
        aux = E * jnp.sum(frac * mean_prob)
        self.sow("intermediates", "moe_aux_loss", aux)

        # capacity-bounded dispatch: slot-major priority (all tokens' 1st
        # choice before any 2nd choice), token order within a slot
        counts = jnp.zeros((E,), jnp.float32)
        dispatch = jnp.zeros((S, E, C), jnp.float32)
        combine = jnp.zeros((S, E, C), jnp.float32)
        for j in range(k):                                   # k static, tiny
            oh = jax.nn.one_hot(sel[:, j], E, dtype=jnp.float32)   # (S, E)
            oh = oh * (tok_w > 0)[:, None]    # padding never claims capacity
            pos = counts[None, :] + jnp.cumsum(oh, axis=0) - oh    # (S, E)
            keep = oh * (pos < C)
            slot = jax.nn.one_hot(pos.astype(jnp.int32), C,
                                  dtype=jnp.float32)               # (S, E, C)
            dispatch = dispatch + keep[..., None] * slot
            combine = combine + (gate_vals[:, j][:, None, None]
                                 * keep[..., None] * slot)
            counts = counts + keep.sum(0)

        # expert compute: three MXU einsums over (E, C, ·) buffers
        xin = jnp.einsum("sec,sd->ecd", dispatch.astype(self.dtype),
                         xf.astype(self.dtype))
        h = jnp.einsum("ecd,edh->ech", xin, w1.astype(self.dtype))
        h = nn.gelu(h + b1[:, None, :].astype(self.dtype))
        out = jnp.einsum("ech,ehd->ecd", h, w2.astype(self.dtype))
        out = out + b2[:, None, :].astype(self.dtype)
        y = jnp.einsum("sec,ecd->sd", combine.astype(self.dtype), out)
        return y.reshape(B, T, d).astype(x.dtype)


def read_moe_aux_loss(intermediates) -> jnp.ndarray:
    """Sum every sown ``moe_aux_loss`` leaf in an ``intermediates``
    collection (other sown intermediates are ignored)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(intermediates)
    total = jnp.asarray(0.0, jnp.float32)
    for path, leaf in flat:
        if any("moe_aux_loss" in str(getattr(p, "key", p)) for p in path):
            total = total + jnp.sum(leaf)
    return total


# ------------------------------------------------ dropless, share-aware

#: rows of one grouped product at the least: a tile holds tokens of one
#: expert. `tile_rows` sets a layer's own from its static load, this many
#: times a power of two
GROUP_TILE = 256
#: a tile's rows double while a uniform share (what one held expert gets of
#: evenly routed tokens) still fills this many tiles: a tile re-reads its
#: expert's three matrices and, backward, rewrites their three float32
#: gradient accumulators, so a held expert's assignments in few large tiles
#: move a fraction of the bytes; a share in fewer tiles would leave more of
#: its last one empty. One layer's walk alone at 32,768 tokens, 2048 x 1792,
#: 4,096 assignments a held expert (`tools/time_grouped_mlp.py`, TPU v5e;
#: PERF.md section 6, PR 35), forward / backward loop: 29.1 / 81.1 ms at 256
#: rows, 25.4 / 53.4 at 512, 24.5 / 44.5 at 1,024, 24.1 / 43.4 at 2,048
GROUP_SHARE_TILES = 4
#: and the most rows of a tile: past 1,024 the same sweep gains 3% of the
#: loops for twice a tile's float32 activations
GROUP_TILE_MAX = 1024
#: the tile walk's floor, in uniform shares, where the family states none of
#: its own (`DroplessMoE.floor_shares`): a layer walks at least the tiles
#: that twice its share of evenly routed tokens would fill, so a step's time
#: is the same whatever the routing until the load passes that. At a fresh
#: init the held experts' load swings 0.5-1.9 shares by the seed (PERF.md
#: section 6, PR 28); a balanced load pays for the tiles it leaves empty
GROUP_FLOOR_SHARES = 2


def tile_rows(share: int) -> int:
    """Rows of a layer's tiles from its static load, `share` = tokens a step
    x experts a token // router outputs (what one held expert receives under
    uniform routing): GROUP_TILE times the largest power of two that still
    cuts a share into GROUP_SHARE_TILES tiles, never under GROUP_TILE and
    never over GROUP_TILE_MAX."""
    rows = GROUP_TILE
    while (2 * rows <= GROUP_TILE_MAX
           and 2 * rows * GROUP_SHARE_TILES <= share):
        rows *= 2
    return rows


def floor_tiles(N: int, k: int, E: int, W: int, rows: int,
                shares=GROUP_FLOOR_SHARES) -> int:
    """The least tiles of `rows` a layer walks: what `shares` (a whole
    number or a `Fraction`) uniform shares of its E held experts fill, where
    N tokens take k of W experts each. Whole-number arithmetic throughout."""
    shares = Fraction(shares)
    return -(-shares.numerator * N * k * E
             // (shares.denominator * W * rows))


def _tiles(token, weight, counts, min_tiles, n_tokens, tile):
    """The walk over the assignments sorted by held expert, in tiles of
    `tile` rows of one expert: (number of tiles, tile t -> (expert, first
    row, which rows are real, their tokens, their weights or 0)). Every
    expert's group is cut into ceil(count / tile) tiles, expert after
    expert; the lists are padded by one tile, so a tile that begins at a
    real assignment never runs past their end. The walk has at least
    `min_tiles` tiles: those after the routing's own have no real row. A row
    that is not real carries the token `n_tokens`, one past the last."""
    tiles = (counts + tile - 1) // tile
    ends, first = jnp.cumsum(tiles), jnp.cumsum(counts) - counts
    token, weight = jnp.pad(token, (0, tile)), jnp.pad(weight, (0, tile))

    def fetch(t):
        e = jnp.minimum(jnp.sum(t >= ends), counts.shape[0] - 1)
        off = (t - (ends[e] - tiles[e])) * tile
        start = first[e] + off
        valid = off + jnp.arange(tile) < counts[e]
        tok = jnp.where(valid, lax.dynamic_slice_in_dim(token, start, tile),
                        n_tokens)
        w = jnp.where(valid, lax.dynamic_slice_in_dim(weight, start, tile),
                      0.0)
        return e.astype(jnp.int32), start, valid, tok, w

    return jnp.maximum(ends[-1], min_tiles), fetch


def _take(x, tok):
    """Rows `tok` of x; a row past the end reads as zeros."""
    return x.at[tok].get(mode="fill", fill_value=0)


def _add(y, tok, rows):
    """y with `rows` added at `tok`; a row past the end is left out."""
    return y.at[tok].add(rows, mode="drop")


def _expert(w, e):
    return lax.dynamic_index_in_dim(w, e, 0, keepdims=False)


def grouped_expert_mlp(x, w_gate, w_up, w_down, token, weight, counts,
                       min_tiles, rows=None):
    """SwiGLU experts over the tokens routed to them, nothing dropped.

    x: (N, d) tokens. w_gate, w_up: (E, d, f); w_down: (E, f, d): the E
    experts held here. `token` (R,) int32 and `weight` (R,) float32 list the
    assignments (token, combine weight) sorted by held expert, `counts` (E,)
    how many belong to each; entries after sum(counts) are ignored. Returns
    (y, computed): y (N, d) float32 = sum over a token's assignments of
    weight * expert(x), and the number of assignments computed
    (== sum(counts)).

    The work is a `while` over tiles of `rows` assignments of one expert
    (static, a shape of the program: GROUP_TILE unless the caller gives it;
    `DroplessMoE` gives `tile_rows` of its layer's static load, so that a
    held expert's matrices are read, and their gradients' accumulators
    rewritten, a few times a layer whatever the load). The trip count is the
    number of tiles the routing needs, and at least `min_tiles` (a value):
    shapes are static (R is the worst case, every token on every held
    expert), nothing is dropped however uneven the routing, and up to
    `min_tiles` the time does not follow the load (a tile with no real row
    costs what a full one does). The backward pass is the same walk (a
    dynamic trip count has no transpose of its own); it recomputes a tile's
    hidden activations. The result does not depend on `rows` but for the
    order in which float32 sums are added."""
    return _grouped(x, w_gate, w_up, w_down, token, weight, counts,
                    min_tiles, rows or GROUP_TILE)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8,))
def _grouped(x, w_gate, w_up, w_down, token, weight, counts, min_tiles,
             rows):
    return _grouped_fwd(x, w_gate, w_up, w_down, token, weight, counts,
                        min_tiles, rows)[0]


def _grouped_fwd(x, w_gate, w_up, w_down, token, weight, counts, min_tiles,
                 rows):
    n_tiles, fetch = _tiles(token, weight, counts, min_tiles, x.shape[0],
                            rows)

    def body(c):
        t, y, computed = c
        e, _, valid, tok, w = fetch(t)
        xs = _take(x, tok)
        a = xs @ _expert(w_gate, e)
        b = xs @ _expert(w_up, e)
        o = jnp.dot((jax.nn.silu(a) * b), _expert(w_down, e),
                    preferred_element_type=jnp.float32)
        y = _add(y, tok, o * w[:, None])
        return t + 1, y, computed + jnp.sum(valid, dtype=jnp.int32)

    _, y, computed = lax.while_loop(
        lambda c: c[0] < n_tiles, body,
        (jnp.int32(0), jnp.zeros(x.shape, jnp.float32), jnp.int32(0)))
    return (y, computed), (x, w_gate, w_up, w_down, token, weight, counts,
                           min_tiles)


def _grouped_bwd(rows, res, cts):
    x, w_gate, w_up, w_down, token, weight, counts, min_tiles = res
    dy = cts[0].astype(x.dtype)
    n_tiles, fetch = _tiles(token, weight, counts, min_tiles, x.shape[0],
                            rows)
    f32 = jnp.float32

    def body(c):
        t, dx, dg, du, dd, dwt = c
        e, start, valid, tok, w = fetch(t)
        xs = _take(x, tok)
        wg, wu, wd = _expert(w_gate, e), _expert(w_up, e), _expert(w_down, e)
        a = jnp.dot(xs, wg, preferred_element_type=f32)
        b = jnp.dot(xs, wu, preferred_element_type=f32)
        sig = jax.nn.sigmoid(a)
        h = (a * sig * b).astype(x.dtype)
        do = _take(dy, tok)
        # the combine weight's gradient: <dy, expert(x)>, real rows only
        o = jnp.dot(h, wd, preferred_element_type=f32)
        dw = jnp.where(valid, jnp.sum(o * do.astype(f32), axis=-1), 0.0)
        dwt = lax.dynamic_update_slice_in_dim(dwt, dw, start, 0)
        do = (do.astype(f32) * w[:, None]).astype(x.dtype)
        dh = jnp.dot(do, wd.T, preferred_element_type=f32)
        da = (dh * b * sig * (1.0 + a * (1.0 - sig))).astype(x.dtype)
        db = (dh * a * sig).astype(x.dtype)
        dd = dd.at[e].add(jnp.dot(h.T, do, preferred_element_type=f32))
        dg = dg.at[e].add(jnp.dot(xs.T, da, preferred_element_type=f32))
        du = du.at[e].add(jnp.dot(xs.T, db, preferred_element_type=f32))
        dxs = (jnp.dot(da, wg.T, preferred_element_type=f32)
               + jnp.dot(db, wu.T, preferred_element_type=f32))
        return t + 1, _add(dx, tok, dxs), dg, du, dd, dwt

    R = token.shape[0]
    init = (jnp.int32(0), jnp.zeros(x.shape, f32),
            jnp.zeros(w_gate.shape, f32), jnp.zeros(w_up.shape, f32),
            jnp.zeros(w_down.shape, f32), jnp.zeros((R + rows,), f32))
    _, dx, dg, du, dd, dwt = lax.while_loop(lambda c: c[0] < n_tiles, body,
                                            init)
    return (dx.astype(x.dtype), dg.astype(w_gate.dtype),
            du.astype(w_up.dtype), dd.astype(w_down.dtype), None,
            dwt[:R].astype(weight.dtype), None, None)


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


_m_experts_held = telemetry.registry.counter(
    "mmlspark_moe_experts_held",
    "experts held here by the dropless expert layers built (static in the "
    "configuration: counted at trace time)", labels=("layer",))
_m_router_width = telemetry.registry.counter(
    "mmlspark_moe_router_width",
    "router outputs (experts routed over, here or elsewhere) of the dropless "
    "expert layers built (counted at trace time)", labels=("layer",))
_m_tile_rows = telemetry.registry.gauge(
    "mmlspark_moe_tile_rows",
    "rows of a tile of the dropless expert layer last built (`tile_rows` of "
    "its static load: set at trace time)", labels=("layer",))
_m_floor_tiles = telemetry.registry.gauge(
    "mmlspark_moe_floor_tiles",
    "the least tiles the walk of the dropless expert layer last built takes "
    "(`floor_tiles` of its static load and floor: set at trace time)",
    labels=("layer",))

#: what a dropless expert layer reports a step, in this order
MOE_STEP_STATS = ("moe_tokens_routed", "moe_expert_tokens_max",
                  "moe_tokens_dropped", "moe_tiles_needed",
                  "moe_tiles_walked")


_m_step = {
    "moe_tokens_routed": telemetry.registry.counter(
        "mmlspark_moe_tokens_routed_total",
        "assignments (token, expert) routed to the experts held here, over "
        "the steps read and the expert layers"),
    "moe_tokens_dropped": telemetry.registry.counter(
        "mmlspark_moe_tokens_dropped_total",
        "assignments routed to the experts held here and not computed, over "
        "the steps read: a dropless layer reads 0"),
    "moe_expert_tokens_max": telemetry.registry.gauge(
        "mmlspark_moe_expert_tokens_max",
        "the fullest held expert's assignments, of the last step read"),
}


def observe_step_stats(values: dict):
    """A finished step's `MOE_STEP_STATS` (host ints) into the registry."""
    for name, metric in _m_step.items():
        if name.endswith("_max"):
            metric.set(values[name])
        else:
            metric.inc(values[name])


class SwiGLU(nn.Module):
    """down(silu(gate(x)) * up(x)), no biases."""
    d_hidden: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        dense = functools.partial(nn.Dense, use_bias=False, dtype=self.dtype)
        h = nn.silu(dense(self.d_hidden, name="gate")(x)) \
            * dense(self.d_hidden, name="up")(x)
        return dense(d, name="down")(h)


class DroplessMoE(nn.Module):
    """One expert-parallel rank's part of a token-choice expert layer that
    drops nothing: (B, T, d) -> ((B, T, d), stats).

    The router scores every token against all `router_width` experts of the
    deployment in float32, by the family's `scoring`: "sigmoid" (each
    logit's sigmoid; the top `top_k` by score + `selection_bias`) or
    "softmax" (the softmax over all `router_width` logits, as `laguna`
    routes; the top `top_k` by score, and no `selection_bias`: such a
    router has none); ties to the lower index. The combine weights are the
    chosen scores, renormalised to sum 1 where `renormalize`, times
    `routed_scale`; the renormalisation divides by the sum + `renorm_eps`,
    the family's own: 1e-20 where its source adds that, 1e-6 in `lfm2_moe`.
    The tile walk takes at least `floor_tiles` of `floor_shares` uniform
    shares, also the family's own: GROUP_FLOOR_SHARES where it states none,
    5/4 in `lfm2_moe` and in `laguna`.
    The layer holds the `num_experts` experts from
    `first_expert` on and computes their part of the result, for however
    many tokens chose them (`grouped_expert_mlp`); what the experts held
    elsewhere would add is left out, and no exchange stands in for it. Each
    of `num_shared` shared experts sees every token (0 of them in `lfm2_moe`:
    the routed part is then the whole result). No capacity, no
    auxiliary loss (a sigmoid router's family balances through
    `selection_bias`, a parameter that starts at zero and takes no gradient:
    its update rule is the trainer's to bring).

    `stats` is int32[5], `MOE_STEP_STATS`: assignments routed to the held
    experts, the fullest held expert's, routed minus computed (0), the tiles
    the routing filled and the tiles walked (those or the floor, the
    greater)."""
    num_experts: int              # held here
    router_width: int             # routed over
    d_hidden: int
    top_k: int = 8
    first_expert: int = 0
    num_shared: int = 1
    renormalize: bool = True
    routed_scale: float = 1.0
    dtype: Any = jnp.bfloat16
    renorm_eps: float = 1e-20
    floor_shares: Any = GROUP_FLOOR_SHARES     # int or Fraction
    scoring: str = "sigmoid"                   # sigmoid | softmax

    @nn.compact
    def __call__(self, x, row_mask=None):
        B, T, d = x.shape
        N, E, W, k = B * T, self.num_experts, self.router_width, self.top_k
        if not 0 <= self.first_expert <= W - E:
            raise ValueError(f"experts {self.first_expert}.."
                             f"{self.first_expert + E - 1} are not among the "
                             f"router's {W}")
        _m_experts_held.labels(layer="/".join(self.path)).inc(E)
        _m_router_width.labels(layer="/".join(self.path)).inc(W)
        xf = x.reshape(N, d)

        router = self.param("router", nn.initializers.lecun_normal(), (d, W),
                            jnp.float32)
        if self.scoring == "sigmoid":
            bias = lax.stop_gradient(self.param(
                "selection_bias", nn.initializers.zeros, (W,), jnp.float32))
            scores = jax.nn.sigmoid(jnp.dot(
                xf.astype(jnp.float32), router,
                precision=lax.Precision.HIGHEST))
            _, chosen = lax.top_k(scores + bias, k)              # (N, k)
        elif self.scoring == "softmax":
            scores = jax.nn.softmax(jnp.dot(
                xf.astype(jnp.float32), router,
                precision=lax.Precision.HIGHEST), axis=-1)
            _, chosen = lax.top_k(scores, k)                     # (N, k)
        else:
            raise ValueError("scoring must be 'sigmoid' or 'softmax', got "
                             f"{self.scoring!r}")
        weight = jnp.take_along_axis(scores, chosen, axis=-1)
        if self.renormalize:
            weight = weight / (weight.sum(-1, keepdims=True)
                               + self.renorm_eps)
        weight = weight * self.routed_scale

        # the assignments to the experts held here, sorted by expert
        local = chosen - self.first_expert
        held = (local >= 0) & (local < E)
        if row_mask is not None:       # padded rows are routed nowhere
            held &= jnp.repeat(row_mask > 0, T)[:, None]
        local = jnp.where(held, local, E).reshape(-1)
        order = jnp.argsort(local, stable=True)
        counts = jnp.sum(local[:, None] == jnp.arange(E), axis=0,
                         dtype=jnp.int32)
        init = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1,
                                            batch_axis=(0,))
        w_gate, w_up, w_down = (
            self.param(n, init, shape, jnp.float32).astype(self.dtype)
            for n, shape in (("expert_gate", (E, d, self.d_hidden)),
                             ("expert_up", (E, d, self.d_hidden)),
                             ("expert_down", (E, self.d_hidden, d))))
        rows = tile_rows(N * k // W)
        min_tiles = floor_tiles(N, k, E, W, rows, self.floor_shares)
        _m_tile_rows.labels(layer="/".join(self.path)).set(rows)
        _m_floor_tiles.labels(layer="/".join(self.path)).set(min_tiles)
        y, computed = grouped_expert_mlp(
            xf.astype(self.dtype), w_gate, w_up, w_down,
            (order // k).astype(jnp.int32), weight.reshape(-1)[order], counts,
            min_tiles, rows)
        y = y.astype(self.dtype)
        for i in range(self.num_shared):
            y = y + SwiGLU(self.d_hidden, self.dtype,
                           name=f"shared{i}")(xf)
        routed, needed = jnp.sum(counts), jnp.sum(-(-counts // rows))
        stats = jnp.stack([routed, jnp.max(counts), routed - computed,
                           needed, jnp.maximum(needed, min_tiles)])
        return y.reshape(B, T, d).astype(x.dtype), stats
