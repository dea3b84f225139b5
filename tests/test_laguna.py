"""The `laguna` family against its plain reference, on the CPU at small sizes
with seeded weights.

The reference is `benchmark/reference/laguna.py` (jax.numpy, float32,
nothing of the program imported): the rotation from the angle formula with
its own YaRN frequencies, grouped-query attention as a full masked softmax
over the band, the gate and the softmax router written out, the expert
layer as a loop over the held experts, the untied head over whole (T, V)
logits. The program runs here in float32 too, so every tolerance below is
the room two orders of float32 summation need (1e-5 relative on values of
order one, a little more through a backward pass or three optimizer steps),
never a precision's: a wrong term reads 1e-2 and more.
"""

import functools
import hashlib
import json
import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.reference import laguna as ref                 # noqa: E402
from mmlspark_tpu import telemetry                            # noqa: E402
from mmlspark_tpu.models import TpuLearner, build_model       # noqa: E402
from mmlspark_tpu.models import kimi_linear as kl             # noqa: E402
from mmlspark_tpu.models import laguna as lg                  # noqa: E402
from mmlspark_tpu.models.modules import (TOKEN_MODELS,        # noqa: E402
                                         example_input, has_experts)
from mmlspark_tpu.models.moe import DroplessMoE, SwiGLU       # noqa: E402

F32 = jnp.float32
KINDS = ["full_attention", "sliding_attention", "full_attention"]


def cell_rope():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "laguna_s_2p1.json")) as f:
        return json.load(f)["rope_parameters"]


def small_config(**over):
    """Three layers, each kind of the benchmark's cut in small (full +
    dense, sliding + experts, full + experts), 2 / 3 query heads over one
    key/value head of 16 lanes, 4 of 16 experts and a shared one, a window
    of 5 keys on rows of 13, the cell's two rope blocks (YaRN turns 8 of the
    16 lanes)."""
    cfg = {"type": "laguna", "vocab_size": 64, "hidden_size": 32,
           "num_hidden_layers": 3, "layer_types": KINDS,
           "num_attention_heads_per_layer": [2, 3, 2],
           "num_key_value_heads": 1, "head_dim": 16,
           "intermediate_size": 96, "dense_columns_held": 48,
           "moe_intermediate_size": 16,
           "shared_expert_intermediate_size": 16, "num_experts": 4,
           "router_width": 16, "first_expert_held": 0,
           "num_experts_per_tok": 4, "norm_topk_prob": True,
           "moe_routed_scaling_factor": 2.5, "rms_norm_eps": 1e-6,
           "mlp_only_layers": [0],
           "mlp_layer_types": ["dense", "sparse", "sparse"],
           "sliding_window": 5, "rope_parameters": cell_rope(),
           "lm_loss_chunk": 8, "dtype": "float32"}
    cfg.update(over)
    return cfg


def close(a, b, tol):
    """Largest difference over the larger of the reference's scale and 1."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape
    assert np.max(np.abs(a - b)) <= tol * max(1.0, np.max(np.abs(b))), \
        np.max(np.abs(a - b))


def trees_close(a, b, tol):
    la = jax.tree_util.tree_flatten_with_path(a)[0]
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for (path, x), y in zip(la, lb):
        try:
            close(x, y, tol)
        except AssertionError as e:
            raise AssertionError(f"{jax.tree_util.keystr(path)}: {e}")


def tokens(B=4, T=13, vocab=64, seed=0):
    return np.random.default_rng(seed).integers(0, vocab, (B, T)).astype(
        np.int32)


# ------------------------------------------------------------- the rotary

def test_yarn_frequencies_and_factor_by_hand():
    """The full layers' YaRN at the cell's settings (theta 500,000, factor
    128 over 8,192 positions, beta 32 / 1, 64 of 128 lanes): the correction
    dims are 64 ln(8192 / (2 pi 32)) / (2 ln 500000) = 9.04 and 64 ln(8192 /
    2 pi) / (2 ln 500000) = 17.49, floored and ceiled to 9 and 18; pair i
    turns at 500000^(-i / 32) up to i = 9, at that over 128 from i = 18,
    and between them at the blend with the ramp (i - 9) / 9; cos and sin
    scale by 0.1 ln 128 + 1, which is the file's attention_factor. The
    sliding layers' default rotary turns all 64 pairs at 10000^(-i / 64).
    The reference computes the same numbers apart."""
    rope = cell_rope()
    yarn = lg.rotary(128, rope["full_attention"])
    assert (yarn.kind, yarn.lanes) == ("yarn", 64)
    assert abs(yarn.scale - (0.1 * math.log(128) + 1)) < 1e-15
    assert yarn.scale == rope["full_attention"]["attention_factor"]
    dims = [64 * math.log(8192 / (2 * math.pi * b)) / (2 * math.log(5e5))
            for b in (32, 1)]
    assert (math.floor(dims[0]), math.ceil(dims[1])) == (9, 18)
    inv = np.asarray(yarn.inv_freq)
    base = 5e5 ** -(np.arange(32) / 32)
    np.testing.assert_allclose(inv[:10], base[:10], rtol=1e-7)
    np.testing.assert_allclose(inv[18:], base[18:] / 128, rtol=1e-7)
    ramp = (12 - 9) / 9
    assert abs(inv[12] / (base[12] / 128 * ramp + base[12] * (1 - ramp))
               - 1) < 1e-7
    plain = lg.rotary(128, rope["sliding_attention"])
    assert (plain.kind, plain.lanes, plain.scale) == ("default", 128, 1.0)
    np.testing.assert_allclose(plain.inv_freq,
                               1e4 ** -(np.arange(64) / 64), rtol=1e-7)
    for kind, got in (("full_attention", yarn),
                      ("sliding_attention", plain)):
        R, want, scale = ref.frequencies(128, rope[kind])
        assert (R, scale) == (got.lanes, got.scale)
        np.testing.assert_array_equal(np.asarray(got.inv_freq, np.float32),
                                      want)


@pytest.mark.parametrize("kind", ["full_attention", "sliding_attention"])
def test_rotation_turns_its_lanes_and_passes_the_rest(kind):
    """`rotate` against the reference's formula, and the lanes past the
    rotated ones unchanged; the rotated part of a score depends on the
    distance of the two positions alone, scaled by the factor squared."""
    rope = cell_rope()[kind]
    rot = lg.rotary(128, rope)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 37, 3, 128), F32)
    got = lg.rotate(x, rot)
    close(got, ref.rotate(x, 128, rope), 2e-6)
    np.testing.assert_array_equal(got[..., rot.lanes:], x[..., rot.lanes:])
    # one vector at every position: its scores with itself at distance 5
    v = jnp.broadcast_to(x[:1, :1, :1], (1, 37, 1, 128))[..., :rot.lanes]
    r = lg.rotate(jnp.pad(v, ((0, 0),) * 3 + ((0, 128 - rot.lanes),)), rot)
    r = r[..., :rot.lanes]
    s = jnp.sum(r[0, 5:, 0] * r[0, :-5, 0], axis=-1)
    close(s, jnp.full_like(s, s[0]), 1e-5)
    close(jnp.sum(r[0, 0, 0] ** 2), rot.scale ** 2 * jnp.sum(v[0, 0, 0] ** 2),
          1e-5)


# ---------------------------------------------------- the attention layer

def make_attention(cfg, kind, heads, impl="blockwise"):
    window = cfg["sliding_window"] if kind == "sliding_attention" else None
    return lg.GatedGQALayer(
        heads, cfg["num_key_value_heads"], cfg["head_dim"],
        kl.causal_attention(impl, 8, window),
        lg.rotary(cfg["head_dim"], cfg["rope_parameters"][kind]), kind, F32)


@pytest.mark.parametrize("impl", ["blockwise", "flash"])
@pytest.mark.parametrize("kind,heads", [("full_attention", 6),
                                        ("sliding_attention", 9)])
def test_attention_layer_matches_reference(kind, heads, impl):
    """Gated grouped-query attention (6 or 9 query heads over one, the
    cell's groups) against the written-out softmax over the band, values and
    gradients, on the blockwise path (K and V repeated) and through the
    flash kernels (the sliding layer's windowed)."""
    cfg = small_config()
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 13, 32), F32)
    layer = make_attention(cfg, kind, heads, impl)
    p = layer.init(jax.random.PRNGKey(9), x)
    assert p["params"]["g_proj"]["kernel"].shape == (32, heads)
    assert p["params"]["k_proj"]["kernel"].shape == (32, 16)
    plain = lambda p, x: ref.attention(cfg, kind, heads, p["params"], x,
                                       "f32")
    close(jax.jit(layer.apply)(p, x), jax.jit(plain)(p, x), 2e-5)
    loss = lambda f: (lambda p, x: jnp.sum(f(p, x) ** 2))
    g, gr = (jax.jit(jax.grad(loss(f), argnums=(0, 1)))(p, x)
             for f in (layer.apply, plain))
    trees_close(g, gr, 1e-4)
    if kind == "sliding_attention":       # the window is the layer's own
        wide = make_attention(dict(cfg, sliding_window=13), kind, heads,
                              impl)
        assert np.max(np.abs(wide.apply(p, x) - layer.apply(p, x))) > 1e-3


def test_the_gate_is_traced_under_the_scope():
    """The gate's projection, sigmoid and product carry the scope
    `head_gate` in the lowered program, and the attention's projections do
    not."""
    cfg = small_config()
    x = jnp.zeros((2, 16, 32), F32)
    layer = make_attention(cfg, "full_attention", 2)
    p = layer.init(jax.random.PRNGKey(0), x)
    text = jax.jit(jax.grad(lambda p, x: jnp.sum(layer.apply(p, x)))).lower(
        p, x).as_text(debug_info=True)
    scoped = [line for line in text.splitlines()
              if f"/{lg.HEAD_GATE_SCOPE}/" in line]
    assert any("logistic" in line for line in scoped)
    assert not any("q_proj" in line or "o_proj" in line for line in scoped)


# ---------------------------------------------------------- the router

def test_softmax_scoring_against_a_plain_top_k():
    """`DroplessMoE(scoring="softmax")` against numpy: probabilities the
    softmax over all 16 logits, the top 4 by probability, weights those over
    their sum times 2.5, each held expert's SwiGLU for the tokens that chose
    it, and the shared expert for every token; the router has no selection
    bias, and the sigmoid router keeps its own."""
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 11, 32), F32)
    layer = DroplessMoE(num_experts=8, router_width=16, d_hidden=16, top_k=4,
                        first_expert=4, num_shared=1, routed_scale=2.5,
                        dtype=F32, scoring="softmax")
    p = layer.init(jax.random.PRNGKey(4), x)["params"]
    assert "selection_bias" not in p
    assert "selection_bias" in layer.clone(scoring="sigmoid").init(
        jax.random.PRNGKey(4), x)["params"]
    y, stats = layer.apply({"params": p}, x)
    xf = np.asarray(x, np.float64).reshape(-1, 32)
    logits = xf @ np.asarray(p["router"], np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    want = np.zeros_like(xf)
    routed = 0
    for n in range(len(xf)):
        top = np.argsort(-probs[n], kind="stable")[:4]
        w = probs[n, top] / probs[n, top].sum() * 2.5
        for e, we in zip(top, w):
            if 4 <= e < 12:
                routed += 1
                g, u, dn = (np.asarray(p[f"expert_{m}"][e - 4], np.float64)
                            for m in ("gate", "up", "down"))
                a = xf[n] @ g
                want[n] += we * ((a / (1 + np.exp(-a)) * (xf[n] @ u)) @ dn)
    shared = SwiGLU(16, F32).apply({"params": p["shared0"]}, x)
    close(y, want.reshape(y.shape) + np.asarray(shared), 1e-5)
    assert int(stats[0]) == routed and int(stats[2]) == 0
    with pytest.raises(ValueError, match="scoring"):
        layer.clone(scoring="sparsemax").init(jax.random.PRNGKey(4), x)


@pytest.mark.parametrize("tile", [4, 8])
def test_the_familys_floor_of_tiles_changes_nothing(tile, monkeypatch):
    """The model's expert layer (softmax scoring, one shared expert) walks
    its family's floor, 5/4 uniform shares (`EXPERT_FLOOR_SHARES`), and
    gives what the same layer at the default two shares gives: the same
    result, the same count of assignments computed and the same five
    gradients (input, router, the three expert stacks), bit for bit, though
    the two walk different numbers of tiles. Tiles of 4 or 8 rows, so that
    both floors stand above the routing's need: 84 tokens, top 4 of 16 with
    4 held, a share of 21, floors of 27 and 42 tiles of 4 (14 and 21 of 8)."""
    from mmlspark_tpu.models import moe
    monkeypatch.setattr(moe, "GROUP_TILE", tile)
    own = build_model(small_config())._mlp(False)()
    assert own.floor_shares == lg.EXPERT_FLOOR_SHARES == 1.25
    assert own.scoring == "softmax" and own.num_shared == 1
    default = own.clone(floor_shares=moe.GROUP_FLOOR_SHARES)
    x = jax.random.normal(jax.random.PRNGKey(17), (2, 42, 32), F32)
    ct = jax.random.normal(jax.random.PRNGKey(18), (2, 42, 32), F32)
    p = own.init(jax.random.PRNGKey(19), x)

    def run(layer):
        def loss(p, x):
            y, stats = layer.apply(p, x)
            return jnp.sum(y * ct), (y, stats)
        (_, (y, stats)), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True))(p, x)
        return y, [int(s) for s in stats], grads

    y, stats, grads = run(own)
    y2, stats2, grads2 = run(default)
    N, k, E, W = 84, 4, 4, 16
    rows = moe.tile_rows(N * k // W)
    assert rows == tile
    floors = {4: (27, 42), 8: (14, 21)}[tile]
    assert floors == tuple(moe.floor_tiles(N, k, E, W, rows, s) for s in
                           (lg.EXPERT_FLOOR_SHARES, moe.GROUP_FLOOR_SHARES))
    # routed, fullest, dropped and needed the same; walked the two floors
    assert stats[:4] == stats2[:4] and stats[2] == 0
    assert stats[3] <= floors[0] and (stats[4], stats2[4]) == floors
    assert np.array_equal(y, y2)

    def five(g):
        return [g[1]] + [g[0]["params"][n] for n in
                         ("router", "expert_gate", "expert_up", "expert_down")]
    for a, b in zip(five(grads), five(grads2)):
        assert np.any(np.asarray(a)) and np.array_equal(a, b)


# ---------------------------------------------------------- the shares

def test_shares_add_up_to_the_uncut_layer():
    """Eight chips' shares of one layer, each with one of 8 key/value heads
    and its query heads (2 in a full layer, 3 in a sliding one), 8 of the
    dense MLP's 64 columns and 4 of 32 routed experts (the whole router,
    top 4): the shares' attention outputs add up to the uncut reference's
    (the output projection is a sum over heads), their dense MLPs to the
    uncut SwiGLU (a sum over columns), and their routed parts with the
    shared expert counted once to the uncut expert layer, which holds all
    32; the norms before each part are computed once, alike on every chip."""
    full = small_config(num_key_value_heads=8, head_dim=8,
                        dense_columns_held=64, num_experts=32,
                        router_width=32)
    z = jax.random.normal(jax.random.PRNGKey(11), (2, 13, 32), F32)
    for kind, per_kv in (("full_attention", 2), ("sliding_attention", 3)):
        H = 8 * per_kv
        p = make_attention(full, kind, H).init(jax.random.PRNGKey(12),
                                               z)["params"]
        one = jax.jit(make_attention(dict(full, num_key_value_heads=1),
                                     kind, per_kv).apply)
        D = full["head_dim"]

        def share(s):
            q = slice(s * per_kv * D, (s + 1) * per_kv * D)
            kv = slice(s * D, (s + 1) * D)
            return {"params": dict(
                q_proj={"kernel": p["q_proj"]["kernel"][:, q]},
                k_proj={"kernel": p["k_proj"]["kernel"][:, kv]},
                v_proj={"kernel": p["v_proj"]["kernel"][:, kv]},
                g_proj={"kernel": p["g_proj"]["kernel"][
                    :, s * per_kv:(s + 1) * per_kv]},
                o_proj={"kernel": p["o_proj"]["kernel"][q]})}
        close(sum(one(share(s), z) for s in range(8)),
              ref.attention(full, kind, H, p, z, "f32"), 2e-5)

    dense = SwiGLU(64, F32)
    pd = dense.init(jax.random.PRNGKey(13), z)["params"]
    cols = lambda s: slice(8 * s, 8 * s + 8)
    close(sum(SwiGLU(8, F32).apply({"params": {
        "gate": {"kernel": pd["gate"]["kernel"][:, cols(s)]},
        "up": {"kernel": pd["up"]["kernel"][:, cols(s)]},
        "down": {"kernel": pd["down"]["kernel"][cols(s)]}}}, z)
        for s in range(8)),
        ref.swiglu(pd, z, functools.partial(ref.common.matmul,
                                            precision="f32")), 2e-5)

    def experts(n, first, shared):
        return DroplessMoE(num_experts=n, router_width=32, d_hidden=16,
                           top_k=4, first_expert=first, num_shared=shared,
                           routed_scale=2.5, dtype=F32, scoring="softmax")
    pe = experts(32, 0, 1).init(jax.random.PRNGKey(14), z)["params"]
    total, routed = SwiGLU(16, F32).apply({"params": pe["shared0"]}, z), 0
    for s in range(8):
        ps = dict(pe, **{n: pe[n][4 * s:4 * s + 4] for n in
                         ("expert_gate", "expert_up", "expert_down")})
        del ps["shared0"]
        y, stats = jax.jit(experts(4, 4 * s, 0).apply)({"params": ps}, z)
        total, routed = total + y, routed + int(stats[0])
        assert int(stats[2]) == 0
    assert routed == 2 * 13 * 4
    close(total, ref.experts(full, pe, z, "f32"), 5e-5)


# ----------------------------------------------------------- the whole model

@pytest.fixture(scope="module")
def whole_reference():
    """The reference's logits, row losses and gradient of the rows' squared
    losses for `small_config` on `tokens()`, from the seeded init (computed
    once for both paths of the test below)."""
    cfg = small_config()
    tok = jnp.asarray(tokens())
    p = build_model(cfg).init(jax.random.PRNGKey(0), tok[:1])
    rows = functools.partial(ref.row_losses, cfg)
    return p, {
        "logits": jax.jit(functools.partial(ref.forward, cfg))(p, tok),
        "rows": jax.jit(rows)(p, tok),
        "grad": jax.jit(jax.grad(lambda p: jnp.sum(rows(p, tok) ** 2)))(p)}


@pytest.mark.parametrize("impl", ["blockwise", "flash"])
def test_model_matches_reference(impl, whole_reference):
    """Logits, row losses and the gradient of the rows' losses against the
    reference (whole logits over the untied head), on the blockwise path
    and through the flash kernels; under `remat` the same row losses to
    float32's summation order."""
    cfg = small_config(attn_impl=impl)
    tok = tokens()
    p, want = whole_reference
    m0, m1 = build_model(cfg), build_model(dict(cfg, remat=True))
    assert set(p["params"]) == {"embed", "norm", "head", "block0", "block1",
                                "block2"}
    assert set(p["params"]["block0"]["mlp"]) == {"gate", "up", "down"}
    assert set(p["params"]["block1"]["mlp"]) == {
        "router", "expert_gate", "expert_up", "expert_down", "shared0"}
    out = jax.jit(m0.apply)(p, tok)
    assert out.shape == (4, 13, 64) and out.dtype == jnp.float32
    close(out, want["logits"], 2e-5)
    rows = lambda m: functools.partial(m.apply, row_losses=True)
    got, stats = jax.jit(functools.partial(rows(m0), step_stats=True))(p, tok)
    close(got, want["rows"], 1e-5)
    close(stats["lm_loss_main"], jnp.mean(want["rows"]), 1e-5)
    assert int(stats["lm_tokens_scored"]) == 4 * 12
    assert int(stats["moe_tokens_dropped"]) == 0
    assert set(stats) == set(m0.step_stat_names)
    close(jax.jit(rows(m1))(p, tok), got, 1e-6)
    g0 = jax.jit(jax.grad(lambda p: jnp.sum(rows(m0)(p, tok) ** 2)))(p)
    trees_close(g0, want["grad"], 1e-4)
    for name in m0.layer_names():
        assert m0.apply(p, tok, output_layer=name).shape[0] == 4


def test_rows_of_weight_zero_carry_no_gradient():
    cfg = small_config()
    tok = tokens()
    m = build_model(cfg)
    p = m.init(jax.random.PRNGKey(0), tok[:1])
    mask = jnp.asarray([1.0, 1.0, 0.0, 0.0])

    def loss(p, t, w):
        rows, stats = m.apply(p, t, row_mask=w, row_losses=True,
                              step_stats=True)
        return jnp.sum(rows * w), stats
    (_, stats), g = jax.jit(jax.value_and_grad(loss, has_aux=True))(
        p, tok, mask)
    other = tok.copy()
    other[2:] = tokens(seed=9)[2:]
    (_, _), g2 = jax.jit(jax.value_and_grad(loss, has_aux=True))(
        p, other, mask)
    trees_close(g, g2, 1e-6)
    assert int(stats["lm_tokens_scored"]) == 2 * 12


# ------------------------------------------------------------ the learner

def test_fit_stream_follows_the_reference_in_float32():
    """Three AdamW steps of `fitStream` under `remat` with the per-token
    objective against the reference's own step loop from the same seeded
    parameters and batches: every parameter's change, to 1% of the largest
    (Adam divides by sqrt(v), so float32 noise in a small gradient moves its
    step by far more than it moves a value, where a wrong term turns steps
    of lr round); the labels handed over are noise and are not read."""
    cfg = small_config(remat=True)
    del cfg["dtype"]          # the learner's precision sets it
    rng = np.random.default_rng(5)
    batches = [(rng.integers(0, 64, (8, 13)).astype(np.int32),
                rng.integers(0, 2, (8,)).astype(np.int32)) for _ in range(3)]
    learner = (TpuLearner().setModelConfig(cfg).setBatchSize(8).setEpochs(1)
               .setOptimizer("adamw").setLearningRate(1e-3)
               .setWeightDecay(0.1).setPrecision("f32")
               .setLoss("next_token").setSeed(3))
    model = learner.fitStream(lambda: iter(batches))
    p0 = jax.device_get(build_model(dict(cfg, dtype="float32")).init(
        jax.random.PRNGKey(3), jnp.asarray(batches[0][0][:1])))
    rcfg = dict(cfg, learner={"optimizer": "adamw", "learningRate": 1e-3,
                              "weightDecay": 0.1})
    want = ref.train_steps(rcfg, p0, batches, block_rows=4)
    assert abs(model._final_loss / want["losses"][-1] - 1) < 1e-5
    diff = lambda a, b: jax.tree_util.tree_map(
        lambda x, y: np.asarray(x) - np.asarray(y), a, b)
    moved, moved_ref = (diff(model.getModelParams(), p0),
                        diff(want["params_after"], p0))
    scale = max(np.max(np.abs(a)) for a in
                jax.tree_util.tree_leaves(moved_ref))
    assert scale > 1e-3           # three steps at 1e-3 moved the weights
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(moved)[0],
                            jax.tree_util.tree_leaves(moved_ref)):
        assert np.max(np.abs(a - b)) <= 1e-2 * scale, \
            (jax.tree_util.keystr(path), np.max(np.abs(a - b)), scale)


def test_fit_stream_at_the_rehearsal_size_builds_the_cells_program():
    """The benchmark's configuration with its `rehearsal` sizes merged in
    (8 rows of 1,024 ids, hidden 128, 2 / 3 heads of 32, the window of 512
    and both rope blocks as published), built by the benchmark's own driver:
    the sliding layers' calls are windowed and the full layers' are not
    (traced, not run, so that this stays a second or two)."""
    from benchmark.drivers.train_stream import build_learner
    from benchmark.run import merge
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "laguna_s_2p1.json")) as f:
        cfg = json.load(f)
    cfg = merge(cfg, cfg["rehearsal"])
    model_cfg = dict(build_learner(cfg, {"batch_rows": 8,
                                         "prefetch_depth": 2},
                                   0).getModelConfig())
    model = build_model({**model_cfg, "attn_impl": "flash"})
    T = cfg["input"]["seq_len"]
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, T), jnp.int32)))
    text = str(jax.make_jaxpr(jax.grad(lambda p, t: jnp.sum(
        model.apply(p, t, row_losses=True))))(
            params, jax.ShapeDtypeStruct((8, T), jnp.int32)))
    names = re.findall(r"name=(flash_(?:fwd|dq|dkv)\w*)", text)
    assert sorted(set(names)) == sorted(
        n + w for n in ("flash_fwd", "flash_dq", "flash_dkv")
        for w in ("", "_window"))


# ------------------------------------- what the other families run as before

# The four sequence families' gradient programs, at small sizes on the flash
# and the blockwise paths, as the tree before the window and the softmax
# router traced them (sha256 of the jaxpr's text, function addresses cut):
# `window=None` and `scoring="sigmoid"` leave every one as it was. A change
# that alters one of these programs on purpose records its new digest here.
T_DIGEST = 64
FAMILIES = {
    "transformer": ({"type": "transformer", "d_model": 256, "heads": 2,
                     "layers": 1, "mlp_ratio": 2, "vocab_size": 64,
                     "max_len": T_DIGEST, "causal": True, "remat": True,
                     "num_classes": 2}, {}),
    "kimi_linear": ({"type": "kimi_linear", "vocab_size": 64,
                     "hidden_size": 32, "num_hidden_layers": 2,
                     "first_k_dense_replace": 1, "num_attention_heads": 2,
                     "intermediate_size": 48, "moe_intermediate_size": 16,
                     "num_experts": 4, "router_width": 8,
                     "num_experts_per_token": 2, "kv_lora_rank": 16,
                     "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
                     "v_head_dim": 16, "remat": True,
                     "linear_attn_config": {"kda_layers": [1],
                                            "full_attn_layers": [2],
                                            "head_dim": 16, "num_heads": 2}},
                    {}),
    "joyai_llm_flash": ({"type": "joyai_llm_flash", "vocab_size": 64,
                         "hidden_size": 32, "num_hidden_layers": 2,
                         "first_k_dense_replace": 1,
                         "num_attention_heads": 2, "q_lora_rank": 24,
                         "kv_lora_rank": 16, "qk_nope_head_dim": 16,
                         "qk_rope_head_dim": 8, "v_head_dim": 16,
                         "intermediate_size": 48, "moe_intermediate_size": 16,
                         "n_routed_experts": 4, "router_width": 8,
                         "num_experts_per_tok": 2, "lm_loss_chunk": 16,
                         "remat": True}, {"row_losses": True}),
    "lfm2_moe": ({"type": "lfm2_moe", "vocab_size": 64, "hidden_size": 32,
                  "num_hidden_layers": 2,
                  "layer_types": ["full_attention", "conv"],
                  "num_dense_layers": 1, "num_attention_heads": 4,
                  "num_key_value_heads": 2, "head_dim": 16,
                  "intermediate_size": 48, "moe_intermediate_size": 16,
                  "num_experts": 4, "router_width": 8,
                  "num_experts_per_tok": 2, "lm_loss_chunk": 16,
                  "remat": True}, {"row_losses": True}),
}
DIGESTS = {
    ("flash", "transformer"):
        "cec24743dc3b00b75b122f63fc0f2076d286c9b09825b3ad543962a689a62f20",
    ("flash", "kimi_linear"):
        "f888be8a61daa4cc2999bbd7cdd70bac3b17fa53ba145c8f2c0e5b8662043dab",
    ("flash", "joyai_llm_flash"):
        "7c81dad1b64a8e06fdd767223a89714c78666057191c41de59aa5c87fb57b27e",
    ("flash", "lfm2_moe"):
        "39d5c7fb4549d94a7fbc98812316e04b763fc9d8df5ee3234abf188c324178ff",
    ("blockwise", "transformer"):
        "1d105dd9aff47e75d481aa9031113674b8496d2c94db14d095b6439efb9d39a3",
    ("blockwise", "kimi_linear"):
        "0c3e553d6bfce815c02101f136acba14301e783dcb66160e361fd6b01610ddd5",
    ("blockwise", "joyai_llm_flash"):
        "2b5052725cf30ed2bcb7953e6171db0df05ed43ea0cc509ae4433dfc92d9ec44",
    ("blockwise", "lfm2_moe"):
        "5e0d54b6019b55ea3094ff26754b56542d383aa8673383aa59e550c86e557da9",
}


@pytest.mark.parametrize("impl,family", sorted(DIGESTS))
def test_the_other_families_trace_to_the_programs_they_had(impl, family):
    cfg, kw = FAMILIES[family]
    model = build_model({**cfg, "attn_impl": impl})
    x = jnp.zeros((2, T_DIGEST), jnp.int32)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), x))

    def loss(p):
        return jnp.sum(model.apply(p, x, **kw).astype(jnp.float32))
    text = re.sub(r" at 0x[0-9a-f]+", "",
                  str(jax.make_jaxpr(jax.grad(loss))(params)))
    assert hashlib.sha256(text.encode()).hexdigest() == \
        DIGESTS[impl, family]


# ------------------------------------------------- registry, flops, counters

def load_cell_config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "laguna_s_2p1.json")) as f:
        return json.load(f)


def test_flops_hand_count_at_the_published_widths():
    """`benchmark/flops/laguna.py` against a count by hand, in multiply-adds
    a token forward, for the benchmark's own configuration (d 3072, heads of
    128, 6 / 9 query heads over 1 held, 8 of 256 experts, 1,536 dense
    columns, 12,544 vocabulary rows, T 4096, window 512). Full attention:
    W_q 3072 x 768, W_k and W_v 3072 x 128, W_o 768 x 3072, W_g 3072 x 6,
    scores and values 2 x 128 x 6 x 4097 / 2 (the causal triangle's mean
    keys). Sliding: W_q 3072 x 1152 and so on with W_g 3072 x 9, scores and
    values 2 x 128 x 9 x (512 x 513 / 2 + 3584 x 512) / 4096. Dense 3 x
    3072 x 1536. Experts: router 3072 x 256, 10 x 8 / 256 = 0.3125 routed
    SwiGLUs and the shared one of 3 x 3072 x 1024. Head 3072 x 12544. About
    300 MFLOP a token forward, three times that to train."""
    from benchmark.flops import laguna as flops
    cfg = load_cell_config()
    band = 512 * 513 // 2 + 3584 * 512
    assert flops.band(4096, 512) == band == 1_966_336
    full = (2 * 3072 * 768 + 2 * 3072 * 128 + 3072 * 6
            + 2 * 128 * 6 * 4097 / 2)
    sliding = (2 * 3072 * 1152 + 2 * 3072 * 128 + 3072 * 9
               + 2 * 128 * 9 * band / 4096)
    assert flops.attention_macs_per_token(cfg, "full_attention", 6) == full
    assert flops.attention_macs_per_token(
        cfg, "sliding_attention", 9) == sliding
    moe = 3072 * 256 + 3 * 3072 * 1024 * 0.3125 + 3 * 3072 * 1024
    assert flops.moe_macs_per_token(cfg) == moe
    dense, head = 3 * 3072 * 1536, 3072 * 12544
    per_token = 2 * full + 3 * sliding + dense + 4 * moe + head
    assert flops.forward_macs_per_token(cfg) == per_token
    assert abs(2 * per_token / 299.4e6 - 1) < 1e-3
    assert flops.train_flops_per_row(cfg) == 3 * 2 * per_token * 4096
    assert flops.layers_of(cfg, "sliding_attention") == [9, 9, 9]
    assert flops.layers_of(cfg, "full_attention") == [6, 6]
    ops, nbytes = flops.flash_fwd(cfg, 8, 9, "sliding_attention")
    assert ops == 4 * 8 * 9 * 128 * band
    assert nbytes == 2 * (9 + 1) * 8 * 4096 * 128 * 2
    assert flops.flash_bwd(cfg, 8, 9, "sliding_attention") == (
        2.5 * ops, 2 * nbytes)
    ops, nbytes = flops.flash_fwd(cfg, 8, 6, "full_attention")
    assert ops == 4 * 8 * 6 * 128 * (4096 * 4097 // 2)
    assert nbytes == 2 * (6 + 1) * 8 * 4096 * 128 * 2
    assert flops.flash_bwd(cfg, 8, 6, "full_attention") == (
        2.5 * ops, 2 * nbytes)


def test_the_configuration_counts_its_parameters():
    """The benchmark's configuration builds the model its file describes:
    468,867,072 parameters by the shapes of the seeded init, 6 / 9 query
    heads over one key/value head of 128, and every number of the source
    either kept or listed in `reduced` with its published value."""
    cfg = load_cell_config()
    meta = ("source", "input", "learner", "published", "reduced",
            "departures", "assumed", "rehearsal", "parameters")
    model = build_model({k: v for k, v in cfg.items() if k not in meta})
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    count = sum(int(np.prod(a.shape))
                for a in jax.tree_util.tree_leaves(shapes))
    assert count == 468_867_072
    assert "468,867,072" in cfg["parameters"]
    P = shapes["params"]
    assert P["block1"]["mixer"]["q_proj"]["kernel"].shape == (3072, 1152)
    assert P["block4"]["mixer"]["g_proj"]["kernel"].shape == (3072, 6)
    assert P["block0"]["mlp"]["gate"]["kernel"].shape == (3072, 1536)
    published = {"hidden_size": 3072, "head_dim": 128,
                 "intermediate_size": 12288, "moe_intermediate_size": 1024,
                 "shared_expert_intermediate_size": 1024,
                 "num_experts_per_tok": 10, "sliding_window": 512,
                 "moe_routed_scaling_factor": 2.5, "rms_norm_eps": 1e-6,
                 "max_position_embeddings": 1048576}
    assert {k: cfg[k] for k in published} == published
    cut = {"num_hidden_layers": 48, "num_experts": 256,
           "num_attention_heads": 48, "num_key_value_heads": 8,
           "vocab_size": 100352}
    for key, value in cut.items():
        assert cfg[key] != value and key in cfg["reduced"]
        assert cfg["published"][key] == value
    assert cfg["num_attention_heads_per_layer"] == [6, 9, 9, 9, 6]


def test_the_registry_knows_the_family():
    assert "laguna" in TOKEN_MODELS
    x = example_input({"type": "laguna", "seq_len": 12}, batch=3)
    assert x.shape == (3, 12) and x.dtype == jnp.int32
    assert has_experts({"type": "laguna", "num_experts": 8})
    for key, bad in [("attention_bias", True), ("tie_word_embeddings", True),
                     ("gating", "per-channel"),
                     ("moe_router_logit_softcapping", 30.0),
                     ("shared_expert_intermediate_size", 32),
                     ("mlp_layer_types", ["dense"] * 3),
                     ("num_attention_heads_per_layer", [2, 3])]:
        with pytest.raises(ValueError, match=key):
            build_model(small_config(**{key: bad}))
    with pytest.raises(ValueError, match="key/value"):
        m = build_model(small_config(num_key_value_heads=2))
        m.init(jax.random.PRNGKey(0), tokens()[:1])
    with pytest.raises(ValueError, match="rope_type"):
        rope = dict(cell_rope(), full_attention={"rope_type": "linear",
                                                 "rope_theta": 1e4})
        build_model(small_config(rope_parameters=rope))


def test_the_counters_say_what_was_built(monkeypatch):
    """Traced with telemetry on, the small model counts its gated layers by
    type, its rotary layers by kind and rotated lanes, and its flash calls
    under the windowed names for its sliding layer."""
    monkeypatch.setattr(sys.modules["mmlspark_tpu.telemetry.registry"]._state,
                        "enabled", True)

    def series(name):
        snap = telemetry.registry.snapshot().get(name, {"series": []})
        return {tuple(sorted(s["labels"].items())): s["value"]
                for s in snap["series"]}
    names = ("mmlspark_attn_head_gates_total", "mmlspark_rope_layers_total",
             "mmlspark_flash_calls_total")
    model = build_model(small_config(attn_impl="flash"))
    tok = jnp.zeros((2, 13), jnp.int32)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), tok))
    before = {n: series(n) for n in names}
    jax.eval_shape(jax.grad(lambda p: jnp.sum(model.apply(
        p, tok, row_losses=True))), params)

    def grew(name, **labels):
        return sum(v - before[name].get(k, 0.0)
                   for k, v in series(name).items()
                   if all(dict(k).get(a) == b for a, b in labels.items()))
    assert grew(names[0], layer_type="sliding_attention") == 1
    assert grew(names[0], layer_type="full_attention") == 2
    assert grew(names[1], kind="yarn", rotary_lanes="8") == 2
    assert grew(names[1], kind="default", rotary_lanes="16") == 1
    assert grew(names[2], kernel="flash_fwd_window", window="5") == 1
    assert grew(names[2], kernel="flash_dkv_window", window="5") == 1
    assert grew(names[2], kernel="flash_fwd", window="none") == 2


# ------------------------------------------------------------ the readers

@pytest.mark.parametrize("name", ["swa_flash_fwd_roofline",
                                  "swa_flash_bwd_roofline",
                                  "full_flash_fwd_roofline",
                                  "full_flash_bwd_roofline",
                                  "laguna_moe_grouped_ms"])
def test_the_new_readers_read_their_cell_and_no_other(name):
    """Each new per-layer reader on a trace that holds what it looks for
    reads a number under this family's configuration, None under another
    family's and None on an empty trace (never 0). The roofline shares are
    the band's (sliding layers) or the causal triangle's (full layers) least
    time over the kernels' time; the tile loops those that carry
    `f32[32768,3072]` and the sort over 327,680 entries."""
    import importlib
    from benchmark.flops import attention, laguna as flops
    reader = importlib.import_module(f"benchmark.layer_metrics.{name}")
    loop = ("(s32[], f32[32768,3072]{1,0}, s32[]) while((s32[], "
            "f32[32768,3072]{1,0}, s32[]) %tuple), condition=%c, body=%b")
    labels = {"flash_fwd_window.1": "", "flash_dq_window.1": "",
              "flash_dkv_window.2": "", "flash_fwd.3": "",
              "flash_dq.6": "", "flash_dkv.7": "",
              "while.4": loop, "sort.5": "f32[327680]{0} sort(...)"}
    seconds = {"flash_fwd_window.1": 0.03, "flash_dq_window.1": 0.04,
               "flash_dkv_window.2": 0.05, "flash_fwd.3": 0.02,
               "flash_dq.6": 0.025, "flash_dkv.7": 0.035,
               "while.4": 0.2, "sort.5": 0.01}
    # a step's calls: 3 of each windowed kernel, 2 of each causal one
    calls = dict.fromkeys(labels, 9)
    calls.update({"flash_fwd.3": 6, "flash_dq.6": 6, "flash_dkv.7": 6})
    trace = {"steps": 3, "window_s": 2.0, "op_s": seconds, "op_n": calls,
             "op_label": labels}
    counters = {"batch_rows": 8, "window_steps": 3}
    peaks = {"flops_bf16": 197e12, "bytes_per_s": 819e9}
    own = {"cell": {"name": "laguna_train_stream"}, "chips": 1,
           "peaks": peaks, "config": load_cell_config()}
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2_8b_a1b.json")) as f:
        other = dict(own, config=json.load(f))
    assert reader.read(trace, counters, other) is None
    got = reader.read(trace, counters, own)
    empty = dict(trace, op_s={}, op_n={}, op_label={})
    assert reader.read(empty, counters, own) is None
    cfg = own["config"]
    if name == "laguna_moe_grouped_ms":
        assert got == pytest.approx((0.2 + 0.01) * 1e3 / 3)
        return
    fwd = "_fwd_" in name
    kind, H, layers, measured = (
        ("sliding_attention", 9, 3, 0.03 if fwd else 0.09)
        if name.startswith("swa") else
        ("full_attention", 6, 2, 0.02 if fwd else 0.06))
    least = attention.least_seconds(*(
        flops.flash_fwd if fwd else flops.flash_bwd)(cfg, 8, H, kind),
        peaks)[0]
    # three steps' worth of calls, each step every layer's least time
    assert got == pytest.approx(100 * 3 * layers * least / measured)
