"""The `lfm2_moe` family against its plain reference, on the CPU at small
sizes with seeded weights.

The reference is `benchmark/reference/lfm2_moe.py` (jax.numpy, float32,
nothing of the program imported): the convolution as a gather of its taps,
the rotation from the angle formula, grouped-query attention as a full
masked softmax over repeated groups, the expert layer as a loop over the
held experts, the tied head over whole (T, V) logits. The program runs here
in float32 too, so every tolerance below is the room two orders of float32
summation need (1e-5 relative on values of order one, a little more through
a backward pass or three optimizer steps), never a precision's: a wrong term
reads 1e-2 and more.
"""

import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.reference import lfm2_moe as ref               # noqa: E402
from mmlspark_tpu import telemetry                            # noqa: E402
from mmlspark_tpu.models import TpuLearner, build_model       # noqa: E402
from mmlspark_tpu.models import kimi_linear as kl             # noqa: E402
from mmlspark_tpu.models import lfm2_moe as lf                # noqa: E402
from mmlspark_tpu.models.modules import (TOKEN_MODELS,        # noqa: E402
                                         example_input, has_experts)
from mmlspark_tpu.models.moe import DroplessMoE               # noqa: E402
from mmlspark_tpu.ops.pallas_kernels import flash_attention   # noqa: E402

F32 = jnp.float32
KINDS = ["conv", "full_attention", "conv"]


def small_config(**over):
    """Three layers as the benchmark's cut in small (conv + dense,
    attention + experts, conv + experts), 4 of 16 experts and 4 query heads
    over 2 key/value heads held, every width tiny."""
    cfg = {"type": "lfm2_moe", "vocab_size": 64, "hidden_size": 32,
           "num_hidden_layers": 3, "layer_types": KINDS,
           "num_dense_layers": 1, "num_attention_heads": 4,
           "num_key_value_heads": 2, "head_dim": 8, "conv_L_cache": 3,
           "rope_theta": 1e6, "intermediate_size": 48,
           "moe_intermediate_size": 16, "num_experts": 4, "router_width": 16,
           "first_expert_held": 0, "num_experts_per_tok": 4,
           "norm_topk_prob": True, "routed_scaling_factor": 1,
           "norm_eps": 1e-5, "lm_loss_chunk": 8, "dtype": "float32"}
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


def tokens(B=4, T=21, vocab=64, seed=0):
    return np.random.default_rng(seed).integers(0, vocab, (B, T)).astype(
        np.int32)


# ---------------------------------------------- grouped flash attention

def plain_grouped(q, k, v, causal, scale):
    """Plain attention with K and V repeated to the query heads."""
    g = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(a, g, axis=2) for a in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        Tq, Tk = s.shape[-2:]
        s = jnp.where(jnp.arange(Tk)[None, :] <= jnp.arange(Tq)[:, None], s,
                      -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


@pytest.mark.parametrize("T,bq,bk", [(256, None, None), (200, 64, 128)])
@pytest.mark.parametrize("D", [pytest.param(64, id="d64-copied"),
                               pytest.param(128, id="d128-in-place")])
@pytest.mark.parametrize("g", [1, 2, 4])
def test_grouped_flash_matches_plain_attention_with_repeated_kv(g, D, T, bq,
                                                                bk):
    """`flash_attention` with Hkv = H / g key/value heads (interpret mode):
    the values and all three gradients against plain attention on K and V
    repeated g times; dK and dV come back at Hkv heads, each the sum over
    its group's g query heads."""
    rng = np.random.default_rng(g * 1000 + D + T)
    B, Hkv = 2, 2
    H = Hkv * g
    q, k, v, w = (jnp.asarray(rng.normal(size=(B, T, n, D)), F32)
                  for n in (H, Hkv, Hkv, H))
    scale = D ** -0.5

    def both(attend):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(attend(q, k, v) * w), (0, 1, 2))(q, k, v)

    with jax.default_matmul_precision("highest"):
        got = both(lambda q, k, v: flash_attention(q, k, v, True, scale, bq,
                                                   bk))
        want = both(lambda q, k, v: plain_grouped(q, k, v, True, scale))
    assert [a.shape for a in got[1]] == [q.shape, k.shape, v.shape]
    close(got[0], want[0], 1e-5)
    for a, b in zip(got[1], want[1]):
        close(a, b, 2e-5)


def test_flash_refuses_heads_that_do_not_group():
    q = jnp.zeros((1, 16, 3, 8), F32)
    k = jnp.zeros((1, 16, 2, 8), F32)
    with pytest.raises(ValueError, match="heads"):
        flash_attention(q, k, k, True)
    with pytest.raises(ValueError, match="heads"):
        flash_attention(jnp.zeros((1, 16, 4, 8), F32), k, k[:, :, :1], True)


def test_flash_calls_are_counted_by_group(monkeypatch):
    """`mmlspark_flash_calls_total` carries the group size beside kernel,
    layout and widths: one increment a kernel for a grouped call."""
    monkeypatch.setattr(sys.modules["mmlspark_tpu.telemetry.registry"]._state,
                        "enabled", True)

    def read():
        series = telemetry.registry.snapshot()[
            "mmlspark_flash_calls_total"]["series"]
        return {tuple(s["labels"][n] for n in
                      ("kernel", "layout", "widths", "group")): s["value"]
                for s in series}
    before = read()
    q = jnp.zeros((1, 256, 8, 64), F32)
    k = jnp.zeros((1, 256, 2, 64), F32)
    jax.make_jaxpr(jax.grad(lambda q, k: jnp.sum(
        flash_attention(q, k, k, True)), argnums=(0, 1)))(q, k)
    grew = {key: n - before.get(key, 0.0) for key, n in read().items()
            if n != before.get(key, 0.0)}
    assert grew == {(kernel, "transposed", "64", "4"): 1.0
                    for kernel in ("flash_fwd", "flash_dq", "flash_dkv")}


# ------------------------------------------------------------ the rotation

@pytest.mark.parametrize("shape", [(2, 9, 3, 8), (2, 9, 8), (1, 130, 2, 64)])
def test_half_split_rotation_matches_the_complex_form(shape):
    """`rotate_half_split` against the complex form: lanes i and i + D/2
    are the real and imaginary parts of one number, multiplied by
    exp(i t theta^(-2i / D)); and against the reference's formula."""
    theta = 1e6
    x = jax.random.normal(jax.random.PRNGKey(1), shape, F32)
    T, D = shape[1], shape[-1]
    z = np.asarray(x[..., :D // 2]) + 1j * np.asarray(x[..., D // 2:])
    ang = (np.arange(T)[:, None] * theta ** (-2.0 * np.arange(D // 2) / D))
    ang = ang.reshape((T,) + (1,) * (len(shape) - 3) + (D // 2,))
    turned = z * np.exp(1j * ang)
    want = np.concatenate([turned.real, turned.imag], axis=-1)
    got = lf.rotate_half_split(x, theta)
    close(got, want, 1e-5)
    close(got, ref.rotate(x, theta), 1e-6)


def test_rotated_scores_depend_on_the_distance_alone():
    q = jax.random.normal(jax.random.PRNGKey(2), (16,), F32)
    k = jax.random.normal(jax.random.PRNGKey(3), (16,), F32)
    T = 12
    qs = lf.rotate_half_split(jnp.broadcast_to(q, (1, T, 16)), 1e4)[0]
    ks = lf.rotate_half_split(jnp.broadcast_to(k, (1, T, 16)), 1e4)[0]
    s = np.asarray(qs @ ks.T)
    for dist in range(1, 5):
        diag = np.diagonal(s, -dist)
        assert np.max(np.abs(diag - diag[0])) < 1e-4


# ------------------------------------------------------------- the mixers

def test_conv_mixer_matches_reference_and_is_causal():
    """The gated short convolution against the reference's gather of taps,
    values and gradients; the output at t reads nothing after t; the
    depthwise kernel takes no activation (`_ShortConv`'s shifted sum, its
    SiLU switched off) while KDA's use keeps it."""
    cfg = small_config()
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 13, 32), F32)
    mixer = lf.ShortConvMixer(3, F32)
    p = mixer.init(jax.random.PRNGKey(5), x)
    assert set(p["params"]) == {"in_proj", "conv", "out_proj"}
    assert p["params"]["in_proj"]["kernel"].shape == (32, 96)
    assert p["params"]["conv"]["kernel"].shape == (3, 32)
    plain = lambda p, x: ref.short_conv(cfg, p["params"], x, "f32")
    close(jax.jit(mixer.apply)(p, x), jax.jit(plain)(p, x), 1e-5)
    loss = lambda f: (lambda p, x: jnp.sum(f(p, x) ** 2))
    g, gr = (jax.jit(jax.grad(loss(f), argnums=(0, 1)))(p, x)
             for f in (mixer.apply, plain))
    trees_close(g, gr, 5e-5)
    later = x.at[:, 7:].add(1.0)
    close(mixer.apply(p, later)[:, :7], mixer.apply(p, x)[:, :7], 1e-6)
    v = jax.random.normal(jax.random.PRNGKey(6), (1, 5, 4), F32)
    bare, kda = kl._ShortConv(3, None), kl._ShortConv(3)
    pc = bare.init(jax.random.PRNGKey(7), v)
    close(kda.apply(pc, v), jax.nn.silu(bare.apply(pc, v)), 1e-6)
    w = pc["params"]["kernel"]
    close(bare.apply(pc, v)[0, 1], w[1] * v[0, 0] + w[2] * v[0, 1], 1e-6)


def make_attention(cfg, impl="blockwise"):
    return lf.GQALayer(cfg["num_attention_heads"],
                       cfg["num_key_value_heads"], cfg["head_dim"],
                       kl.causal_attention(impl, 8), cfg["rope_theta"],
                       cfg["norm_eps"], F32)


@pytest.mark.parametrize("impl", ["blockwise", "flash"])
def test_attention_mixer_matches_reference(impl):
    """Grouped-query attention with per-head QK-norm and whole-head rotary
    against the written-out softmax, values and gradients, on the blockwise
    path (K and V repeated) and through the grouped flash kernels."""
    cfg = small_config()
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 13, 32), F32)
    layer = make_attention(cfg, impl)
    p = layer.init(jax.random.PRNGKey(9), x)
    assert p["params"]["q_norm"]["scale"].shape == (8,)
    assert p["params"]["k_proj"]["kernel"].shape == (32, 16)
    # scales away from 1, so that a norm left out or misplaced shows
    p = jax.tree_util.tree_map_with_path(
        lambda path, a: a * 1.5 if "norm" in jax.tree_util.keystr(path)
        else a, p)
    plain = lambda p, x: ref.attention(cfg, p["params"], x, "f32")
    close(jax.jit(layer.apply)(p, x), jax.jit(plain)(p, x), 2e-5)
    loss = lambda f: (lambda p, x: jnp.sum(f(p, x) ** 2))
    g, gr = (jax.jit(jax.grad(loss(f), argnums=(0, 1)))(p, x)
             for f in (layer.apply, plain))
    trees_close(g, gr, 1e-4)


def test_reference_softmax_in_query_blocks_is_the_whole_softmax(monkeypatch):
    cfg = small_config()
    x = jax.random.normal(jax.random.PRNGKey(10), (2, 13, 32), F32)
    p = make_attention(cfg).init(jax.random.PRNGKey(9), x)["params"]
    whole = ref.attention(cfg, p, x, "f32")
    monkeypatch.setattr(ref, "QUERY_BLOCK", 4)
    close(ref.attention(cfg, p, x, "f32"), whole, 1e-6)


# ----------------------------------------------------------- the whole model

def test_model_matches_reference_and_remat_changes_nothing():
    """Logits, row losses and the gradient of the rows' losses against the
    reference (whole logits over the tied head); under `remat` the same to
    float32's summation order."""
    cfg = small_config()
    tok = tokens()
    m0, m1 = build_model(cfg), build_model(dict(cfg, remat=True))
    p = m0.init(jax.random.PRNGKey(0), tok[:1])
    assert set(p["params"]) == {"embed", "norm", "block0", "block1", "block2"}
    assert set(p["params"]["block0"]["mlp"]) == {"gate", "up", "down"}
    assert set(p["params"]["block1"]["mlp"]) == {
        "router", "selection_bias", "expert_gate", "expert_up",
        "expert_down"}                            # no shared expert
    out = jax.jit(m0.apply)(p, tok)
    assert out.shape == (4, 21, 64) and out.dtype == jnp.float32
    close(out, jax.jit(functools.partial(ref.forward, cfg))(
        p, jnp.asarray(tok)), 2e-5)
    rows = lambda m: functools.partial(m.apply, row_losses=True)
    plain = functools.partial(ref.row_losses, cfg)
    got, stats = jax.jit(functools.partial(rows(m0), step_stats=True))(p, tok)
    want = jax.jit(plain)(p, jnp.asarray(tok))
    close(got, want, 1e-5)
    close(stats["lm_loss_main"], jnp.mean(want), 1e-5)
    assert int(stats["lm_tokens_scored"]) == 4 * 20
    assert int(stats["moe_tokens_dropped"]) == 0
    assert set(stats) == set(m0.step_stat_names)
    close(jax.jit(rows(m1))(p, tok), got, 1e-6)
    grad = lambda f: jax.jit(jax.grad(
        lambda p: jnp.sum(f(p, jnp.asarray(tok)) ** 2)))(p)
    g0, g1 = grad(rows(m0)), grad(rows(m1))
    trees_close(g1, g0, 1e-6)
    trees_close(g0, grad(plain), 1e-4)
    for name in m0.layer_names():
        assert m0.apply(p, tok, output_layer=name).shape[0] == 4


def test_the_tied_embeddings_gradient_is_the_sum_of_its_two_uses():
    """With the look-up's use of the embedding held constant the gradient is
    the head's alone, with the head's use held constant the look-up's, and
    the model's is their sum (neither is zero)."""
    cfg = small_config()
    tok = jnp.asarray(tokens())
    m = build_model(cfg)
    p = m.init(jax.random.PRNGKey(0), tok[:1])
    E = p["params"]["embed"]["embedding"]

    def with_embedding(lookup, head):
        """The rows' losses with one array looked up and another as head:
        the reference's own forward, the two uses apart."""
        P = dict(p["params"], embed={"embedding": lookup})
        x = ref.rmsnorm(ref.hidden(cfg, {"params": P}, tok), P["norm"],
                        cfg["norm_eps"])
        z = ref.common.matmul(x, head.T, "f32")
        return jnp.sum(jnp.mean(ref.token_losses(z[:, :-1], tok[:, 1:]),
                                axis=1))
    g_lookup, g_head = jax.jit(jax.grad(with_embedding, argnums=(0, 1)))(E, E)
    got = jax.jit(jax.grad(lambda p: jnp.sum(
        m.apply(p, tok, row_losses=True))))(p)["params"]["embed"]["embedding"]
    assert np.max(np.abs(g_lookup)) > 1e-3 and np.max(np.abs(g_head)) > 1e-3
    close(got, g_lookup + g_head, 1e-4)


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
    assert int(stats["lm_tokens_scored"]) == 2 * 20


# ---------------------------------------------------------------- the shares

def head_share(p, s, per_kv, cfg):
    """Share `s` of the attention layer's parameters: `per_kv` of the
    key/value heads with their groups of query heads."""
    D, g = cfg["head_dim"], (cfg["num_attention_heads"]
                             // cfg["num_key_value_heads"])

    def cols(w, per):        # (d, heads * D) -> this share's heads
        return w[:, s * per * D:(s + 1) * per * D]
    return dict(
        p, q_proj={"kernel": cols(p["q_proj"]["kernel"], per_kv * g)},
        k_proj={"kernel": cols(p["k_proj"]["kernel"], per_kv)},
        v_proj={"kernel": cols(p["v_proj"]["kernel"], per_kv)},
        o_proj={"kernel": p["o_proj"]["kernel"][
            s * per_kv * g * D:(s + 1) * per_kv * g * D]})


def test_head_shares_add_up_to_the_uncut_layer():
    """Four shares of 2 query heads over 1 key/value head each add up to
    what the uncut reference gives for 8 heads over 4 (the output projection
    is a sum over heads; the head norms are alike on every share)."""
    full = small_config(num_attention_heads=8, num_key_value_heads=4,
                        head_dim=4)
    share = dict(full, num_attention_heads=2, num_key_value_heads=1)
    x = jax.random.normal(jax.random.PRNGKey(11), (2, 13, 32), F32)
    p = make_attention(full).init(jax.random.PRNGKey(12), x)["params"]
    one = jax.jit(make_attention(share).apply)
    total = sum(one({"params": head_share(p, s, 1, full)}, x)
                for s in range(4))
    close(total, jax.jit(lambda p, x: ref.attention(full, p, x, "f32"))(p, x),
          2e-5)


def make_experts(cfg):
    return DroplessMoE(
        num_experts=cfg["num_experts"], router_width=cfg["router_width"],
        d_hidden=cfg["moe_intermediate_size"],
        top_k=cfg["num_experts_per_tok"],
        first_expert=cfg["first_expert_held"], num_shared=0,
        renormalize=True, routed_scale=cfg["routed_scaling_factor"],
        dtype=F32, renorm_eps=1e-6)


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four shares of 8 routed experts each (router width 32, top 4, every
    share with the whole router and no shared expert): the four routed
    parts add up to the uncut reference's layer, which holds all 32, and
    every assignment lands on one share, none dropped."""
    cfg = small_config(router_width=32, num_experts=32)
    x = jax.random.normal(jax.random.PRNGKey(13), (2, 21, 32), F32)
    p = make_experts(cfg).init(jax.random.PRNGKey(14), x)["params"]
    assert not any(name.startswith("shared") for name in p)
    total, routed = 0.0, 0
    for s in range(4):
        one = dict(cfg, num_experts=8, first_expert_held=8 * s)
        ps = dict(p, **{n: p[n][8 * s:8 * s + 8] for n in
                        ("expert_gate", "expert_up", "expert_down")})
        y, stats = jax.jit(make_experts(one).apply)({"params": ps}, x)
        total = total + y
        routed += int(stats[0])
        assert int(stats[2]) == 0
    assert routed == 2 * 21 * 4
    close(total, jax.jit(lambda p, x: ref.experts(cfg, p, x, "f32"))(p, x),
          5e-5)


def test_the_renormalisations_constant_is_the_familys():
    """The chosen scores are divided by their sum + 1e-6 in this family and
    by their sum + 1e-20 where the field is left alone: with a router that
    scores next to nothing the two differ, and the reference has this
    family's."""
    cfg = small_config(num_experts=16, router_width=16)
    x = jax.random.normal(jax.random.PRNGKey(15), (1, 9, 32), F32)
    layer = make_experts(cfg)
    p = layer.init(jax.random.PRNGKey(16), x)["params"]
    p = dict(p, router=p["router"] * 0.0 - 3.0)      # sigmoid(-96 ..) tiny
    y, _ = layer.apply({"params": p}, x)
    close(y, ref.experts(cfg, p, x, "f32"), 1e-5)
    as_it_was, _ = layer.clone(renorm_eps=1e-20).apply({"params": p}, x)
    assert DroplessMoE.renorm_eps == 1e-20
    assert np.max(np.abs(as_it_was - y)) > 1e-3 * np.max(np.abs(as_it_was))


@pytest.mark.parametrize("tile", [4, 8])
def test_the_familys_floor_of_tiles_changes_nothing(tile, monkeypatch):
    """The model's expert layer walks its family's floor, 5/4 uniform shares
    (`EXPERT_FLOOR_SHARES`), and gives what the same layer at the default
    two shares gives: the same result, the same count of assignments
    computed and the same five gradients (input, router, the three expert
    stacks), bit for bit, though the two walk different numbers of tiles.
    Tiles of 4 or 8 rows, so that both floors stand above the routing's
    need: 84 tokens, top 4 of 16 with 4 held, a share of 21, floors of 27
    and 42 tiles of 4 (14 and 21 of 8)."""
    from mmlspark_tpu.models import moe
    monkeypatch.setattr(moe, "GROUP_TILE", tile)
    own = build_model(small_config())._mlp(False)()
    assert own.floor_shares == lf.EXPERT_FLOOR_SHARES == 1.25
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
                           (lf.EXPERT_FLOOR_SHARES, moe.GROUP_FLOOR_SHARES))
    # routed, fullest, dropped and needed the same; walked the two floors
    assert stats[:4] == stats2[:4] and stats[2] == 0
    assert stats[3] <= floors[0] and (stats[4], stats2[4]) == floors
    close(y, y2, 0)

    def five(g):
        return [g[1]] + [g[0]["params"][n] for n in
                         ("router", "expert_gate", "expert_up", "expert_down")]
    for a, b in zip(five(grads), five(grads2)):
        assert np.any(np.asarray(a)) and np.array_equal(a, b)


def test_the_slices_logits_are_the_slice_of_the_whole_vocabularys():
    """A chip that holds rows 0-15 of a 64-row embedding (which is the head
    too), on ids drawn from its slice, gives the first 16 of the 64 logits
    the whole vocabulary's model gives; four such slices tile the whole."""
    whole = small_config()
    tok = tokens(vocab=16)
    m = build_model(whole)
    p = jax.device_get(m.init(jax.random.PRNGKey(0), tok[:1]))
    z = jax.jit(m.apply)(p, tok)
    E = p["params"]["embed"]["embedding"]
    h = m.apply(p, tok, output_layer="block2")
    x = ref.rmsnorm(h, p["params"]["norm"], whole["norm_eps"])
    parts = []
    for s in range(4):
        cut = jax.tree_util.tree_map(lambda a: a, p)
        cut["params"]["embed"] = {"embedding": E[16 * s:16 * s + 16]}
        if s == 0:
            part = jax.jit(build_model(small_config(vocab_size=16)).apply)(
                cut, tok)
        else:       # the ids are of slice 0: the other slices' heads alone
            part = x @ cut["params"]["embed"]["embedding"].T
        parts.append(part)
    close(parts[0], z[..., :16], 1e-6)
    close(jnp.concatenate(parts, axis=-1), z, 1e-5)


# ------------------------------------------------------------ the learner

def stream_of(batches):
    return lambda: iter(batches)


def learner_for(cfg, precision="f32", loss="next_token"):
    return (TpuLearner().setModelConfig(cfg).setBatchSize(8).setEpochs(1)
            .setOptimizer("adamw").setLearningRate(1e-3).setWeightDecay(0.1)
            .setPrecision(precision).setLoss(loss).setSeed(3))


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
    batches = [(rng.integers(0, 64, (8, 21)).astype(np.int32),
                rng.integers(0, 2, (8,)).astype(np.int32)) for _ in range(3)]
    model = learner_for(cfg).fitStream(stream_of(batches))
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


def test_fit_and_transform_as_every_token_model():
    from mmlspark_tpu.core.dataframe import DataFrame
    cfg = small_config(num_hidden_layers=2, layer_types=KINDS[:2])
    del cfg["dtype"]
    tok = tokens(B=16, T=12)
    df = DataFrame({"features": [r.astype(np.float32) for r in tok],
                    "label": np.zeros(16, np.int64)})
    model = learner_for(cfg, "bf16").setEpochs(2).fit(df)
    assert np.isfinite(model._final_loss)
    out = model.setOutputCol("scores").transform(df)
    assert np.asarray(out["scores"][0]).shape == (12, 64)


# ------------------------------------------------- registry, flops, spans

def load_cell_config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2_8b_a1b.json")) as f:
        return json.load(f)


def test_flops_hand_count_at_the_published_widths():
    """`benchmark/flops/lfm2_moe.py` against a count by hand, in
    multiply-adds a token forward, for the benchmark's own configuration
    (d 2048, 8 / 2 heads of 64 held, 8 of 32 experts held, 16,384 vocabulary
    rows, T 4096). Conv mixer: W_in 2048 x 6144 and W_out 2048 x 2048.
    Attention: W_q 2048 x 512, W_k and W_v 2048 x 128 each, W_o 512 x 2048,
    scores and values 4096 x 128 x 8 / 2. Experts: router 2048 x 32 and
    4 x 8 / 32 = 1 routed SwiGLU of 3 x 2048 x 1792. Dense: 3 x 2048 x 7168.
    Head 2048 x 16384, once (tied). Layers: conv + dense, attention +
    experts, 3 x (conv + experts). Twice that a token in operations, three
    times forward to train, nothing recomputed counted. The grouped flash
    calls: every query head's products, K and V moved at 2 heads."""
    from benchmark.flops import attention, lfm2_moe as flops
    cfg = load_cell_config()
    conv = 12_582_912 + 4_194_304
    attn = 1_048_576 + 2 * 262_144 + 1_048_576 + 2_097_152
    moe = 65_536 + 11_010_048
    dense, head = 44_040_192, 33_554_432
    assert flops.conv_macs_per_token(cfg) == conv == 16_777_216
    assert flops.attention_macs_per_token(cfg) == attn == 4_718_592
    assert flops.moe_macs_per_token(cfg) == moe
    assert flops.expected_assignments_per_token(cfg) * 32768 / 8 == 4096
    per_token = (conv + dense) + (attn + moe) + 3 * (conv + moe) + head
    assert flops.forward_macs_per_token(cfg) == per_token == 193_724_416
    assert flops.train_flops_per_row(cfg) == 3 * 2 * per_token * 4096
    assert abs(flops.train_flops_per_row(cfg) / 4.761e12 - 1) < 1e-3
    ops, nbytes = flops.gqa_flash_fwd(cfg, 8)
    assert ops == 4 * 8 * 8 * 4096 * 4096 * 64 / 2
    assert nbytes == (2 * 8 + 2 * 2) * 8 * 4096 * 64 * 2
    # ungrouped, the accepted count: the same operations, K and V at 8 heads
    assert (ops, 4 * 8 * 8 * 4096 * 64 * 2) == attention.flash_fwd(
        8, 8, 4096, 64, True)
    assert flops.gqa_flash_bwd(cfg, 8) == (2.5 * ops, 2 * nbytes)
    assert flops.short_conv_bytes(cfg, 8) == (4 * 8 * 4096 * 2048 * 2,
                                              8 * 8 * 4096 * 2048 * 2)


def test_the_configuration_counts_its_parameters():
    """The benchmark's configuration builds the model its file describes:
    499,955,968 parameters by the shapes of the seeded init, 8 query heads
    over 2 key/value heads of 64, and every number of the source either kept
    or listed in `reduced`."""
    cfg = load_cell_config()
    meta = ("source", "input", "learner", "published", "reduced",
            "departures", "assumed", "rehearsal", "parameters")
    model = build_model({k: v for k, v in cfg.items() if k not in meta})
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    count = sum(int(np.prod(a.shape))
                for a in jax.tree_util.tree_leaves(shapes))
    assert count == 499_955_968
    assert "499,955,968" in cfg["parameters"]
    assert shapes["params"]["block1"]["mixer"]["k_proj"]["kernel"].shape \
        == (2048, 128)
    published = {"conv_L_cache": 3, "hidden_size": 2048,
                 "intermediate_size": 7168, "moe_intermediate_size": 1792,
                 "norm_eps": 1e-5, "num_experts_per_tok": 4,
                 "rope_theta": 1000000, "routed_scaling_factor": 1,
                 "max_position_embeddings": 128000}
    assert {k: cfg[k] for k in published} == published
    cut = {"num_hidden_layers": 24, "num_dense_layers": 2, "num_experts": 32,
           "num_attention_heads": 32, "num_key_value_heads": 8,
           "vocab_size": 65536}
    for key, value in cut.items():
        assert cfg[key] != value and key in cfg["reduced"]
        assert cfg["published"][key] == value


def test_the_registry_knows_the_family():
    assert "lfm2_moe" in TOKEN_MODELS
    with pytest.raises(KeyError, match="lfm2_moe"):
        build_model({"type": "lfm2_moe_9000"})
    x = example_input({"type": "lfm2_moe", "seq_len": 12}, batch=3)
    assert x.shape == (3, 12) and x.dtype == jnp.int32
    assert has_experts({"type": "lfm2_moe", "num_experts": 8})
    assert not has_experts({"type": "lfm2_moe", "num_experts": 0})
    for key, bad in [("conv_bias", True), ("use_expert_bias", False),
                     ("tie_word_embeddings", False)]:
        with pytest.raises(ValueError, match=key):
            build_model(small_config(**{key: bad}))
    with pytest.raises(ValueError, match="layer_types"):
        build_model(small_config(num_hidden_layers=4))
    with pytest.raises(ValueError, match="layer kind"):
        m = build_model(small_config(layer_types=["conv", "window", "conv"]))
        m.init(jax.random.PRNGKey(0), tokens()[:1])
    with pytest.raises(ValueError, match="key/value"):
        m = build_model(small_config(num_attention_heads=3))
        m.init(jax.random.PRNGKey(0), tokens()[:1])
    with pytest.raises(ValueError, match="no position to score"):
        m = build_model(small_config())
        p = m.init(jax.random.PRNGKey(0), tokens()[:1])
        m.apply(p, tokens(T=1), row_losses=True)


@pytest.mark.parametrize("on", [True, False])
def test_step_values_reach_the_ring_only_with_telemetry_on(on):
    """With telemetry on a stream fit records one `fit/step_stats` a step
    with the loss, the positions scored and the expert layers' counts, and
    the static counters say what was built (conv mixers by kernel size,
    experts held, router width, vocabulary rows, the head's chunks); off,
    the step program has no such output and nothing is recorded."""
    was = telemetry.enabled()
    (telemetry.enable if on else telemetry.disable)()
    try:
        telemetry.trace.clear()
        snap0 = telemetry.snapshot()
        cfg = small_config(remat=True)
        del cfg["dtype"]
        rng = np.random.default_rng(2)
        # 6 rows a batch: fitStream pads to 8, and the two padded rows score
        # nothing
        batches = [(rng.integers(0, 64, (6, 16)).astype(np.int32),
                    np.zeros(6, np.int32)) for _ in range(3)]
        model = learner_for(cfg, "bf16").fitStream(stream_of(batches))
        events = telemetry.trace.events()
        stats = [e["args"] for e in events if e["name"] == "fit/step_stats"]
        if not on:
            assert stats == []
            return
        assert [s["step"] for s in stats] == [0, 1, 2]
        assert all(s["lm_tokens_scored"] == 6 * 15 for s in stats)
        assert all(s["moe_tokens_dropped"] == 0 for s in stats)
        assert all(s["moe_expert_tokens_max"] > 0 for s in stats)
        assert abs(stats[-1]["lm_loss_main"] - model._final_loss) \
            < 1e-4 * model._final_loss
        snap = telemetry.snapshot()

        def grew(name, **labels):
            def value(s):
                return sum(x["value"] for x in s.get(name, {"series": []})[
                    "series"] if all(x["labels"].get(k) == v
                                     for k, v in labels.items()))
            return value(snap) - value(snap0)
        assert grew("mmlspark_short_conv_mixers_total", kernel_size="3") >= 2
        assert grew("mmlspark_lm_loss_chunks_total", head="main") >= 2
        assert grew("mmlspark_lm_vocab_rows") >= 64
        assert grew("mmlspark_moe_experts_held") >= 2 * 4
        assert grew("mmlspark_moe_router_width") >= 2 * 16
    finally:
        (telemetry.enable if was else telemetry.disable)()


def test_the_conv_core_is_traced_under_the_scope_its_metric_reads():
    """What lies between the conv mixer's two projections carries the scope
    `short_conv` in the lowered program (forward and backward), and the
    projections do not."""
    x = jnp.zeros((2, 16, 32), F32)
    mixer = lf.ShortConvMixer(3, F32)
    p = mixer.init(jax.random.PRNGKey(0), x)
    text = jax.jit(jax.grad(lambda p, x: jnp.sum(mixer.apply(p, x)))).lower(
        p, x).as_text(debug_info=True)
    scoped = [line for line in text.splitlines()
              if f"/{lf.SHORT_CONV_SCOPE}/" in line]
    assert scoped and not any("dot_general" in line for line in scoped)


FIXTURE = os.path.join(ROOT, "benchmark", "fixtures",
                       "short_conv_mixer.xplane.pb")


def test_scoped_operations_of_a_recorded_trace(monkeypatch):
    """`benchmark/scope_ops.py` on a trace recorded on the chip (my chip
    run, PR 34: the gradient of one conv mixer at 8 x 1,024 x 2,048, five
    runs traced, three whole): the operations whose `op_name` lies under
    `short_conv` are the core's loop fusions and not the projections'
    products, and `short_conv_ms` adds their window time up: 0.3434 +
    0.1783 + 0.1024 ms a run and four operations under a microsecond."""
    from benchmark import scope_ops, trace_reduce
    from benchmark.layer_metrics import short_conv_ms
    names = scope_ops.under_scope(FIXTURE, "short_conv")
    assert {"slice_multiply_fusion.3", "fusion.36",
            "broadcast_multiply_fusion.1"} <= names
    ops = scope_ops.op_names(FIXTURE)
    assert any("in_proj/dot_general" in t for t in ops["fusion.12"])
    assert not names & {"fusion.12", "fusion.15", "fusion.26",
                        "broadcast_multiply_fusion.2"}
    assert scope_ops.under_scope(FIXTURE, "short") == set()     # whole parts
    trace = trace_reduce.summarise(trace_reduce.load_events(FIXTURE))
    assert trace["steps"] == 3
    monkeypatch.setattr(scope_ops, "traced_run_file", lambda: FIXTURE)
    cell = {"config": load_cell_config()}
    assert abs(short_conv_ms.read(trace, {}, cell) - 0.62489) < 1e-4
    monkeypatch.setattr(scope_ops, "traced_run_file", lambda: None)
    assert short_conv_ms.read(trace, {}, cell) is None


@pytest.mark.parametrize("name", ["short_conv_ms", "gqa_flash_fwd_roofline",
                                  "gqa_flash_bwd_roofline",
                                  "lfm2_expert_tokens_max"])
def test_the_new_readers_return_none_on_another_familys_cell(name,
                                                             monkeypatch):
    """Each new per-layer reader on a trace and counters that hold the very
    things it looks for, but under another family's configuration, finds
    nothing and says None (never 0); an empty trace under its own
    configuration reads None too."""
    import importlib
    from benchmark import scope_ops
    from benchmark.layer_metrics import moe_expert_tokens_max
    reader = importlib.import_module(f"benchmark.layer_metrics.{name}")
    monkeypatch.setattr(scope_ops, "traced_run_file", lambda: FIXTURE)
    monkeypatch.setattr(
        moe_expert_tokens_max, "window_stats", lambda counters: [
            {"moe_expert_tokens_max": 5000, "moe_tokens_dropped": 0}])
    label = "bf16[8,4096,2048]{2,1,0} fusion(bf16[8,4096,6144]{2,1,0} %f)"
    names = {"flash_fwd.1": "", "flash_dq.1": "", "flash_dkv.1": "",
             "slice_multiply_fusion.3": label}
    trace = {"steps": 3, "window_s": 2.0, "op_s": dict.fromkeys(names, 0.01),
             "op_n": dict.fromkeys(names, 3), "op_label": names}
    counters = {"batch_rows": 8, "window_steps": 3}
    peaks = {"flops_bf16": 197e12, "bytes_per_s": 819e9}
    own = {"cell": {"name": "lfm2moe_train_stream"}, "chips": 1,
           "peaks": peaks, "config": load_cell_config()}
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "joyai_llm_flash_48b_a3b.json")) as f:
        other = dict(own, config=json.load(f))
    assert reader.read(trace, counters, other) is None
    assert reader.read(trace, counters, own) is not None
    empty = dict(trace, op_s={}, op_n={}, op_label={})
    if name != "lfm2_expert_tokens_max":
        assert reader.read(empty, counters, own) is None


# --------------------------------- the tile's rows, its counts, their readers

def _loop(carried):
    return (f"(s32[], {carried}{{1,0}}, s32[]) while((s32[], "
            f"{carried}{{1,0}}, s32[]) %tuple), condition=%c, body=%b")


@pytest.mark.parametrize("tile", [256, 1024])
def test_the_tile_loops_are_read_whatever_a_tiles_rows(tile):
    """`lfm2_moe_grouped_ms` sums the `while` operations that carry the
    float32 (tokens, hidden) accumulator after the counter and the sort over
    one entry an assignment; what a tile gathers (`bf16[rows, hidden]` in the
    body) is not part of the rule, the tied head's walks and the router's
    top-k sort are not counted, another family's cell reads None."""
    from benchmark.layer_metrics import lfm2_moe_grouped_ms as reader
    labels = {
        "while.19": _loop("f32[32768,2048]"), "while.23": _loop(
            "f32[32768,2048]"),
        "while.27": _loop("f32[8]"),            # the head's forward walk
        "sort.3": "(s32[131072], s32[131072]) sort(s32[131072] %a)",
        "sort.9": "(f32[32768,32], s32[32768,32]) sort(f32[32768,32] %s)",
        f"gather_fusion.{tile}": f"bf16[{tile},2048] fusion(%x)"}
    seconds = {"while.19": 0.06, "while.23": 0.15, "while.27": 0.03,
               "sort.3": 0.004, "sort.9": 0.002, f"gather_fusion.{tile}": 0.01}
    trace = {"steps": 2, "op_s": seconds, "op_n": dict.fromkeys(seconds, 2),
             "op_label": labels}
    counters = {"batch_rows": 8}
    own = {"chips": 1, "config": load_cell_config()}
    assert abs(reader.read(trace, counters, own) - 107.0) < 1e-9
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "joyai_llm_flash_48b_a3b.json")) as f:
        assert reader.read(trace, counters, dict(own, config=json.load(f))) \
            is None
    assert reader.read(dict(trace, op_s={}, op_n={}, op_label={}), counters,
                       own) is None
    assert reader.read(trace, {}, own) is None


@pytest.mark.parametrize("stats,want", [
    ([], None),                                         # telemetry off
    ([{"moe_expert_tokens_max": 4660, "moe_tokens_dropped": 0}] * 3, None),
    ([{"moe_tiles_needed": 140, "moe_tiles_walked": 256},
      {"moe_tiles_needed": 128, "moe_tiles_walked": 256},
      {"moe_tiles_needed": 300, "moe_tiles_walked": 300}], 54.6875),
])
def test_the_needed_share_is_the_median_of_needed_over_walked(stats, want,
                                                              monkeypatch):
    """`lfm2_moe_tiles_needed_share`: per cent of the walked tiles that the
    routing filled, the median over the window's steps; None (never 0, and
    no exception) on a program that records neither count, as the parent of
    the PR that brought them, and on another family's cell."""
    from benchmark.layer_metrics import (lfm2_moe_tiles_needed_share as
                                         reader, moe_expert_tokens_max)
    monkeypatch.setattr(moe_expert_tokens_max, "window_stats",
                        lambda counters: stats)
    own = {"chips": 1, "config": load_cell_config()}
    assert reader.read({}, {"window_steps": 3}, own) == want
    assert reader.read({}, {"window_steps": 3},
                       {"chips": 1, "config": {"type": "kimi_linear"}}) is None


def test_the_models_step_counts_carry_the_tiles():
    """The model's `step_stats` sum the expert layers' tiles: two expert
    layers of 84 tokens, top 4 of 16 with 4 held (a share of 21: 256 rows a
    tile, a floor of one tile a layer), so every held expert that got a
    token needs one tile and the walk is what is needed."""
    cfg = small_config()
    model = build_model(cfg)
    x = tokens()
    p = model.init(jax.random.PRNGKey(0), x)
    _, stats = jax.jit(functools.partial(model.apply, step_stats=True,
                                         row_losses=True))(p, x)
    needed, walked = (int(stats[n]) for n in ("moe_tiles_needed",
                                              "moe_tiles_walked"))
    assert 2 <= needed == walked <= 2 * 4
    assert int(stats["moe_tokens_dropped"]) == 0


@pytest.mark.parametrize("argv,want", [
    ([], ((32768, 2048, 1792, 8, 4, 32), lf.EXPERT_FLOOR_SHARES,
          (256, 512, 1024, 2048), 0)),
    (["--cell", "kimilinear", "--rows", "256"],
     ((16384, 2304, 1024, 8, 8, 256), 2, (256,), 0)),
    (["--cell", "joyai", "--ops", "5"],
     ((32768, 2048, 768, 8, 8, 256), 2, (256, 512, 1024, 2048), 5)),
    (["--shape", "64,16,8,3,2,6", "--rows", "8,16"],
     ((64, 16, 8, 3, 2, 6), lf.EXPERT_FLOOR_SHARES, (8, 16), 0)),
    (["--cell", "joyai", "--shape", "64,16,8,3,2,6", "--rows", "8,16"],
     ((64, 16, 8, 3, 2, 6), 2, (8, 16), 0)),
    (["--shape", "64,16,8,7,2,6"], SystemExit),       # more held than routed
    (["--shape", "64,16,8"], SystemExit),
    (["--rows", "100"], SystemExit),
    (["--cell", "cgpt"], SystemExit),
])
def test_the_walks_timer_reads_its_arguments(argv, want, monkeypatch, capsys):
    """`tools/time_grouped_mlp.py` on the CPU: a cell's shape comes from its
    two benchmark files and its floor from its family (lfm2moe's 5/4
    uniform shares, the others' two; `--shape` keeps the cell's floor), a
    shape or tile sizes that cannot be are refused, the seeded routing's
    lists are `DroplessMoE`'s (every assignment to a held expert once,
    sorted), its two programs give the same numbers at two tile sizes and
    walk the floor's tiles, and it times nothing where there is no chip."""
    monkeypatch.syspath_prepend(os.path.join(ROOT, "tools"))
    import time_grouped_mlp as timer
    if want is SystemExit:
        with pytest.raises(SystemExit):
            timer.parse(argv)
        return
    shape, shares, sizes, ops = timer.parse(argv)
    assert (shape, shares, sizes, ops) == want
    if shape[0] > 64:
        return
    N, d, f, E, k, W = shape
    data = timer.inputs(shape)
    token, counts = np.asarray(data[4]), np.asarray(data[6])
    assert token.shape == (N * k,) and 0 < counts.sum() < N * k
    assert np.bincount(token[:counts.sum()], minlength=N).max() <= E
    outs = []
    for rows in sizes:
        fns, floor = timer.programs(shape, shares, rows)
        assert floor == -(-shares * N * k * E // (W * rows))
        outs.append(jax.tree_util.tree_leaves(
            (fns["fwd"](*data), fns["fwd_bwd"](*data))))
    for a, b in zip(*outs):
        close(a.astype(F32), b.astype(F32), 1e-2)     # bfloat16 results
    monkeypatch.setattr(sys, "argv", ["time_grouped_mlp.py"] + argv)
    with pytest.raises(SystemExit, match="needs a TPU"):
        timer.main()


def test_the_tile_counter_reads_every_expert_layer(monkeypatch):
    """`tools/count_tiles_needed.py` on the CPU at a small size: for each
    seed and each batch of its pool, the fullest expert and the tiles needed
    of every expert layer at the seed's fresh init; their maximum and sum
    are the model's own step counts for that batch, and the summary's
    candidate floors are `moe.floor_tiles` of their shares. It refuses the
    cell's own sizes where there is no chip."""
    monkeypatch.syspath_prepend(os.path.join(ROOT, "tools"))
    import count_tiles_needed as counter
    from benchmark.drivers import train_stream
    from mmlspark_tpu.models import moe
    config = dict(small_config(), num_classes=2,
                  input={"kind": "token_ids_int32", "seq_len": 21},
                  learner={"optimizer": "adamw", "learningRate": 1e-3,
                           "precision": "f32", "loss": "next_token"})
    traffic = {"batch_rows": 4, "pool_batches": 2, "prefetch_depth": 1,
               "label_rule": "sum_mod_classes"}
    records = list(counter.layer_counts(config, traffic, [5, 6]))
    assert [seed for seed, _ in records] == [5, 6]
    for seed, batches in records:
        assert len(batches) == 2
        pool = train_stream.make_pool(config, traffic, seed)
        learner = train_stream.build_learner(config, traffic, seed)
        model = build_model(dict(learner.getModelConfig()))
        p = model.init(jax.random.PRNGKey(learner.getSeed()),
                       jnp.asarray(pool[0][0][:1]))
        for (x, _), layers in zip(pool, batches):
            assert [c[0] for c in layers] == ["block1/mlp", "block2/mlp"]
            _, stats = model.apply(p, jnp.asarray(x), step_stats=True,
                                   row_losses=True)
            assert max(c[1] for c in layers) == int(
                stats["moe_expert_tokens_max"])
            assert sum(c[2] for c in layers) == int(stats["moe_tiles_needed"])
    got = counter.summary(config, traffic, records)
    assert (got["share"], got["rows"], got["layer_steps"]) == (21, 256, 8)
    assert got["fullest"] == max(c[1] for _, b in records for bb in b
                                 for c in bb)
    assert {s: f["tiles"] for s, f in got["floors"].items()} == {
        str(s): moe.floor_tiles(84, 4, 4, 16, 256, s)
        for s in counter.FLOORS}
    needed = [c[2] for _, b in records for bb in b for c in bb]
    assert {s: f["layer_steps_past"] for s, f in got["floors"].items()} == {
        s: sum(n > f["tiles"] for n in needed)
        for s, f in got["floors"].items()}
    assert got["floors"]["1"]["layer_steps_past"] > 0    # 4 tiles for 1
    assert counter.parse(["--workload", "w", "--seeds", "3-5"])[1] == \
        range(3, 6)
    with pytest.raises(SystemExit, match="needs a TPU"):
        counter.main(["--workload", "lfm2moe_train_stream", "--seeds", "1"])
