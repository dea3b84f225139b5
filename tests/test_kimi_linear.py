"""The `kimi_linear` family against its plain reference, on the CPU at small
sizes with seeded weights.

The reference is `benchmark/reference/kimi_linear.py` (jax.numpy, float32,
nothing of the program imported): KDA token by token, latent attention as a
full masked softmax, the expert layer as a loop over the held experts. The
program runs here in float32 too, so every tolerance below is the room two
orders of float32 summation need (1e-5 relative on values of order one, a
little more through a backward pass or three optimizer steps), never a
precision's: a wrong term reads 1e-2 and more.
"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.reference import kimi_linear as ref            # noqa: E402
from mmlspark_tpu import telemetry                            # noqa: E402
from mmlspark_tpu.models import TpuLearner, build_model       # noqa: E402
from mmlspark_tpu.models import kimi_linear as kl             # noqa: E402
from mmlspark_tpu.models.modules import (example_input,       # noqa: E402
                                         has_experts)
from mmlspark_tpu.models.moe import DroplessMoE, grouped_expert_mlp  # noqa: E402
from mmlspark_tpu.ops import delta_rule                       # noqa: E402
from mmlspark_tpu.ops.delta_rule import chunked_delta_rule    # noqa: E402

F32 = jnp.float32


def small_config(**over):
    """Five layers as the benchmark's cut (KDA dense, KDA, KDA, MLA, KDA with
    experts), 4 of 16 experts and 2 heads held, every width tiny."""
    cfg = {"type": "kimi_linear", "vocab_size": 64, "hidden_size": 32,
           "num_hidden_layers": 5, "first_k_dense_replace": 1,
           "num_attention_heads": 2,
           "linear_attn_config": {"kda_layers": [1, 2, 3, 5],
                                  "full_attn_layers": [4], "head_dim": 8,
                                  "num_heads": 2,
                                  "short_conv_kernel_size": 4},
           "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
           "v_head_dim": 8, "intermediate_size": 48,
           "moe_intermediate_size": 16, "num_experts": 4, "router_width": 16,
           "first_expert_held": 0, "num_experts_per_token": 4,
           "num_shared_experts": 1, "moe_renormalize": True,
           "routed_scaling_factor": 2.446, "rms_norm_eps": 1e-5,
           "kda_chunk_size": 8, "num_classes": 2, "pool": "mean",
           "dtype": "float32"}
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


# ------------------------------------------------------- the chunked scan

def recurrence(q, k, v, g, beta, scale):
    """S_t = (I - b k k^T) Diag(a) S_{t-1} + b k v^T; o_t = scale S_t^T q."""
    B, T, H, K = q.shape

    def token(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = jnp.exp(g_t)[..., None] * S
        err = v_t - jnp.einsum("bhk,bhkv->bhv", k_t, S)
        S = S + jnp.einsum("bhk,bhv->bhkv", b_t[..., None] * k_t, err)
        return S, scale * jnp.einsum("bhk,bhkv->bhv", q_t, S)

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(token, jnp.zeros((B, H, K, v.shape[-1]), F32), xs)
    return jnp.moveaxis(o, 0, 1)


def scan_inputs(T, decay, seed=0, B=2, H=2, K=8, V=8):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (B, T, H, K), F32))
    k = unit(jax.random.normal(ks[1], (B, T, H, K), F32))
    v = jax.random.normal(ks[2], (B, T, H, V), F32)
    u = jax.random.uniform(ks[3], (B, T, H, K), F32)
    # log decay a step: near 1 (a ~ 0.9999), as the module's init draws it
    # (a in 0.2-0.999), near 0 (a ~ e^-12: k / exp(G) would overflow float32
    # within a chunk of 8), and all three among the channels
    g = {"near_one": -1e-4 * u, "moderate": -1.6 * u, "near_zero": -12.0 * u,
         "mixed": -12.0 * u ** 6}[decay]
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H), F32))
    return q, k, v, g, beta


@pytest.mark.parametrize("decay", ["near_one", "moderate", "near_zero",
                                   "mixed"])
@pytest.mark.parametrize("T,chunk", [(37, 8), (16, 16), (5, 64), (70, 32),
                                     (130, 64), (96, 48), (64, 64)])
def test_chunked_scan_matches_recurrence(T, chunk, decay):
    """Values and all five gradients; T = 37 is no multiple of the chunk
    (the pad must leave the state alone), T = 5 is shorter than one, and
    chunks of 32, 48 and 64 take two, three and four diagonal blocks, the
    decay products left of them as products over the channels."""
    args = scan_inputs(T, decay)
    ct = jax.random.normal(jax.random.PRNGKey(9), args[2].shape, F32)

    def loss(fn, *a):
        return jnp.sum(fn(*a) * ct)

    chunked = functools.partial(chunked_delta_rule, chunk=chunk, scale=0.35)
    plain = functools.partial(recurrence, scale=0.35)
    close(chunked(*args), plain(*args), 2e-5)
    got = jax.grad(functools.partial(loss, chunked), argnums=range(5))(*args)
    want = jax.grad(functools.partial(loss, plain), argnums=range(5))(*args)
    for a, b in zip(got, want):
        assert np.all(np.isfinite(np.asarray(a)))
        close(a, b, 1e-4)


def test_chunked_scan_under_the_hardest_decay():
    """Log-decays down to -40 a token a channel at chunk 64: a channel falls
    by e^-2500 inside a chunk, k / exp(G) would overflow float32 after three
    tokens, and a row block's two factors underflow only where their product
    does. Outputs and gradients finite and the recurrence's."""
    q, k, v, g, beta = scan_inputs(128, "near_zero")
    g = g * (40.0 / 12.0)
    assert float(g.min()) < -39.0
    ct = jax.random.normal(jax.random.PRNGKey(9), v.shape, F32)

    def loss(fn, *a):
        return jnp.sum(fn(*a) * ct)

    chunked = functools.partial(chunked_delta_rule, chunk=64, scale=0.35)
    plain = functools.partial(recurrence, scale=0.35)
    args = (q, k, v, g, beta)
    got = chunked(*args)
    assert np.all(np.isfinite(np.asarray(got)))
    close(got, plain(*args), 2e-5)
    grads = jax.grad(functools.partial(loss, chunked), argnums=range(5))(*args)
    want = jax.grad(functools.partial(loss, plain), argnums=range(5))(*args)
    for a, b in zip(grads, want):
        assert np.all(np.isfinite(np.asarray(a)))
        close(a, b, 1e-4)


def test_chunked_scan_with_one_key_repeated():
    """The same key at every token, steps near 1, decay near 1: the system
    of a chunk is the all-ones lower triangle, whose inverse is bidiagonal
    while its powers grow like binomials. Blocks of 16 keep them within
    float32 (C(15, 7) = 6,435 under an epsilon of 6e-8, hence 1e-3); a
    whole chunk's powers reach C(63, 31) and would read nonsense."""
    q, k, v, g, beta = scan_inputs(128, "near_one")
    k = jnp.broadcast_to(k[:, :1], k.shape)
    beta = jnp.full_like(beta, 0.999)
    got = chunked_delta_rule(q, k, v, g, beta, chunk=64, scale=1.0)
    close(got, recurrence(q, k, v, g, beta, 1.0), 1e-3)


def intermediate_shapes(jaxpr):
    """Shapes of the arrays the jaxpr's equations make, those of the jaxprs
    they call among them."""
    shapes = set()
    for eqn in jaxpr.eqns:
        shapes |= {tuple(v.aval.shape) for v in eqn.outvars}
        for sub in jax.core.jaxprs_in_params(eqn.params):
            shapes |= intermediate_shapes(getattr(sub, "jaxpr", sub))
    return shapes


@pytest.mark.parametrize("C,tensor", [
    (64, (1, 1, 4, 16, 16, 128)),   # the four diagonal blocks' (t, s, channel)
    (48, (1, 1, 3, 16, 16, 128)),
    (16, (1, 1, 16, 16, 128)),      # one block: the (C, C, K) tensor, as it was
    (8, (1, 1, 8, 8, 128))])
def test_chunk_step_builds_the_decay_tensor_in_diagonal_blocks_only(
        C, tensor):
    K = 128
    row = jax.ShapeDtypeStruct((1, 1, C, K), F32)
    xs = (row, row, row, row, jax.ShapeDtypeStruct((1, 1, C), F32))
    state = jax.ShapeDtypeStruct((1, 1, K, K), F32)
    step = functools.partial(delta_rule._chunk_step, scale=1.0)
    shapes = intermediate_shapes(jax.make_jaxpr(step)(state, xs).jaxpr)
    assert tensor in shapes
    # nothing larger than that tensor or the state
    assert max(map(np.prod, shapes)) == max(np.prod(tensor), K * K)


@pytest.mark.parametrize("T,chunk,exps", [
    (130, 64, 2 * 3 * 3 * 4 * 256 * 8),     # B H nc n 16 16 K
    (96, 48, 2 * 3 * 2 * 3 * 256 * 8),
    (37, 8, 2 * 3 * 5 * 8 * 8 * 8),         # B H nc C C K
    (5, 64, 2 * 3 * 1 * 5 * 5 * 8)])
def test_decay_exps_counter_follows_the_blocks(T, chunk, exps):
    was = telemetry.enabled()
    telemetry.enable()
    try:
        layer = f"counted/{T}/{chunk}"
        args = scan_inputs(T, "moderate", H=3)
        jax.eval_shape(functools.partial(chunked_delta_rule, chunk=chunk,
                                         layer=layer), *args)
        snap = telemetry.snapshot()
        read = lambda name: {s["labels"]["layer"]: s["value"]
                             for s in snap[name]["series"]}[layer]
        assert read("mmlspark_kda_decay_exps_total") == exps
        assert read("mmlspark_kda_chunks_total") == 2 * 3 * -(-T // chunk)
    finally:
        (telemetry.enable if was else telemetry.disable)()


# ------------------------------------------------- the layers, one by one

def layer_config(heads=2):
    cfg = small_config(num_attention_heads=heads)
    cfg["linear_attn_config"] = dict(cfg["linear_attn_config"],
                                     num_heads=heads)
    return cfg


attention = kl.causal_attention("blockwise", 8)


def make_mixer(kind, cfg):
    lin = cfg["linear_attn_config"]
    if kind == "kda":
        return kl.KDALayer(lin["num_heads"], lin["head_dim"], 4,
                           cfg["kda_chunk_size"], 1e-5, F32)
    return kl.MLALayer(cfg["num_attention_heads"], cfg["kv_lora_rank"],
                       cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                       cfg["v_head_dim"], attention, 1e-5, F32)


@pytest.mark.parametrize("kind", ["kda", "mla"])
def test_mixer_matches_reference(kind):
    """KDA (projections, convolutions, decay, gate and all) and the latent
    layer at query/key width 12 against value width 8 (the published 192
    against 128 in small), values and gradients, T = 19 over chunks of 8."""
    cfg = layer_config()
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 19, 32), F32)
    layer = make_mixer(kind, cfg)
    p = layer.init(jax.random.PRNGKey(2), x)
    plain_fn = {"kda": ref.kda, "mla": ref.mla}[kind]
    plain = jax.jit(lambda p, x: plain_fn(cfg, p["params"], x, "f32"))
    close(jax.jit(layer.apply)(p, x), plain(p, x), 2e-5)
    ct = jax.random.normal(jax.random.PRNGKey(3), x.shape, F32)
    grad = lambda f: jax.jit(jax.grad(lambda p, x: jnp.sum(f(p, x) * ct),
                                      argnums=(0, 1)))(p, x)
    trees_close(grad(layer.apply), grad(plain), 1e-4)


def make_experts(cfg):
    return DroplessMoE(
        num_experts=cfg["num_experts"], router_width=cfg["router_width"],
        d_hidden=cfg["moe_intermediate_size"],
        top_k=cfg["num_experts_per_token"],
        first_expert=cfg["first_expert_held"],
        num_shared=cfg["num_shared_experts"], renormalize=True,
        routed_scale=cfg["routed_scaling_factor"], dtype=F32)


def with_bias(p, ids, width):
    """The layer's parameters with the selection bias raised on `ids`."""
    bias = jnp.zeros((width,), F32).at[jnp.asarray(ids)].set(10.0)
    return {"params": dict(p["params"], selection_bias=bias)}


@pytest.mark.parametrize("routing", ["seeded", "all_to_one_held",
                                     "none_held", "all_held_chosen"])
def test_experts_match_reference_and_drop_nothing(routing):
    """The dropless layer against the loop over held experts, values and
    gradients, under the seeded routing and under three forced by the
    selection bias: every token on held expert 1 (and three experts held
    elsewhere), no token on any held expert, every token on all four held.
    The shapes are the same in all four; the counts say nothing was dropped."""
    cfg = small_config()
    B, T = 2, 150            # 300 tokens: more than one tile of 256 rows
    x = jax.random.normal(jax.random.PRNGKey(4), (B, T, 32), F32)
    layer = make_experts(cfg)
    p = layer.init(jax.random.PRNGKey(5), x)
    forced = {"all_to_one_held": [1, 9, 10, 11], "none_held": [8, 9, 10, 11],
              "all_held_chosen": [0, 1, 2, 3]}
    if routing in forced:
        p = with_bias(p, forced[routing], 16)
    y, stats = jax.jit(layer.apply)(p, x)
    routed, fullest, dropped = (int(s) for s in stats[:3])
    N = B * T
    want = {"all_to_one_held": (N, N), "none_held": (0, 0),
            "all_held_chosen": (4 * N, N)}.get(routing)
    if want:
        assert (routed, fullest) == want
    else:
        assert 0 < fullest < routed < 4 * N
    assert dropped == 0
    plain = jax.jit(lambda p, x: ref.experts(cfg, p["params"], x, "f32"))
    close(y, plain(p, x), 2e-5)
    ct = jax.random.normal(jax.random.PRNGKey(6), x.shape, F32)
    grad = lambda f: jax.jit(jax.grad(lambda p, x: jnp.sum(f(p, x) * ct),
                                      argnums=(0, 1)))(p, x)
    got = grad(lambda p, x: layer.apply(p, x)[0])
    trees_close(got, grad(plain), 1e-4)
    assert not np.any(np.asarray(got[0]["params"]["selection_bias"]))


def test_grouped_experts_ignore_the_tail_and_padded_rows():
    """`grouped_expert_mlp` reads only the first sum(counts) assignments:
    what follows them in the list (assignments to experts held elsewhere)
    changes nothing, and a row mask of zero routes a row's tokens nowhere."""
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    N, d, f, E = 40, 16, 8, 3
    x = jax.random.normal(ks[0], (N, d), F32)
    wg, wu = (jax.random.normal(k, (E, d, f), F32) for k in ks[1:3])
    wd = jax.random.normal(ks[3], (E, f, d), F32)
    counts = jnp.asarray([5, 0, 7], jnp.int32)
    token = jax.random.permutation(ks[4], N)[:20].astype(jnp.int32)
    weight = jax.random.uniform(ks[5], (20,), F32)
    y, rows = grouped_expert_mlp(x, wg, wu, wd, token, weight, counts, 0)
    y2, _ = grouped_expert_mlp(x, wg, wu, wd, token.at[12:].set(3),
                               weight.at[12:].set(99.0), counts, 0)
    assert int(rows) == 12
    close(y, y2, 0)
    want = np.zeros((N, d))
    for j in range(12):
        e = 0 if j < 5 else 2
        h = jax.nn.silu(x[token[j]] @ wg[e]) * (x[token[j]] @ wu[e])
        want[int(token[j])] += float(weight[j]) * np.asarray(h @ wd[e])
    close(y, want, 2e-5)

    cfg = small_config()
    xb = jax.random.normal(ks[0], (4, 10, 32), F32)
    layer = make_experts(cfg)
    p = layer.init(ks[1], xb)
    mask = jnp.asarray([1.0, 0.0, 1.0, 0.0])
    y_masked, stats = layer.apply(p, xb, mask)
    y_two, stats_two = layer.apply(p, xb[::2])
    assert int(stats[0]) == int(stats_two[0]) and int(stats[2]) == 0
    close(y_masked[::2], y_two, 2e-5)


@pytest.mark.parametrize("tile", [4, 8])
@pytest.mark.parametrize("counts", [(5, 0, 7), (0, 0, 0), (0, 20, 0)])
def test_grouped_experts_floor_of_tiles_changes_nothing(counts, tile,
                                                        monkeypatch):
    """A walk held to more tiles than the routing needs (`min_tiles`: the
    tiles past the routing's own have no real row) gives the same result,
    the same gradients and the same count of assignments computed, bit for
    bit: what it adds is zeros. Tiles of 4 or 8 (the default an unnamed
    `rows` takes), so that an expert has several."""
    from mmlspark_tpu.models import moe
    monkeypatch.setattr(moe, "GROUP_TILE", tile)
    ks = jax.random.split(jax.random.PRNGKey(8), 7)
    N, d, f, E = 40, 16, 8, 3
    x = jax.random.normal(ks[0], (N, d), F32)
    wg, wu = (jax.random.normal(k, (E, d, f), F32) for k in ks[1:3])
    wd = jax.random.normal(ks[3], (E, f, d), F32)
    token = jax.random.permutation(ks[4], N)[:24].astype(jnp.int32)
    weight = jax.random.uniform(ks[5], (24,), F32)
    ct = jax.random.normal(ks[6], (N, d), F32)
    counts = jnp.asarray(counts, jnp.int32)

    def run(min_tiles):
        def f(x, wg, wu, wd, weight):
            y, rows = grouped_expert_mlp(x, wg, wu, wd, token, weight,
                                         counts, min_tiles)
            return jnp.sum(y * ct), (y, rows)
        (_, out), grads = jax.value_and_grad(f, argnums=(0, 1, 2, 3, 4),
                                             has_aux=True)(x, wg, wu, wd,
                                                           weight)
        return out, grads

    (y, rows), grads = run(0)
    (y2, rows2), grads2 = run(11)
    assert int(rows) == int(rows2) == int(counts.sum())
    close(y, y2, 0)
    for a, b in zip(grads, grads2):
        close(a, b, 0)
    # the weights' gradient past the assignments computed stays zero
    assert not np.any(np.asarray(grads2[4])[int(counts.sum()):])


@pytest.mark.parametrize("cell,want", [
    ("kimilinear", (16384, 8, 256, 256, 32)),
    ("joyai", (32768, 8, 256, 256, 64)),
    ("lfm2moe", (32768, 4, 32, 1024, 40)),
    ("laguna", (32768, 10, 256, 256, 50)),
    # the layers this file, test_joyai_llm_flash and test_lfm2_moe build
    ((300, 4, 16), 256), ((40, 4, 16), 256), ((42, 4, 32), 256),
    ((128, 4, 16), 256), ((4 * 21, 4, 16), 256),
    # the rule's steps: four tiles a share, doubling, 1,024 at the most
    ((1, 1, 4), 256), ((2047, 1, 1), 256), ((2048, 1, 1), 512),
    ((4095, 1, 1), 512), ((4096, 1, 1), 1024), ((1 << 20, 8, 8), 1024),
])
def test_tile_rows_follow_the_layers_static_load(cell, want, monkeypatch):
    """`moe.tile_rows` of a uniform share N * k // W: at least four tiles a
    share, 256 rows at the least and 1,024 at the most. The four expert
    cells' shapes, read from their benchmark files as the walk's timer reads
    them, give 256 / 256 / 1,024 / 256 rows, and their models, built from
    the same files as the benchmark builds them and traced at a step's batch
    (nothing runs), walk at least 32 / 64 tiles of 256 (two uniform shares,
    the default), 40 of 1,024 (lfm2_moe's own 5/4) and 50 of 256 (laguna's
    own 5/4) in every expert layer."""
    from mmlspark_tpu.models import moe
    if isinstance(cell, str):
        monkeypatch.syspath_prepend(os.path.join(ROOT, "tools"))
        import time_grouped_mlp
        N, _, _, E, k, W = time_grouped_mlp.cell_shape(cell)
        assert (N, k, W) == want[:3]
        want, floor = want[3:]
        shares = time_grouped_mlp.CELLS[cell][2]
        assert moe.floor_tiles(N, k, E, W, want, shares) == floor
        assert traced_floors(cell, monkeypatch) == {(want, floor)}
    else:
        N, k, W = cell
    rows = moe.tile_rows(N * k // W)
    assert rows == want
    assert rows == moe.GROUP_TILE or rows * moe.GROUP_SHARE_TILES <= N * k // W
    assert moe.GROUP_FLOOR_SHARES == 2


def traced_floors(cell, monkeypatch):
    """{(tile rows, floor tiles)} the expert layers of the cell's model ask
    for when its init is traced at one step's batch, the model built from
    the cell's configuration file as `train_stream.build_learner` builds it."""
    import json
    from benchmark.drivers import train_stream
    from mmlspark_tpu.models import moe
    import time_grouped_mlp
    config, traffic, _ = time_grouped_mlp.CELLS[cell]
    with open(os.path.join(ROOT, "benchmark", "configs",
                           config + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           traffic + ".json")) as f:
        traffic = json.load(f)
    module = build_model(dict(train_stream.build_learner(
        config, traffic, 0).getModelConfig()))
    seen, floor_tiles = [], moe.floor_tiles

    def spy(N, k, E, W, rows, shares):
        seen.append((rows, floor_tiles(N, k, E, W, rows, shares)))
        return seen[-1][1]

    monkeypatch.setattr(moe, "floor_tiles", spy)
    jax.eval_shape(module.init, jax.random.PRNGKey(0), jax.ShapeDtypeStruct(
        (traffic["batch_rows"], config["input"]["seq_len"]), jnp.int32))
    assert len(seen) >= 4
    return set(seen)


@pytest.mark.parametrize("counts", [
    pytest.param((5, 0, 7), id="an_empty_expert"),
    pytest.param((0, 33, 0), id="one_expert_holds_everything"),
    pytest.param((7, 3, 13), id="no_multiple_of_any_tile"),
    pytest.param((16, 8, 4), id="whole_tiles"),
])
def test_grouped_experts_are_the_same_at_every_tile_size(counts):
    """`grouped_expert_mlp` at 4, 8 and 16 rows a tile (a static argument, no
    module constant patched) against the loop over assignments: the same
    result, the same count of assignments computed, gradients within 1e-5 of
    each other, with and without a floor of tiles. Tokens repeat across
    experts, as a token's several assignments do."""
    ks = jax.random.split(jax.random.PRNGKey(9), 8)
    N, d, f, E = 40, 16, 8, 3
    x = jax.random.normal(ks[0], (N, d), F32)
    wg, wu = (jax.random.normal(k, (E, d, f), F32) for k in ks[1:3])
    wd = jax.random.normal(ks[3], (E, f, d), F32)
    R = sum(counts)
    token = jnp.concatenate(
        [jax.random.permutation(k, N)[:c] for k, c in zip(ks[4:7], counts)]
        + [jnp.full((N - R,), 3)]).astype(jnp.int32)
    weight = jax.random.uniform(ks[7], (N,), F32)
    ct = jax.random.normal(ks[6], (N, d), F32)
    counts = jnp.asarray(counts, jnp.int32)

    def run(rows, min_tiles):
        def loss(x, wg, wu, wd, weight):
            y, computed = grouped_expert_mlp(x, wg, wu, wd, token, weight,
                                             counts, min_tiles, rows)
            return jnp.sum(y * ct), (y, computed)
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(x, wg, wu, wd,
                                                          weight)
        return out, grads

    want = np.zeros((N, d))
    expert = np.repeat(np.arange(E), np.asarray(counts))
    for j in range(R):
        e = expert[j]
        h = jax.nn.silu(x[token[j]] @ wg[e]) * (x[token[j]] @ wu[e])
        want[int(token[j])] += float(weight[j]) * np.asarray(h @ wd[e])
    (y4, n4), g4 = run(4, 0)
    close(y4, want, 2e-5)
    for rows, min_tiles in [(8, 0), (16, 0), (16, 7), (8, 40)]:
        (y, n), g = run(rows, min_tiles)
        assert int(n) == int(n4) == R
        close(y, y4, 1e-6)
        for a, b in zip(g, g4):
            close(a, b, 1e-5)
        assert not np.any(np.asarray(g[4])[R:])


@pytest.mark.parametrize("tile", [4, 256])
@pytest.mark.parametrize("routing", ["seeded", "all_to_one_held",
                                     "none_held", "all_held_chosen"])
def test_tiles_needed_and_walked_match_a_hand_count(routing, tile,
                                                    monkeypatch):
    """`moe_tiles_needed` is the sum over held experts of ceil(count / rows)
    and `moe_tiles_walked` that or the floor of two uniform shares, the
    greater, at the rows `tile_rows` gives the layer: 300 tokens, top 4 of
    16, 4 held make a share of 75 (256 rows a tile, a floor of 3 tiles; with
    GROUP_TILE at 4, 16 rows and 38). `mmlspark_moe_tile_rows` says the rows
    and `mmlspark_moe_floor_tiles` the floor; nothing is dropped."""
    from mmlspark_tpu.models import moe
    monkeypatch.setattr(moe, "GROUP_TILE", tile)
    cfg = small_config()
    N, k, E, W = 300, 4, 4, 16
    rows = {4: 16, 256: 256}[tile]
    assert moe.tile_rows(N * k // W) == rows
    floor = -(-2 * N * k * E // (W * rows))
    assert floor == {4: 38, 256: 3}[tile]
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 150, 32), F32)
    layer = make_experts(cfg)
    p = layer.init(jax.random.PRNGKey(5), x)
    forced = {"all_to_one_held": [1, 9, 10, 11], "none_held": [8, 9, 10, 11],
              "all_held_chosen": [0, 1, 2, 3]}
    if routing in forced:
        p = with_bias(p, forced[routing], W)
    was = telemetry.enabled()
    telemetry.enable()
    try:
        _, stats = jax.jit(layer.apply)(p, x)
        snap = telemetry.snapshot()
        gauge, least = ([s for s in snap[name]["series"]
                         if s["labels"]["layer"] == ""]
                        for name in ("mmlspark_moe_tile_rows",
                                     "mmlspark_moe_floor_tiles"))
        assert [gauge[0]["value"], least[0]["value"]] == [rows, floor]
    finally:
        (telemetry.enable if was else telemetry.disable)()
    # the routing by hand: the top 4 of sigmoid(x . router) + bias
    scores = jax.nn.sigmoid(jnp.dot(
        x.reshape(N, -1), p["params"]["router"],
        precision=jax.lax.Precision.HIGHEST))
    _, chosen = jax.lax.top_k(scores + p["params"]["selection_bias"], k)
    counts = np.bincount(np.asarray(chosen).ravel(), minlength=W)[:E]
    needed = int(sum(-(-int(c) // rows) for c in counts))
    want = {"all_to_one_held": -(-N // rows), "none_held": 0,
            "all_held_chosen": E * -(-N // rows)}.get(routing, needed)
    assert needed == want
    routed, fullest, dropped, got_needed, walked = (int(s) for s in stats)
    assert (routed, fullest, dropped) == (counts.sum(), counts.max(), 0)
    assert (got_needed, walked) == (needed, max(needed, floor))


# ------------------------------------------------------- the share test

def uncut(cfg, **over):
    cfg = dict(cfg, **over)
    cfg["linear_attn_config"] = dict(
        cfg["linear_attn_config"], num_heads=cfg["num_attention_heads"])
    return cfg


def head_share(kind, p, s, per, H, D, cfg):
    """The parameters of heads s*per .. (s+1)*per - 1 of a mixer's `p`:
    the columns (or rows) of every head-wise projection; what all shares hold
    whole (the bottlenecks' inputs, the shared c / k_r projection, the norms)
    is copied."""
    def cols(w, width):
        return w.reshape(w.shape[:-1] + (H, width))[..., s * per:(s + 1) * per,
                                                    :].reshape(
            w.shape[:-1] + (per * width,))

    def rows(w, width):
        return w.reshape((H, width) + w.shape[1:])[s * per:(s + 1) * per] \
            .reshape((per * width,) + w.shape[1:])

    out = dict(p)
    if kind == "kda":
        for n in ("q", "k", "v"):
            out[f"{n}_proj"] = {"kernel": cols(p[f"{n}_proj"]["kernel"], D)}
            out[f"{n}_conv"] = {"kernel": cols(p[f"{n}_conv"]["kernel"], D)}
        for n in ("f_b_proj", "g_b_proj"):
            out[n] = {"kernel": cols(p[n]["kernel"], D)}
        out["b_proj"] = {"kernel": cols(p["b_proj"]["kernel"], 1)}
        out["A_log"] = p["A_log"][s * per:(s + 1) * per]
        out["dt_bias"] = cols(p["dt_bias"], D)
        out["o_proj"] = {"kernel": rows(p["o_proj"]["kernel"], D)}
    else:
        qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        nv = cfg["qk_nope_head_dim"] + cfg["v_head_dim"]
        out["q_proj"] = {"kernel": cols(p["q_proj"]["kernel"], qk)}
        out["kv_b_proj"] = {"kernel": cols(p["kv_b_proj"]["kernel"], nv)}
        out["o_proj"] = {"kernel": rows(p["o_proj"]["kernel"],
                                        cfg["v_head_dim"])}
    return out


@pytest.mark.parametrize("kind", ["kda", "mla"])
def test_head_shares_add_up_to_the_uncut_layer(kind):
    """Four shares of 2 heads each, every share on the same input: their
    outputs add up to what the uncut reference gives for all 8 heads (the
    output projection is a sum over heads; nothing else couples them)."""
    full = uncut(small_config(), num_attention_heads=8)
    share = uncut(small_config(), num_attention_heads=2)
    x = jax.random.normal(jax.random.PRNGKey(11), (2, 13, 32), F32)
    p = make_mixer(kind, full).init(jax.random.PRNGKey(12), x)["params"]
    D = full["linear_attn_config"]["head_dim"]
    one = jax.jit(make_mixer(kind, share).apply)
    total = sum(one({"params": head_share(kind, p, s, 2, 8, D, full)}, x)
                for s in range(4))
    plain = {"kda": ref.kda, "mla": ref.mla}[kind]
    close(total, jax.jit(lambda p, x: plain(full, p, x, "f32"))(p, x), 2e-5)


def test_expert_shares_add_up_to_the_uncut_layer():
    """32 shares of one routed expert each (router width 32, top 8, every
    share with the whole router and the shared expert): the routed parts of
    the 32, with the shared expert counted once, add up to the uncut
    reference's layer, which holds all 32."""
    cfg = small_config(router_width=32, num_experts=32,
                       num_experts_per_token=8)
    x = jax.random.normal(jax.random.PRNGKey(13), (2, 21, 32), F32)
    p = make_experts(cfg).init(jax.random.PRNGKey(14), x)["params"]
    shared = ref.swiglu(p["shared0"], x.reshape(-1, 32),
                        functools.partial(ref.common.matmul,
                                          precision="f32")).reshape(x.shape)
    total, routed = shared, 0
    for s in range(32):
        one = dict(cfg, num_experts=1, first_expert_held=s)
        ps = dict(p, **{n: p[n][s:s + 1] for n in
                        ("expert_gate", "expert_up", "expert_down")})
        y, stats = jax.jit(make_experts(one).apply)({"params": ps}, x)
        total = total + (y - shared)
        routed += int(stats[0])
        assert int(stats[2]) == 0
    assert routed == 2 * 21 * 8          # every assignment lands on one share
    close(total, jax.jit(lambda p, x: ref.experts(cfg, p, x, "f32"))(p, x),
          5e-5)


# --------------------------------------------------------- the whole model

def tokens(B=4, T=21, vocab=64, seed=0):
    return np.random.default_rng(seed).integers(0, vocab, (B, T)).astype(
        np.int32)


def test_model_matches_reference_and_remat_changes_nothing():
    cfg = small_config()
    cfg["input"] = {"seq_len": 21}
    tok = tokens()
    m0, m1 = build_model(cfg), build_model(dict(cfg, remat=True))
    p = m0.init(jax.random.PRNGKey(0), tok[:1])
    plain = functools.partial(ref.forward, cfg)
    out = jax.jit(m0.apply)(p, tok)
    assert out.shape == (4, 2) and out.dtype == jnp.float32
    close(out, jax.jit(plain)(p, jnp.asarray(tok)), 2e-5)
    close(jax.jit(m1.apply)(p, tok), out, 1e-6)
    grad = lambda f: jax.jit(jax.grad(lambda p: jnp.sum(f(p, tok) ** 2)))(p)
    g0, g1 = grad(m0.apply), grad(m1.apply)
    # the same operations run twice: float32 to the bit but for the order
    # XLA sums the recomputed block's gradients in
    trees_close(g1, g0, 1e-6)
    trees_close(g0, grad(plain), 1e-4)
    for name in m0.layer_names():
        assert m0.apply(p, tok, output_layer=name).shape[0] == 4
    _, stats = m0.apply(p, tok, step_stats=True)
    assert set(stats) == set(m0.step_stat_names)
    assert int(stats["moe_tokens_dropped"]) == 0
    assert 0 < int(stats["moe_expert_tokens_max"]) \
        < int(stats["moe_tokens_routed"]) < 4 * 4 * 21 * 4


def stream_of(batches):
    return lambda: iter(batches)


def learner_for(cfg, precision="f32"):
    return (TpuLearner().setModelConfig(cfg).setBatchSize(8).setEpochs(1)
            .setOptimizer("adamw").setLearningRate(1e-3).setWeightDecay(0.1)
            .setPrecision(precision).setLoss("cross_entropy").setSeed(3))


def test_fit_stream_follows_the_reference_in_float32():
    """Three AdamW steps of `fitStream` under `remat` against the reference's
    `train_steps` from the same seeded parameters and batches: every
    parameter's change, to 1% of the largest: Adam divides by sqrt(v), so
    float32 noise in a small gradient moves its step by far more than it
    moves a value (read: 0.5%), where a wrong term turns steps of lr round."""
    cfg = small_config(remat=True)
    del cfg["dtype"]          # the learner's precision sets it
    rng = np.random.default_rng(5)
    batches = [(rng.integers(0, 64, (8, 21)).astype(np.int32),
                rng.integers(0, 2, (8,)).astype(np.int32)) for _ in range(3)]
    model = learner_for(cfg).fitStream(stream_of(batches))
    p0 = build_model(dict(cfg, dtype="float32")).init(
        jax.random.PRNGKey(3), jnp.asarray(batches[0][0][:1]))
    rcfg = dict(cfg, input={"seq_len": 21},
                learner={"optimizer": "adamw", "learningRate": 1e-3,
                         "weightDecay": 0.1})
    want = ref.train_steps(rcfg, jax.device_get(p0), batches, block_rows=4)
    got = model.getModelParams()
    moved = jax.tree_util.tree_map(lambda a, b: np.asarray(a) - np.asarray(b),
                                   got, jax.device_get(p0))
    moved_ref = jax.tree_util.tree_map(
        lambda a, b: np.asarray(a) - np.asarray(b), want["params_after"],
        jax.device_get(p0))
    scale = max(np.max(np.abs(a)) for a in
                jax.tree_util.tree_leaves(moved_ref))
    assert scale > 1e-3           # three steps at 1e-3 moved the weights
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(moved)[0],
                            jax.tree_util.tree_leaves(moved_ref)):
        assert np.max(np.abs(a - b)) <= 1e-2 * scale, \
            (jax.tree_util.keystr(path), np.max(np.abs(a - b)), scale)


def test_fit_and_transform_as_every_token_model():
    from mmlspark_tpu.core.dataframe import DataFrame
    cfg = small_config(num_hidden_layers=2, first_k_dense_replace=1)
    cfg["linear_attn_config"] = dict(cfg["linear_attn_config"],
                                     kda_layers=[1], full_attn_layers=[2])
    del cfg["dtype"]
    tok = tokens(B=16, T=12)
    df = DataFrame({"features": [r.astype(np.float32) for r in tok],
                    "label": (tok.sum(1) % 2).astype(np.int64)})
    model = learner_for(cfg, "bf16").setEpochs(2).fit(df)
    assert np.isfinite(model._final_loss)
    out = model.setOutputCol("scores").transform(df)
    assert np.asarray(out["scores"][0]).shape == (2,)


def test_flops_hand_count_at_the_published_widths():
    """`benchmark/flops/kimi_linear.py` against a count by hand, in
    multiply-adds a token forward, for the benchmark's own configuration
    (d 2304, 8 heads of 128 held, 8 of 256 experts held, T 2048).
    KDA: q, k, v, o 4 x 2304 x 1024; the two bottlenecks 2 x (2304 x 128 +
    128 x 1024); the step 2304 x 8; the state 3 x 8 x 128 x 128.
    Latent: q 2304 x 1536; compression 2304 x 576; expansion 512 x 2048;
    o 1024 x 2304; scores and values 2048 x 320 x 8 / 2.
    Experts: router 2304 x 256; shared + 8 x 8 / 256 routed = 1.25 SwiGLUs
    of 3 x 2304 x 1024. Dense: 3 x 2304 x 9216. Layers: KDA + dense,
    3 x (KDA + experts), latent + experts. Twice that a token in operations,
    three times forward to train, nothing recomputed counted."""
    import json
    from benchmark.flops import kimi_linear as flops
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "kimi_linear_48b_a3b.json")) as f:
        cfg = json.load(f)
    kda = 9_437_184 + 851_968 + 18_432 + 393_216
    mla = 3_538_944 + 1_327_104 + 1_048_576 + 2_359_296 + 2_621_440
    moe = 589_824 + 8_847_360
    dense = 63_700_992
    assert flops.kda_macs_per_token(cfg) == kda == 10_700_800
    assert flops.mla_macs_per_token(cfg) == mla == 10_895_360
    assert flops.moe_macs_per_token(cfg) == moe
    assert flops.expected_assignments_per_token(cfg) * 16384 / 8 == 512
    per_token = (kda + dense) + 3 * (kda + moe) + (mla + moe)
    assert flops.forward_macs_per_token(cfg) == per_token == 155_148_288
    want = 3 * (2 * per_token * 2048 + 2 * 2304 * 2)
    assert flops.train_flops_per_row(cfg) == want
    assert abs(want / 1.906e12 - 1) < 1e-3


# ------------------------------------------------- registry, predicate, spans

def test_unknown_type_names_the_family_and_example_input_knows_it():
    with pytest.raises(KeyError, match="kimi_linear"):
        build_model({"type": "kimi_linear_9000"})
    x = example_input({"type": "kimi_linear", "seq_len": 12}, batch=3)
    assert x.shape == (3, 12) and x.dtype == jnp.int32
    with pytest.raises(ValueError, match="mla_use_nope"):
        build_model(small_config(mla_use_nope=False))
    with pytest.raises(ValueError, match="q_lora_rank"):
        build_model(small_config(q_lora_rank=64))
    bad = small_config()
    bad["linear_attn_config"] = dict(bad["linear_attn_config"],
                                     full_attn_layers=[3, 4])
    with pytest.raises(ValueError, match="must split layers"):
        build_model(bad)
    off = build_model(small_config(first_expert_held=14))
    with pytest.raises(ValueError, match="not among the router"):
        off.init(jax.random.PRNGKey(0), tokens()[:1])


@pytest.mark.parametrize("cfg,want", [
    ({"type": "transformer", "num_experts": 4}, True),
    ({"type": "transformer", "num_experts": 0}, False),
    ({"type": "transformer"}, False),
    ({"type": "kimi_linear", "num_experts": 8}, True),
    ({"type": "kimi_linear", "num_experts": 0}, False),
    ({"type": "mlp", "num_experts": 4}, False),
    ({"type": "resnet50", "num_experts": 2}, False),
])
def test_has_experts_is_the_one_predicate(cfg, want):
    assert has_experts(cfg) is want


@pytest.mark.parametrize("on", [True, False])
def test_step_counts_reach_the_ring_only_with_telemetry_on(on):
    """With telemetry on a stream fit records one `fit/step_stats` a step,
    numbered as its `fit/dispatch`, and feeds the registry; off, the step
    program has no such output and nothing is recorded. No token dropped."""
    was = telemetry.enabled()
    (telemetry.enable if on else telemetry.disable)()
    try:
        telemetry.trace.clear()
        routed = telemetry.registry.counter("mmlspark_moe_tokens_routed_total")
        dropped = telemetry.registry.counter(
            "mmlspark_moe_tokens_dropped_total")
        before = routed.value
        cfg = small_config(remat=True)
        del cfg["dtype"]
        rng = np.random.default_rng(2)
        batches = [(rng.integers(0, 64, (8, 16)).astype(np.int32),
                    rng.integers(0, 2, (8,)).astype(np.int32))
                   for _ in range(4)]
        learner_for(cfg, "bf16").fitStream(stream_of(batches))
        events = telemetry.trace.events()
        stats = [e["args"] for e in events if e["name"] == "fit/step_stats"]
        steps = [e["args"]["step"] for e in events
                 if e["name"] == "fit/dispatch"]
        if not on:
            assert stats == [] and routed.value == before
            return
        assert [s["step"] for s in stats] == steps == [0, 1, 2, 3]
        assert all(s["moe_tokens_dropped"] == 0 for s in stats)
        assert all(0 < s["moe_expert_tokens_max"] < s["moe_tokens_routed"]
                   for s in stats)
        # four expert layers of 128 tokens, top 4 of 16, 4 held: 256 rows a
        # tile, a floor of 2 * 128 * 4 * 4 / (16 * 256) -> 1 tile a layer,
        # and a layer's four experts need one tile each
        assert all(s["moe_tiles_needed"] == s["moe_tiles_walked"] == 4 * 4
                   for s in stats)
        assert routed.value - before == sum(s["moe_tokens_routed"]
                                              for s in stats)
        assert dropped.value == 0
        snap = telemetry.snapshot()
        chunks = {s["labels"]["layer"]: s["value"]
                  for s in snap["mmlspark_kda_chunks_total"]["series"]}
        assert {"block0/mixer", "block4/mixer"} <= set(chunks)
        # the test models' chunk of 8 is one diagonal block of 8 channels
        exps = {s["labels"]["layer"]: s["value"]
                for s in snap["mmlspark_kda_decay_exps_total"]["series"]}
        assert all(exps[layer] == n * 8 * 8 * 8
                   for layer, n in chunks.items() if layer.startswith("block"))
        held = snap["mmlspark_moe_experts_held"]["series"]
        width = snap["mmlspark_moe_router_width"]["series"]
        tile = {s["labels"]["layer"]: s["value"]
                for s in snap["mmlspark_moe_tile_rows"]["series"]}
        assert all(tile[f"block{i}/mlp"] == 256 for i in range(1, 5))
        assert {s["labels"]["layer"] for s in held} >= {"block1/mlp"}
        by_layer = {s["labels"]["layer"]: s["value"] for s in width}
        for s in held:
            if s["value"]:
                assert by_layer[s["labels"]["layer"]] == 4 * s["value"]
    finally:
        (telemetry.enable if was else telemetry.disable)()
