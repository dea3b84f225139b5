"""The `joyai_llm_flash` family against its plain reference, on the CPU at
small sizes with seeded weights.

The reference is `benchmark/reference/joyai_llm_flash.py` (jax.numpy,
float32, nothing of the program imported): the rotation from the angle
formula, latent attention as a full masked softmax, the expert layer as a
loop over the held experts, both heads over whole (T, V) logits. The program
runs here in float32 too, so every tolerance below is the room two orders of
float32 summation need (1e-5 relative on values of order one, a little more
through a backward pass or three optimizer steps), never a precision's: a
wrong term reads 1e-2 and more.
"""

import functools
import json
import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.reference import joyai_llm_flash as ref        # noqa: E402
from mmlspark_tpu import telemetry                            # noqa: E402
from mmlspark_tpu.models import TpuLearner, build_model       # noqa: E402
from mmlspark_tpu.models import joyai_llm_flash as jf         # noqa: E402
from mmlspark_tpu.models import kimi_linear as kl             # noqa: E402
from mmlspark_tpu.models import trainer                       # noqa: E402
from mmlspark_tpu.models.modules import (TOKEN_MODELS,        # noqa: E402
                                         example_input, has_experts)
from mmlspark_tpu.models.moe import DroplessMoE               # noqa: E402
from mmlspark_tpu.parallel.sequence import blockwise_attention  # noqa: E402

F32 = jnp.float32


def small_config(**over):
    """Three layers as the benchmark's cut in small (dense, experts,
    experts) and the prediction module, 4 of 16 experts and 2 heads held,
    every width tiny; query/key 8 + 4 against value 8."""
    cfg = {"type": "joyai_llm_flash", "vocab_size": 64, "hidden_size": 32,
           "num_hidden_layers": 3, "first_k_dense_replace": 1,
           "num_attention_heads": 2, "num_key_value_heads": 2,
           "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
           "qk_rope_head_dim": 4, "v_head_dim": 8, "rope_theta": 32e6,
           "intermediate_size": 48, "moe_intermediate_size": 16,
           "n_routed_experts": 4, "router_width": 16, "first_expert_held": 0,
           "num_experts_per_tok": 4, "n_shared_experts": 1,
           "norm_topk_prob": True, "routed_scaling_factor": 2.5,
           "rms_norm_eps": 1e-6, "num_nextn_predict_layers": 1,
           "mtp_loss_weight": 0.3, "lm_loss_chunk": 8, "dtype": "float32"}
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


# ------------------------------------------------------------ the rotation

@pytest.mark.parametrize("shape", [(2, 9, 3, 8), (2, 9, 8), (1, 130, 2, 64)])
def test_rotation_matches_the_complex_form(shape):
    """(x[2i] + j x[2i+1]) e^{j t theta^(-2i/D)}, in float64 on the host;
    the program's product with the pair-swapping matrix and the reference's
    strided form both: 1e-5 is float32's sine of an angle up to 129."""
    theta = 32e6
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(0), shape, F32))
    T, D = shape[1], shape[-1]
    ang = np.arange(T)[:, None] * theta ** (-np.arange(0, D, 2) / D)
    ang = ang.reshape((T,) + (1,) * (len(shape) - 3) + (D // 2,))
    z = (x[..., 0::2] + 1j * x[..., 1::2].astype(np.float64)) * np.exp(
        1j * ang)
    want = np.stack([z.real, z.imag], axis=-1).reshape(shape)
    close(kl.rotate_pairs(jnp.asarray(x), theta), want, 1e-5)
    close(ref.rotate(jnp.asarray(x), theta), want, 1e-5)


def test_rotated_scores_depend_on_the_distance_alone():
    """<rot_t q, rot_s k> is a function of t - s: the same q and k at every
    position give a Toeplitz score matrix. theta 100 so that the angles
    differ by position at 12 positions."""
    q = jax.random.normal(jax.random.PRNGKey(1), (8,), F32)
    k = jax.random.normal(jax.random.PRNGKey(2), (8,), F32)
    T = 12
    rq = kl.rotate_pairs(jnp.broadcast_to(q, (1, T, 8)), 100.0)[0]
    rk = kl.rotate_pairs(jnp.broadcast_to(k, (1, T, 8)), 100.0)[0]
    s = np.asarray(rq @ rk.T)
    for off in range(-T + 1, T):
        diag = np.diagonal(s, off)
        assert np.max(np.abs(diag - diag[0])) < 1e-5
    assert np.abs(s[0, 0] - s[0, 5]) > 1e-2       # and does depend on it


# ---------------------------------------------------- the latent layer

attention = kl.causal_attention("blockwise", 8)


def make_mla(cfg, **over):
    kw = dict(q_rank=cfg["q_lora_rank"], rope_theta=cfg["rope_theta"])
    kw.update(over)
    return kl.MLALayer(cfg["num_attention_heads"], cfg["kv_lora_rank"],
                       cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                       cfg["v_head_dim"], attention, cfg["rms_norm_eps"],
                       F32, **kw)


def test_latent_layer_with_bottleneck_and_rotation_matches_reference():
    """Values and gradients against the full masked softmax with the
    rotation written out, T = 19 (no multiple of the attention block)."""
    cfg = small_config()
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 19, 32), F32)
    layer = make_mla(cfg)
    p = layer.init(jax.random.PRNGKey(2), x)
    assert set(p["params"]) == {"q_a_proj", "q_a_norm", "q_b_proj",
                                "kv_a_proj", "kv_a_norm", "kv_b_proj",
                                "o_proj"}
    plain = jax.jit(lambda p, x: ref.mla(cfg, p["params"], x, "f32"))
    close(jax.jit(layer.apply)(p, x), plain(p, x), 2e-5)
    ct = jax.random.normal(jax.random.PRNGKey(3), x.shape, F32)
    grad = lambda f: jax.jit(jax.grad(lambda p, x: jnp.sum(f(p, x) * ct),
                                      argnums=(0, 1)))(p, x)
    trees_close(grad(layer.apply), grad(plain), 1e-4)


def test_reference_softmax_in_query_blocks_is_the_whole_softmax(monkeypatch):
    """The reference cuts the query rows into blocks for memory alone."""
    cfg = small_config()
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 19, 32), F32)
    p = make_mla(cfg).init(jax.random.PRNGKey(2), x)["params"]
    whole = ref.mla(cfg, p, x, "f32")
    monkeypatch.setattr(ref, "QUERY_BLOCK", 8)
    close(ref.mla(cfg, p, x, "f32"), whole, 1e-6)


class _MLAAsItWas(nn.Module):
    """`MLALayer.__call__` as it stood before the bottleneck and the
    rotation (PR 31's tree), kept here as the pin it is compared with: it
    pads q, k and v to one width and cuts the result, which since PR 33 is
    what `causal_attention`'s blockwise branch does with the v it is handed
    unpadded."""
    heads: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    attention: object
    eps: float = 1e-5
    dtype: object = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        B, T, d = x.shape
        H, qk = self.heads, self.nope_dim + self.rope_dim
        dense = functools.partial(kl._dense, dtype=self.dtype)
        q = dense(H * qk, name="q_proj")(x).reshape(B, T, H, qk)
        kva = dense(self.kv_rank + self.rope_dim, name="kv_a_proj")(x)
        c = nn.RMSNorm(epsilon=self.eps, dtype=self.dtype,
                       name="kv_a_norm")(kva[..., :self.kv_rank])
        k_r = kva[..., self.kv_rank:]
        kv = dense(H * (self.nope_dim + self.v_dim), name="kv_b_proj")(c)
        kv = kv.reshape(B, T, H, self.nope_dim + self.v_dim)
        k = jnp.concatenate(
            [kv[..., :self.nope_dim],
             jnp.broadcast_to(k_r[:, :, None, :], (B, T, H, self.rope_dim))],
            axis=-1)
        v = kv[..., self.nope_dim:]
        width = -(-max(qk, self.v_dim) // 128) * 128

        def padded(a):
            return jnp.pad(a, ((0, 0),) * 3 + ((0, width - a.shape[-1]),))

        o = self.attention(padded(q), padded(k), padded(v), qk ** -0.5)
        o = o[..., :self.v_dim].reshape(B, T, H * self.v_dim)
        return dense(d, name="o_proj")(o)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_latent_layer_without_both_is_the_one_kimi_had(dtype):
    """Neither field set (kimi's build), on the blockwise path: the same
    parameters, the same program (jaxpr for jaxpr) and the same bits as the
    layer before, which padded v itself and took a one-width attention."""
    def one_width(q, k, v, scale):
        return blockwise_attention(q, k, v, block_size=8, causal=True,
                                   scale=scale)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 19, 32), dtype)
    new = kl.MLALayer(2, 16, 8, 4, 8, attention, 1e-5, dtype)
    old = _MLAAsItWas(2, 16, 8, 4, 8, one_width, 1e-5, dtype)
    p = old.init(jax.random.PRNGKey(2), x)
    assert jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: bool(jnp.array_equal(a, b)), p,
        new.init(jax.random.PRNGKey(2), x)))
    assert str(jax.make_jaxpr(new.apply)(p, x)) \
        == str(jax.make_jaxpr(old.apply)(p, x))
    assert jnp.array_equal(jax.jit(new.apply)(p, x), jax.jit(old.apply)(p, x))


# ------------------------------------------------------- the chunked loss

@pytest.mark.parametrize("T,chunk", [(21, 8), (21, 1), (21, 64), (16, 8)])
def test_chunked_loss_matches_whole_logits(T, chunk):
    """Values and the three gradients (hidden states, norm, head) against
    -log softmax over whole (B, T, V) logits: T = 21 over chunks of 8 is no
    multiple (the padded positions must carry nothing), chunk 1, one chunk
    for the whole row, and an exact multiple. Some positions unscored."""
    B, d, V = 3, 32, 40
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    h = jax.random.normal(ks[0], (B, T, d), F32)
    scale = 1.0 + 0.1 * jax.random.normal(ks[1], (d,), F32)
    kernel = jax.random.normal(ks[2], (d, V), F32) * d ** -0.5
    tgt = jax.random.randint(ks[3], (B, T), 0, V)
    scored = (jnp.arange(T) < T - 2)

    def chunked(h, scale, kernel):
        return jf.chunked_token_losses(h, scale, kernel, tgt, scored,
                                       eps=1e-6, chunk=chunk, dtype=F32)

    def whole(h, scale, kernel):
        z = ref.rmsnorm(h, {"scale": scale}, 1e-6) @ kernel
        return jnp.sum(ref.token_losses(z, tgt) * scored, axis=1)

    close(jax.jit(chunked)(h, scale, kernel), whole(h, scale, kernel), 1e-5)
    ct = jnp.asarray([1.0, -2.0, 0.5])
    grad = lambda f: jax.jit(jax.grad(
        lambda *a: jnp.sum(f(*a) * ct), argnums=(0, 1, 2)))(h, scale, kernel)
    trees_close(grad(chunked), grad(whole), 2e-5)


def shapes_in(jaxpr, found):
    """Every array shape of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        for v in list(eqn.invars) + list(eqn.outvars):
            if hasattr(v, "aval") and hasattr(v.aval, "shape"):
                found.add(tuple(v.aval.shape))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            shapes_in(sub, found)
    return found


def test_no_logits_of_the_whole_batch_in_the_loss_program():
    """With row losses asked for, value and gradient, the program holds a
    (B, chunk, V) array and none shaped (B, T, V) or (B * T, V); the logits
    path, for contrast, does."""
    cfg = small_config(vocab_size=72)
    m = build_model(cfg)
    tok = tokens(B=4, T=24)
    p = m.init(jax.random.PRNGKey(0), tok[:1])
    loss = jax.make_jaxpr(jax.grad(
        lambda p: jnp.sum(m.apply(p, tok, row_losses=True))))(p)
    seen = shapes_in(loss.jaxpr, set())
    assert (4, 8, 72) in seen
    assert not {(4, 24, 72), (96, 72)} & seen
    assert (4, 24, 72) in shapes_in(
        jax.make_jaxpr(lambda p: m.apply(p, tok))(p).jaxpr, set())


# ------------------------------------------- the model and its two losses

def test_model_matches_reference_and_remat_changes_nothing():
    """Logits, the two loss terms and the gradient of the rows' losses
    against the reference (whole logits, the prediction module over T - 1
    positions); under `remat` the same to float32's summation order."""
    cfg = small_config()
    tok = tokens()
    m0, m1 = build_model(cfg), build_model(dict(cfg, remat=True))
    p = m0.init(jax.random.PRNGKey(0), tok[:1])
    assert {"embed", "head", "norm", "mtp", "block0", "block2"} \
        <= set(p["params"])
    assert set(p["params"]["mtp"]) == {"enorm", "hnorm", "eh_proj", "block",
                                       "norm"}
    out = jax.jit(m0.apply)(p, tok)
    assert out.shape == (4, 21, 64) and out.dtype == jnp.float32
    close(out, jax.jit(functools.partial(ref.forward, cfg))(
        p, jnp.asarray(tok)), 2e-5)
    rows = lambda m: functools.partial(m.apply, row_losses=True)
    plain = functools.partial(ref.row_losses, cfg)
    got, stats = jax.jit(functools.partial(rows(m0), step_stats=True))(p, tok)
    main, extra = jax.jit(functools.partial(plain, parts=True))(
        p, jnp.asarray(tok))
    close(got, main + 0.3 * extra, 1e-5)
    close(stats["lm_loss_main"], jnp.mean(main), 1e-5)
    close(stats["lm_loss_mtp"], jnp.mean(extra), 1e-5)
    assert int(stats["lm_tokens_scored"]) == 4 * (20 + 19)
    assert int(stats["moe_tokens_dropped"]) == 0
    close(jax.jit(rows(m1))(p, tok), got, 1e-6)
    grad = lambda f: jax.jit(jax.grad(
        lambda p: jnp.sum(f(p, jnp.asarray(tok)) ** 2)))(p)
    g0, g1 = grad(rows(m0)), grad(rows(m1))
    trees_close(g1, g0, 1e-6)
    trees_close(g0, grad(plain), 1e-4)
    for name in m0.layer_names():
        assert m0.apply(p, tok, output_layer=name).shape[0] == 4


def planted(T=12, V=16):
    """A model whose answer is known: V = d = 16, the embedding sqrt(d) times
    the identity, every block's contribution to the residual stream zero,
    W_eh passing the next token's embedding through, and a head that maps
    token i to a logit of 40 on token i + 1. On a counting row (id_t = s + t
    mod V) the main head at t holds id_t and answers id_{t+1}; the
    prediction module at t holds id_{t+1} and answers id_{t+2}."""
    cfg = small_config(vocab_size=V, hidden_size=V, lm_loss_chunk=5)
    m = build_model(cfg)
    rows = (np.arange(3)[:, None] * 5 + np.arange(T)[None, :]) % V
    p = jax.device_get(m.init(jax.random.PRNGKey(0),
                              rows[:1].astype(np.int32)))

    def silence(path, leaf):
        names = [getattr(k, "key", None) for k in path]
        if "o_proj" in names or "down" in names or "expert_down" in names:
            return np.zeros_like(leaf)
        return leaf
    p = jax.tree_util.tree_map_with_path(silence, p)
    P = p["params"]
    P["embed"]["embedding"] = np.eye(V, dtype=np.float32) * V ** 0.5
    P["mtp"]["eh_proj"]["kernel"] = np.concatenate(
        [np.eye(V), np.zeros((V, V))]).astype(np.float32)
    P["head"]["kernel"] = 10.0 * np.roll(np.eye(V, dtype=np.float32), 1,
                                         axis=1)
    return m, p, rows.astype(np.int32)


def test_prediction_module_scores_id_t_plus_2_at_t_and_T_minus_2_positions():
    """On counting rows both heads are right at every scored position (loss
    ~ 15 e^-40). One wrong last id costs the main head its position T - 2
    and the prediction module its position T - 3, one of T - 1 and one of
    T - 2: each mean rises by the margin of 40 over its count, and by no
    more, so position T - 2 of the module (fed the wrong id, its target
    wrapped to id_0) is not scored. A wrong first id costs neither (nothing
    predicts id_0, no position is fed it but the main head's t = 0, whose
    answer id_1 is then wrong: 40 / (T - 1) in the main head alone)."""
    m, p, rows = planted()
    T = rows.shape[1]
    run = jax.jit(functools.partial(m.apply, row_losses=True,
                                    step_stats=True))
    loss, stats = run(p, rows)
    assert float(jnp.max(loss)) < 1e-6
    assert int(stats["lm_tokens_scored"]) == 3 * (T - 1 + T - 2)
    last = rows.copy()
    last[:, -1] = (last[:, -1] + 3) % 16
    loss, stats = run(p, last)
    close(stats["lm_loss_main"], 40.0 / (T - 1), 1e-5)
    close(stats["lm_loss_mtp"], 40.0 / (T - 2), 1e-5)
    close(loss, np.full(3, 40.0 / (T - 1) + 0.3 * 40.0 / (T - 2)), 1e-5)
    first = rows.copy()
    first[:, 0] = (first[:, 0] + 3) % 16
    _, stats = run(p, first)
    close(stats["lm_loss_main"], 40.0 / (T - 1), 1e-5)
    assert float(stats["lm_loss_mtp"]) < 1e-6


def test_rows_of_weight_zero_carry_no_gradient():
    """The trainer's one forward with `next_token`: a batch whose last two
    rows weigh 0 gives the loss and the gradient of its first two rows
    alone, whatever stands in the weightless rows; the labels are not read."""
    cfg = small_config()
    m = build_model(cfg)
    tok = tokens()
    p = m.init(jax.random.PRNGKey(0), tok[:1])
    compute = trainer._make_loss_compute(
        m, trainer.make_loss("next_token", per_example=True), True, 0.0)
    w = jnp.asarray([1.0, 1.0, 0.0, 0.0])
    run = jax.jit(jax.value_and_grad(compute))
    l0, g0 = run(p, tok, jnp.zeros((4,)), w)
    other = tok.copy()
    other[2:] = tokens(seed=9)[2:]
    l1, g1 = run(p, other, jnp.full((4,), 7.0), w)
    close(l1, l0, 1e-6)
    trees_close(g1, g0, 1e-6)
    want = jnp.mean(ref.row_losses(cfg, p, jnp.asarray(tok[:2])))
    close(l0, want, 1e-5)


# ------------------------------------------------------------ the shares

def head_share(p, s, per, H, cfg):
    """The parameters of heads s*per .. (s+1)*per - 1 of a latent layer's
    `p`: the columns of W_qb and W_kvb and the rows of W_o; W_qa, W_kva and
    their norms (and with them the shared k_r) are whole on every share."""
    def cols(w, width):
        return w.reshape(w.shape[:-1] + (H, width))[
            ..., s * per:(s + 1) * per, :].reshape(
                w.shape[:-1] + (per * width,))

    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    nv = cfg["qk_nope_head_dim"] + cfg["v_head_dim"]
    w_o = p["o_proj"]["kernel"]
    w_o = w_o.reshape((H, cfg["v_head_dim"]) + w_o.shape[1:])[
        s * per:(s + 1) * per].reshape((per * cfg["v_head_dim"],)
                                       + w_o.shape[1:])
    return dict(p, q_b_proj={"kernel": cols(p["q_b_proj"]["kernel"], qk)},
                kv_b_proj={"kernel": cols(p["kv_b_proj"]["kernel"], nv)},
                o_proj={"kernel": w_o})


def test_head_shares_add_up_to_the_uncut_layer():
    """Four shares of 2 heads each on the same input add up to what the
    uncut reference gives for all 8 heads (the output projection is a sum
    over heads; the bottleneck and k_r are computed alike on every share)."""
    full = small_config(num_attention_heads=8)
    share = small_config(num_attention_heads=2)
    x = jax.random.normal(jax.random.PRNGKey(11), (2, 13, 32), F32)
    p = make_mla(full).init(jax.random.PRNGKey(12), x)["params"]
    one = jax.jit(make_mla(share).apply)
    total = sum(one({"params": head_share(p, s, 2, 8, full)}, x)
                for s in range(4))
    close(total, jax.jit(lambda p, x: ref.mla(full, p, x, "f32"))(p, x), 2e-5)


def make_experts(cfg):
    return DroplessMoE(
        num_experts=cfg["n_routed_experts"], router_width=cfg["router_width"],
        d_hidden=cfg["moe_intermediate_size"],
        top_k=cfg["num_experts_per_tok"],
        first_expert=cfg["first_expert_held"],
        num_shared=cfg["n_shared_experts"], renormalize=True,
        routed_scale=cfg["routed_scaling_factor"], dtype=F32)


def test_expert_shares_add_up_to_the_uncut_layer():
    """32 shares of one routed expert each (router width 32, top 8, every
    share with the whole router and the shared expert): the routed parts of
    the 32, with the shared expert counted once, add up to the uncut
    reference's layer, which holds all 32. Nothing dropped on any share."""
    cfg = small_config(router_width=32, n_routed_experts=32,
                       num_experts_per_tok=8)
    x = jax.random.normal(jax.random.PRNGKey(13), (2, 21, 32), F32)
    p = make_experts(cfg).init(jax.random.PRNGKey(14), x)["params"]
    shared = ref.swiglu(p["shared0"], x.reshape(-1, 32),
                        functools.partial(ref.common.matmul,
                                          precision="f32")).reshape(x.shape)
    total, routed = shared, 0
    for s in range(32):
        one = dict(cfg, n_routed_experts=1, first_expert_held=s)
        ps = dict(p, **{n: p[n][s:s + 1] for n in
                        ("expert_gate", "expert_up", "expert_down")})
        y, stats = jax.jit(make_experts(one).apply)({"params": ps}, x)
        total = total + (y - shared)
        routed += int(stats[0])
        assert int(stats[2]) == 0
    assert routed == 2 * 21 * 8          # every assignment lands on one share
    close(total, jax.jit(lambda p, x: ref.experts(cfg, p, x, "f32"))(p, x),
          5e-5)


def test_the_slices_logits_are_the_slice_of_the_whole_vocabularys():
    """A rank that holds rows 0-15 of a 64-row embedding and head, on ids
    drawn from its slice, gives the first 16 of the 64 logits the whole
    vocabulary's model gives (every other parameter the same)."""
    whole, part = small_config(), small_config(vocab_size=16)
    tok = tokens(vocab=16)
    m = build_model(whole)
    p = jax.device_get(m.init(jax.random.PRNGKey(0), tok[:1]))
    cut = jax.tree_util.tree_map(lambda a: a, p)
    cut["params"]["embed"] = {
        "embedding": p["params"]["embed"]["embedding"][:16]}
    cut["params"]["head"] = {"kernel": p["params"]["head"]["kernel"][:, :16]}
    close(jax.jit(build_model(part).apply)(cut, tok),
          jax.jit(m.apply)(p, tok)[..., :16], 1e-6)


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
    cfg = small_config(num_hidden_layers=2)
    del cfg["dtype"]
    tok = tokens(B=16, T=12)
    df = DataFrame({"features": [r.astype(np.float32) for r in tok],
                    "label": np.zeros(16, np.int64)})
    model = learner_for(cfg, "bf16").setEpochs(2).fit(df)
    assert np.isfinite(model._final_loss)
    out = model.setOutputCol("scores").transform(df)
    assert np.asarray(out["scores"][0]).shape == (12, 64)


@pytest.mark.parametrize("cfg", [
    {"type": "mlp", "hidden": [8], "num_classes": 2, "input_dim": 4},
    {"type": "transformer", "vocab_size": 32, "d_model": 16, "heads": 2,
     "layers": 1, "max_len": 16},
    {"type": "kimi_linear", "vocab_size": 32, "hidden_size": 16,
     "num_hidden_layers": 1,
     "linear_attn_config": {"kda_layers": [1], "full_attn_layers": [],
                            "head_dim": 8, "num_heads": 2}},
], ids=lambda c: c["type"])
def test_next_token_on_a_family_without_a_head_raises(cfg):
    loss_fn = trainer.make_loss("next_token", per_example=True)
    with pytest.raises(ValueError, match="vocabulary head"):
        trainer._make_loss_compute(build_model(cfg), loss_fn, False, 0.0)
    with pytest.raises(ValueError, match="row losses"):
        loss_fn(jnp.zeros((4, 2)), jnp.zeros((4,)))
    x = example_input(cfg, batch=8)
    batches = [(np.asarray(x), np.zeros(8, np.int32))]
    with pytest.raises(ValueError, match="vocabulary head"):
        learner_for(cfg).fitStream(stream_of(batches))


def compute_as_it_was(module, loss_fn, is_moe):
    """`_make_loss_compute`'s forward before `next_token` (PR 31's tree;
    no auxiliary loss, no step counts)."""
    def compute(p, xb, yb, wb):
        kw = {"row_mask": wb} if is_moe else {}
        preds = module.apply(p, xb, **kw)
        losses = loss_fn(preds, yb)
        main = jnp.sum(losses * wb) / jnp.maximum(jnp.sum(wb), 1.0)
        return main + 0.0 * 0.0
    return compute


@pytest.mark.parametrize("loss,cfg", [
    ("mse", {"type": "mlp", "hidden": [8], "num_classes": 1,
             "input_dim": 4}),
    ("cross_entropy", {"type": "mlp", "hidden": [8], "num_classes": 3,
                       "input_dim": 4}),
    ("cross_entropy", {
        "type": "kimi_linear", "vocab_size": 32, "hidden_size": 16,
        "num_hidden_layers": 2, "first_k_dense_replace": 1,
        "num_attention_heads": 2, "intermediate_size": 16,
        "moe_intermediate_size": 8, "num_experts": 2, "router_width": 4,
        "num_experts_per_token": 2, "kv_lora_rank": 8,
        "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8,
        "linear_attn_config": {"kda_layers": [1], "full_attn_layers": [2],
                               "head_dim": 8, "num_heads": 2}}),
], ids=["mse-mlp", "cross_entropy-mlp", "cross_entropy-kimi_linear"])
def test_the_other_losses_trace_to_the_program_they_had(loss, cfg):
    """`cross_entropy` and `mse` go through `_make_loss_compute` to the
    jaxpr they had before the new choice, equation for equation."""
    module = build_model(cfg)
    x = example_input(cfg, batch=4)
    p = module.init(jax.random.PRNGKey(0), x)
    y = jnp.zeros((4,), jnp.float32 if loss == "mse" else jnp.int32)
    w = jnp.ones((4,), F32)
    loss_fn = trainer.make_loss(loss, per_example=True)
    new = trainer._make_loss_compute(module, loss_fn, has_experts(cfg), 0.0)
    old = compute_as_it_was(module, loss_fn, has_experts(cfg))
    assert str(jax.make_jaxpr(new)(p, x, y, w)) \
        == str(jax.make_jaxpr(old)(p, x, y, w))


# ------------------------------------------------- registry, flops, spans

def test_flops_hand_count_at_the_published_widths():
    """`benchmark/flops/joyai_llm_flash.py` against a count by hand, in
    multiply-adds a token forward, for the benchmark's own configuration
    (d 2048, 8 heads held, 8 of 256 experts held, 16,160 vocabulary rows,
    T 4096). Latent: W_qa 2048 x 1536; W_qb 1536 x 1536; W_kva 2048 x 576;
    W_kvb 512 x 2048; W_o 1024 x 2048; scores and values 4096 x 320 x 8 / 2.
    Experts: router 2048 x 256; shared + 8 x 8 / 256 routed = 1.25 SwiGLUs
    of 3 x 2048 x 768. Dense: 3 x 2048 x 7168. Head 2048 x 16160, twice.
    W_eh 4096 x 2048. Layers: latent + dense, 4 x (latent + experts), the
    module's latent + experts. Twice that a token in operations, three times
    forward to train, nothing recomputed counted."""
    from benchmark.flops import joyai_llm_flash as flops
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "joyai_llm_flash_48b_a3b.json")) as f:
        cfg = json.load(f)
    mla = (3_145_728 + 2_359_296 + 1_179_648 + 1_048_576 + 2_097_152
           + 5_242_880)
    moe = 524_288 + 5_898_240
    dense, head, w_eh = 44_040_192, 33_095_680, 8_388_608
    assert flops.mla_macs_per_token(cfg) == mla == 15_073_280
    assert flops.moe_macs_per_token(cfg) == moe
    assert flops.expected_assignments_per_token(cfg) * 32768 / 8 == 1024
    per_token = (mla + dense) + 4 * (mla + moe) + head \
        + (w_eh + mla + moe + head)
    assert flops.forward_macs_per_token(cfg) == per_token == 241_172_480
    assert flops.train_flops_per_row(cfg) == 3 * 2 * per_token * 4096
    assert abs(flops.train_flops_per_row(cfg) / 5.927e12 - 1) < 1e-3


def test_the_registry_knows_the_family():
    assert "joyai_llm_flash" in TOKEN_MODELS
    with pytest.raises(KeyError, match="joyai_llm_flash"):
        build_model({"type": "joyai_llm_flash_9000"})
    x = example_input({"type": "joyai_llm_flash", "seq_len": 12}, batch=3)
    assert x.shape == (3, 12) and x.dtype == jnp.int32
    assert has_experts({"type": "joyai_llm_flash", "n_routed_experts": 8})
    assert not has_experts({"type": "joyai_llm_flash",
                            "n_routed_experts": 0})
    assert not has_experts({"type": "mlp", "n_routed_experts": 8})
    for key, bad in [("rope_interleave", False), ("scoring_func", "softmax"),
                     ("rope_scaling", {"type": "yarn"}),
                     ("tie_word_embeddings", True), ("n_group", 8)]:
        with pytest.raises(ValueError, match=key):
            build_model(small_config(**{key: bad}))
    with pytest.raises(ValueError, match="key/value heads"):
        build_model(small_config(num_key_value_heads=1))
    with pytest.raises(ValueError, match="no position to score"):
        m = build_model(small_config())
        p = m.init(jax.random.PRNGKey(0), tokens()[:1])
        m.apply(p, tokens(T=2), row_losses=True)
    for key, what in [("mla_use_nope", False), ("q_lora_rank", 64)]:
        with pytest.raises(ValueError, match="joyai_llm_flash"):
            build_model({"type": "kimi_linear", key: what})


@pytest.mark.parametrize("on", [True, False])
def test_step_values_reach_the_ring_only_with_telemetry_on(on):
    """With telemetry on a stream fit records one `fit/step_stats` a step
    with the two loss terms, the positions scored and the expert layers'
    counts, and the static counters say what was built; off, the step
    program has no such output and nothing is recorded."""
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
        assert all(s["lm_tokens_scored"] == 6 * (15 + 14) for s in stats)
        assert all(s["moe_tokens_dropped"] == 0 for s in stats)
        assert all(isinstance(s["lm_loss_main"], float) for s in stats)
        last = stats[-1]
        assert abs(last["lm_loss_main"] + 0.3 * last["lm_loss_mtp"]
                   - model._final_loss) < 1e-4 * model._final_loss
        snap = telemetry.snapshot()

        def grew(name, **labels):
            def value(s):
                return sum(x["value"] for x in s.get(name, {"series": []})[
                    "series"] if all(x["labels"].get(k) == v
                                     for k, v in labels.items()))
            return value(snap) - value(snap0)
        # 16 positions over chunks of 8: two chunks a head a trace
        assert grew("mmlspark_lm_loss_chunks_total", head="main") >= 2
        assert grew("mmlspark_lm_loss_chunks_total", head="main") \
            == grew("mmlspark_lm_loss_chunks_total", head="mtp")
        assert grew("mmlspark_lm_vocab_rows") >= 64
        assert grew("mmlspark_mla_rotary_layers_total") >= 4
    finally:
        (telemetry.enable if was else telemetry.disable)()


@pytest.mark.parametrize("steps,reads", [
    ([], None),
    ([{"moe_tokens_dropped": 0}], None),     # a model with no per-token loss
    ([{"lm_tokens_scored": 8 * 29, "moe_tokens_dropped": 0}] * 3, 8.0 * 29),
    ([{"lm_tokens_scored": 8 * 29, "moe_tokens_dropped": 0},
      {"lm_tokens_scored": 8 * 28, "moe_tokens_dropped": 0}], "scored"),
    ([{"lm_tokens_scored": 8 * 29, "moe_tokens_dropped": 0},
      {"lm_tokens_scored": 8 * 29, "moe_tokens_dropped": 5}], "dropped"),
])
def test_the_scored_positions_reader_raises_on_fewer_and_on_dropped(
        steps, reads, monkeypatch):
    """The benchmark's `lm_tokens_scored` reads the window's
    `fit/step_stats`: nothing there gives None (never 0), every step at
    rows x (2T - 3) gives that count, and a step that scored another count
    or dropped an assignment raises, so neither can read as a faster step."""
    from benchmark.layer_metrics import lm_tokens_scored as reader
    monkeypatch.setattr(reader, "window_stats", lambda counters: steps)
    cell = {"config": {"input": {"seq_len": 16},
                       "num_nextn_predict_layers": 1}}
    counters = {"batch_rows": 8}
    if isinstance(reads, str):
        with pytest.raises(RuntimeError, match=reads):
            reader.read(None, counters, cell)
    else:
        assert reader.read(None, counters, cell) == reads
