"""Pallas kernel correctness (interpret mode on the CPU mesh — the same
kernels compile natively on TPU; the bench exercises that path)."""

import functools
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.ops.pallas_kernels import flash_attention, histogram_fused
from mmlspark_tpu.parallel.sequence import plain_attention


def _qkv(rng, B=2, T=32, H=2, D=16):
    def a():
        return jnp.asarray(rng.normal(size=(B, T, H, D)).astype(np.float32))
    return a(), a(), a()


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_plain(rng, causal):
    q, k, v = _qkv(rng)
    ref = plain_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=8, block_k=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_flash_nondivisible_seq(rng):
    q, k, v = _qkv(rng, T=20)
    ref = plain_attention(q, k, v)
    out = flash_attention(q, k, v, block_q=8, block_k=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_flash_cross_attention_lengths(rng):
    q = jnp.asarray(rng.normal(size=(1, 12, 2, 8)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 28, 2, 8)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, 28, 2, 8)).astype(np.float32))
    ref = plain_attention(q, k, v)
    out = flash_attention(q, k, v, block_q=8, block_k=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


# The causal tile schedule: (Tq, Tk, block_q, block_k, sub). Lengths that pad
# the one sequence, the other, both or neither; blocks the diagonal cuts
# unevenly (block_q != block_k), sub-tiles smaller than the copied block, and
# sequences shorter than one sub-tile.
_SCHEDULES = [
    pytest.param(32, 32, 8, 16, 8, id="even-sub<block"),
    pytest.param(32, 32, 16, 8, 8, id="even-bq>bk"),
    pytest.param(48, 48, 16, 24, 8, id="even-bq!=bk-diagonal-uneven"),
    pytest.param(32, 32, 8, 32, 16, id="even-whole-k-resident"),
    pytest.param(20, 32, 8, 16, 8, id="tq-padded"),
    pytest.param(32, 20, 8, 16, 8, id="tk-padded-boundary-in-sub-tile"),
    pytest.param(32, 24, 8, 16, 8, id="tk-padded-whole-sub-tile"),
    pytest.param(20, 28, 8, 16, 8, id="both-padded-tq<tk"),
    pytest.param(44, 20, 16, 8, 8, id="both-padded-tq>tk"),
    pytest.param(12, 28, 8, 8, 8, id="tq<tk-one-level"),
    pytest.param(5, 5, 8, 8, 8, id="shorter-than-a-sub-tile"),
    pytest.param(5, 19, 16, 16, 16, id="tq-shorter-than-a-sub-tile"),
]


def _with_sub(monkeypatch, sub):
    """The sub-tile is derived from the block (no argument carries it):
    give the derivation test-sized widths."""
    from mmlspark_tpu.ops import pallas_kernels as pk
    monkeypatch.setattr(
        pk, "_sub_tile",
        lambda block, want: sub if block % sub == 0 else block)


def _out_and_grads(attn, args, w):
    """attn(q, k, v) and the gradients of sum(out * w) by the three, in
    float32 numpy."""
    import jax

    def loss(q, k, v):
        out = attn(q, k, v)
        return jnp.sum(out.astype(jnp.float32) * w), out
    (_, out), grads = jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True)(*args)
    return [np.asarray(a, dtype=np.float32) for a in (out, *grads)]


def _flash_vs_plain(Tq, Tk, bq, bk, causal, dtype, rng, H=2, D=8,
                    reference=plain_attention):
    """Forward and the three gradients of both, in float32 numpy."""
    q = jnp.asarray(rng.normal(size=(2, Tq, H, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(2, Tk, H, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(2, Tk, H, D)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(2, Tq, H, D)).astype(np.float32))
    got = _out_and_grads(
        lambda q, k, v: flash_attention(q, k, v, causal, None, bq, bk),
        [a.astype(dtype) for a in (q, k, v)], w)
    want = _out_and_grads(lambda q, k, v: reference(q, k, v, causal=causal),
                          (q, k, v), w)
    return got, want


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("Tq,Tk,bq,bk,sub", _SCHEDULES)
def test_flash_schedule_f32(rng, monkeypatch, Tq, Tk, bq, bk, sub, causal):
    _with_sub(monkeypatch, sub)
    got, want = _flash_vs_plain(Tq, Tk, bq, bk, causal, jnp.float32, rng)
    np.testing.assert_allclose(got[0], want[0], atol=1e-5, rtol=1e-5)
    for g, r in zip(got[1:], want[1:]):
        np.testing.assert_allclose(g, r, atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("Tq,Tk,bq,bk,sub", _SCHEDULES)
def test_flash_schedule_bf16(rng, monkeypatch, Tq, Tk, bq, bk, sub, causal):
    _with_sub(monkeypatch, sub)
    got, want = _flash_vs_plain(Tq, Tk, bq, bk, causal, jnp.bfloat16, rng)
    np.testing.assert_allclose(got[0], want[0], atol=3e-2, rtol=3e-2)
    for g, r in zip(got[1:], want[1:]):
        scale = max(1e-3, float(np.abs(r).max()))
        np.testing.assert_allclose(g / scale, r / scale, atol=5e-2)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_real_sub_tiles(rng, causal):
    """The derivation as it ships (no test-sized sub-tile): Tk = 1,100 in one
    copied tile of 1,536 keys, whose third 512-wide sub-tile holds the
    boundary; Tq = 200 shorter than a sub-tile."""
    from mmlspark_tpu.ops.pallas_kernels import _default_blocks
    assert _default_blocks(8, causal, 200, 1100) == (200, 1536, 512)
    assert _default_blocks(8, causal, 200, 1100,
                           kernel="flash_dkv") == (200, 512, 200)
    q = jnp.asarray(rng.normal(size=(1, 200, 1, 8)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 1100, 1, 8)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, 1100, 1, 8)).astype(np.float32))
    ref = plain_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("args,want", [
    # T = 2,048 causal a head: today's one-level tiles, forward and backward
    ((2048, 2048, 512, 1024, 1024, True), (8, 6, 4)),
    ((2048, 2048, 512, 512, 512, True), (16, 10, 4)),
    # two-level: 512 x 256 and 256 x 256 compute sub-tiles
    ((2048, 2048, 512, 1024, 256, True), (32, 20, 8)),
    ((2048, 2048, 512, 2048, 256, True), (32, 20, 8)),
    ((2048, 2048, 256, 1024, 256, True), (64, 36, 8)),
    # non-causal, unpadded: everything computed, nothing masked
    ((2048, 2048, 512, 1024, 256, False), (32, 32, 0)),
    ((4096, 4096, 1024, 2048, 512, False), (32, 32, 0)),
    # Tk = 20 in 16-blocks of 8-sub-tiles: [16, 24) holds the boundary and
    # is masked, [24, 32) only padding and is skipped, for each of 4 q blocks
    ((32, 20, 8, 16, 8, False), (16, 12, 4)),
    # Tk = 24: the padding is one whole sub-tile, nothing masked
    ((32, 24, 8, 16, 8, False), (16, 12, 0)),
    # top-left alignment with Tq < Tk: rows 0..11 see keys 0..11 only
    ((12, 28, 8, 8, 8, True), (8, 3, 2)),
    # a sequence shorter than one sub-tile: one masked sub-tile
    ((5, 5, 8, 8, 8, True), (1, 1, 1)),
])
def test_flash_tile_counts(args, want):
    from mmlspark_tpu.ops.pallas_kernels import flash_tile_counts
    assert flash_tile_counts(*args) == want


def test_flash_tile_counts_match_mask():
    """The counts against the mask itself, tile by tile."""
    from mmlspark_tpu.ops.pallas_kernels import flash_tile_counts
    for Tq, Tk, bq, bk, sub, causal in [
            (48, 48, 16, 24, 8, True), (20, 28, 8, 16, 8, True),
            (44, 20, 16, 8, 8, True), (44, 20, 16, 8, 8, False)]:
        Tqp, Tkp = -(-Tq // bq) * bq, -(-Tk // bk) * bk
        keep = np.arange(Tkp)[None, :] < Tk
        if causal:
            keep = keep & (np.arange(Tqp)[:, None] >= np.arange(Tkp)[None, :])
        keep = np.broadcast_to(keep, (Tqp, Tkp))
        tiles = keep.reshape(Tqp // bq, bq, Tkp // sub, sub).transpose(
            0, 2, 1, 3).reshape(-1, bq * sub)
        # a tile wholly in the key padding is skipped though it holds no
        # kept score *and* no dropped real one; under a causal mask a tile
        # that keeps nothing is wholly above the diagonal or in the padding
        computed = tiles.any(axis=1)
        assert flash_tile_counts(Tq, Tk, bq, bk, sub, causal) == (
            len(tiles), int(computed.sum()),
            int((computed & ~tiles.all(axis=1)).sum()))


def test_flash_noncausal_unpadded_has_no_mask_code():
    """A non-causal call whose lengths are block multiples emits neither
    body's mask: no iota anywhere in the three kernels' jaxprs."""
    import jax
    q = jnp.zeros((1, 256, 1, 8), jnp.float32)

    def loss(q, k, v, causal):
        return jnp.sum(flash_attention(q, k, v, causal, None, 128, 256))

    def text(causal):
        return str(jax.make_jaxpr(jax.grad(
            lambda q, k, v: loss(q, k, v, causal), argnums=(0, 1, 2)))(
                q, q, q))
    assert text(False).count("pallas_call") == 3
    assert "iota" not in text(False)
    assert "iota" in text(True)          # the check can see a mask


def test_flash_subtile_counters(monkeypatch):
    """One increment per call built, labelled by kernel."""
    import jax
    from mmlspark_tpu import telemetry
    from mmlspark_tpu.ops import pallas_kernels as pk
    monkeypatch.setattr(sys.modules["mmlspark_tpu.telemetry.registry"]._state,
                        "enabled", True)
    names = ("total", "computed", "masked")

    def read():
        snap = telemetry.registry.snapshot()
        return {(s["labels"]["kernel"], n): s["value"] for n in names
                for s in snap[f"mmlspark_flash_subtiles_{n}"]["series"]}
    before = read()
    T, D = 2048, 128
    q = jnp.zeros((1, T, 1, D), jnp.float32)
    jax.make_jaxpr(jax.grad(lambda q: jnp.sum(
        pk.flash_attention(q, q, q, True))))(q)
    after = read()
    for kernel in ("flash_fwd", "flash_dq", "flash_dkv"):
        bq, bk, sub = pk._default_blocks(D, True, T, T, kernel=kernel)
        want = (pk.flash_tile_counts(T, T, sub, bk, bk, True)
                if kernel == "flash_dkv" else
                pk.flash_tile_counts(T, T, bq, bk, sub, True))
        assert want[0] > want[1] > want[2] > 0, (kernel, want)
        got = tuple(after[kernel, n] - before.get((kernel, n), 0.0)
                    for n in names)
        assert got == want, (kernel, got, want)


# A head read where the model left it: one 128-lane tile wide, it is a lane
# block of the (B, T, H*D) view; any other width (256 here, 8-64 above) is
# copied to (B*H, T, D). (Tq, Tk, block_q, block_k): lengths the blocks
# divide and lengths they do not (the padded path in the in-place layout),
# Tq != Tk.
_LANE_TILE_LENGTHS = [
    pytest.param(32, 32, 16, 16, id="blocks-divide"),
    pytest.param(20, 20, 8, 16, id="both-padded"),
    pytest.param(12, 28, 8, 8, id="tq<tk-keys-padded"),
    pytest.param(40, 24, 16, 8, id="tq>tk-queries-padded"),
]


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("Tq,Tk,bq,bk", _LANE_TILE_LENGTHS)
@pytest.mark.parametrize("H", [2, 3])
@pytest.mark.parametrize("D", [pytest.param(128, id="d128-in-place"),
                               pytest.param(256, id="d256-copied")])
def test_flash_heads_of_whole_lane_tiles(rng, D, H, Tq, Tk, bq, bk, causal):
    """Values and the three gradients where a head is a lane block (128:
    the in-place layout, padded lengths included) and at the next multiple
    of 128 lanes, which runs the copied layout (256)."""
    from mmlspark_tpu.parallel.sequence import blockwise_attention
    got, want = _flash_vs_plain(
        Tq, Tk, bq, bk, causal, jnp.float32, rng, H, D,
        functools.partial(blockwise_attention, block_size=8))
    np.testing.assert_allclose(got[0], want[0], atol=1e-5, rtol=1e-5)
    for g, r in zip(got[1:], want[1:]):
        np.testing.assert_allclose(g, r, atol=2e-3, rtol=2e-3)


# A latent head: q and k wider than v. (Tq, Tk, block_q, block_k) as above:
# a sequence no block divides, cross lengths either way.
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("Tq,Tk,bq,bk", _LANE_TILE_LENGTHS)
@pytest.mark.parametrize("qk,Dqk,Dv", [
    pytest.param(256, 256, 128, id="256/128"),
    pytest.param(192, 256, 128, id="192-padded-to-256/128"),
])
def test_flash_two_widths(rng, qk, Dqk, Dv, Tq, Tk, bq, bk, causal, dtype):
    """v narrower than q and k: values and the three gradients against the
    plain softmax at the widths the model asks for (q and k `qk` wide, the
    scale theirs) and against the zero-padded one-width call this replaces
    (v padded to `Dqk`, the result and dv cut back), which computes the same
    sums with zero terms more."""
    B, H = 2, 2

    def draw(T, D):
        return jnp.asarray(rng.normal(size=(B, T, H, D)).astype(np.float32))
    q, k, v, w = draw(Tq, qk), draw(Tk, qk), draw(Tk, Dv), draw(Tq, Dv)
    scale = qk ** -0.5

    def lanes(a, width):
        return jnp.pad(a, ((0, 0),) * 3 + ((0, width - a.shape[-1]),))

    def two_widths(q, k, v):
        return flash_attention(lanes(q, Dqk), lanes(k, Dqk), v, causal,
                               scale, bq, bk)

    def one_width(q, k, v):
        return flash_attention(lanes(q, Dqk), lanes(k, Dqk), lanes(v, Dqk),
                               causal, scale, bq, bk)[..., :Dv]

    def plain(q, k, v):
        return plain_attention(q, k, v, causal=causal, scale=scale)

    cast = [a.astype(dtype) for a in (q, k, v)]
    got, padded, want = (_out_and_grads(two_widths, cast, w),
                         _out_and_grads(one_width, cast, w),
                         _out_and_grads(plain, (q, k, v), w))
    assert [a.shape for a in got] == [a.shape for a in (w, q, k, v)]
    if dtype == jnp.float32:
        # the same products in the same order, the padded call's with zero
        # terms more: equal to float32's rounding of the sums
        for g, p in zip(got, padded):
            np.testing.assert_allclose(g, p, atol=1e-6, rtol=1e-6)
        np.testing.assert_allclose(got[0], want[0], atol=1e-5, rtol=1e-5)
        for g, r in zip(got[1:], want[1:]):
            np.testing.assert_allclose(g, r, atol=2e-3, rtol=2e-3)
    else:
        # bfloat16 operands, float32 sums: zeros change no product and no
        # sum, so the two calls agree to the bit
        for g, p in zip(got, padded):
            np.testing.assert_array_equal(g, p)
        np.testing.assert_allclose(got[0], want[0], atol=3e-2, rtol=3e-2)
        for g, r in zip(got[1:], want[1:]):
            top = max(1e-3, float(np.abs(r).max()))
            np.testing.assert_allclose(g / top, r / top, atol=5e-2)


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs it calls (the jitted
    calls), the kernels' own bodies left out."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


@pytest.mark.parametrize("D,Dv,fwd_transposes,grad_transposes", [
    # the copied layout as it was: q, k, v in and O out; the same again in
    # the gradient's forward, q, k, v, dO in twice, dq, dk, dv out
    pytest.param(64, 64, 4, 15, id="d64-transposed"),
    pytest.param(128, 128, 0, 0, id="d128-in-place"),
    pytest.param(256, 256, 4, 15, id="d256-transposed"),
    # two widths: the same copies, those of v, O, dO and dv at v's width
    pytest.param(256, 128, 4, 15, id="d256/128-transposed"),
])
def test_flash_layout_copies_in_jaxpr(D, Dv, fwd_transposes,
                                      grad_transposes):
    """No transpose of an operand-sized array around the in-place calls,
    forward or gradient; the other widths hold what they held. A call at
    two widths holds no v, O, dO or dv at q's width: each kernel reads v and
    dO and writes O and dv at v's own, and only q, k, dq and dk at q's."""
    import jax
    q = jnp.zeros((1, 256, 2, D), jnp.float32)
    v = jnp.zeros((1, 256, 2, Dv), jnp.float32)

    def attend(q, k, v):
        return flash_attention(q, k, v, True)

    grad = jax.grad(lambda q, k, v: jnp.sum(attend(q, k, v)),
                    argnums=(0, 1, 2))
    for fn, calls, want in ((attend, 1, fwd_transposes),
                            (grad, 3, grad_transposes)):
        jaxpr = jax.make_jaxpr(fn)(q, q, v)
        eqns = list(_equations(jaxpr.jaxpr))
        assert sum(e.primitive.name == "pallas_call" for e in eqns) == calls
        assert sum(e.primitive.name == "transpose"
                   and e.outvars[0].aval.size in (q.size, v.size)
                   for e in eqns) == want
        if D == Dv == 128:
            continue        # in place: the kernels see (B, T, H*D) arrays
        # what each kernel is handed and what it writes, by last dimension:
        # q, k (and dO after v in the backward calls), then the results
        kernels = [e for e in eqns if e.primitive.name == "pallas_call"]
        assert [tuple(a.aval.shape[-1] for a in e.invars[:n])
                for e, n in zip(kernels, (3, 4, 4))] == \
            [(D, D, Dv), (D, D, Dv, Dv), (D, D, Dv, Dv)][:calls]
        assert [tuple(o.aval.shape[-1] for o in e.outvars)
                for e in kernels] == [(Dv, 1), (D,), (D, Dv)][:calls]
        assert [a.shape[-1] for a in jaxpr.out_avals] == \
            [[Dv], [D, D, Dv]][calls == 3]


def _flash_calls_counted(monkeypatch, trace):
    """What `mmlspark_flash_calls_total` grew by while `trace()` ran:
    {(kernel, layout, widths): calls}."""
    from mmlspark_tpu import telemetry
    monkeypatch.setattr(sys.modules["mmlspark_tpu.telemetry.registry"]._state,
                        "enabled", True)

    def read():
        series = telemetry.registry.snapshot()[
            "mmlspark_flash_calls_total"]["series"]
        return {tuple(s["labels"][n] for n in ("kernel", "layout", "widths")):
                s["value"] for s in series}
    before = read()
    trace()
    return {key: n - before.get(key, 0.0) for key, n in read().items()
            if n != before.get(key, 0.0)}


@pytest.mark.parametrize("D,Dv,layout,widths", [
    (64, 64, "transposed", "64"), (128, 128, "in_place", "128"),
    (256, 256, "transposed", "256"), (256, 128, "transposed", "256/128"),
    (192, 128, "transposed", "192/128"), (128, 256, "transposed", "128/256")])
def test_flash_calls_counter(monkeypatch, D, Dv, layout, widths):
    """One increment a call built, labelled by kernel, by how it addresses
    a head and by the head's widths; the choice follows the two widths
    alone (in place only where both are one lane tile)."""
    import jax
    q = jnp.zeros((1, 256, 3, D), jnp.float32)
    v = jnp.zeros((1, 256, 3, Dv), jnp.float32)
    grew = _flash_calls_counted(monkeypatch, lambda: jax.make_jaxpr(jax.grad(
        lambda q, v: jnp.sum(flash_attention(q, q, v, True)),
        argnums=(0, 1)))(q, v))
    assert grew == {(kernel, layout, widths): 1.0
                    for kernel in ("flash_fwd", "flash_dq", "flash_dkv")}


@pytest.mark.parametrize("cell,layout,widths,blocks", [
    ("cgpt1p3b_train_stream", "in_place", "128", 6),
    ("kimilinear_train_stream", "transposed", "256/128", 1),
    ("joyai_train_stream", "transposed", "256/128", 6),
])
def test_flash_calls_counter_at_the_cells_sizes(monkeypatch, cell, layout,
                                                widths, blocks):
    """The benchmark's three sequence cells as its own driver builds them
    (configuration and traffic from the manifest: 8 rows of 2,048 or 4,096
    ids, `remat`), traced and not run: every flash call of the step is
    counted under the layout and the widths its heads ask for, a block's
    forward twice (`remat`), its dq and dkv once."""
    import json
    import os
    import jax
    from benchmark.drivers.train_stream import build_learner
    from mmlspark_tpu.models import build_model
    root = os.path.join(os.path.dirname(__file__), os.pardir)

    def load(*path):
        with open(os.path.join(root, *path)) as f:
            return json.load(f)
    entry, = (w for w in load("BENCHMARK.json")["workloads"]
              if w["name"] == cell)
    config = load("benchmark", "configs", entry["config"] + ".json")
    traffic = load("benchmark", "traffic", entry["traffic"] + ".json")
    model_cfg = dict(build_learner(config, traffic, 0).getModelConfig())
    model = build_model({**model_cfg, "attn_impl": "flash"})
    T = config["input"]["seq_len"]
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, T), jnp.int32)))
    kw = ({"row_losses": True} if config["learner"]["loss"] == "next_token"
          else {})
    grew = _flash_calls_counted(monkeypatch, lambda: jax.eval_shape(
        jax.grad(lambda p, t: jnp.sum(
            model.apply(p, t, **kw).astype(jnp.float32))), params,
        jax.ShapeDtypeStruct((traffic["batch_rows"], T), jnp.int32)))
    assert grew == {("flash_fwd", layout, widths): 2.0 * blocks,
                    ("flash_dq", layout, widths): 1.0 * blocks,
                    ("flash_dkv", layout, widths): 1.0 * blocks}


def test_histogram_matches_numpy(rng):
    N, F, n_bins = 100, 5, 16
    bins = rng.integers(0, n_bins, size=(N, F)).astype(np.int32)
    g = rng.normal(size=N).astype(np.float32)
    h = rng.random(N).astype(np.float32)
    hg, hh = histogram_fused(jnp.asarray(bins), jnp.asarray(g),
                             jnp.asarray(h), n_bins=n_bins, block_n=32)
    ref_g = np.zeros((F, n_bins), np.float32)
    ref_h = np.zeros((F, n_bins), np.float32)
    for f in range(F):
        for b in range(n_bins):
            sel = bins[:, f] == b
            ref_g[f, b] = g[sel].sum()
            ref_h[f, b] = h[sel].sum()
    np.testing.assert_allclose(np.asarray(hg), ref_g, atol=1e-4)
    np.testing.assert_allclose(np.asarray(hh), ref_h, atol=1e-4)


def test_histogram_row_padding_masked(rng):
    """N not a multiple of block_n: padded rows must not contribute."""
    N, F, n_bins = 33, 3, 8
    bins = rng.integers(0, n_bins, size=(N, F)).astype(np.int32)
    g = np.ones(N, np.float32)
    h = np.ones(N, np.float32)
    hg, hh = histogram_fused(jnp.asarray(bins), jnp.asarray(g),
                             jnp.asarray(h), n_bins=n_bins, block_n=16)
    assert float(np.asarray(hg).sum()) == pytest.approx(N * F)
    assert float(np.asarray(hh).sum()) == pytest.approx(N * F)


@pytest.mark.extended
def test_transformer_flash_matches_blockwise(rng):
    """attn_impl='flash' must be numerically interchangeable."""
    import jax
    from mmlspark_tpu.models import build_model
    toks = jnp.asarray(rng.integers(0, 50, size=(2, 16)).astype(np.int32))
    base = {"type": "transformer", "vocab_size": 50, "d_model": 32,
            "heads": 4, "layers": 1, "num_classes": 3}
    m1 = build_model(base)
    m2 = build_model({**base, "attn_impl": "flash"})
    params = m1.init(jax.random.PRNGKey(0), toks)
    o1 = m1.apply(params, toks)
    o2 = m2.apply(params, toks)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               atol=1e-2, rtol=1e-2)


def test_gbdt_pallas_hist_matches_segment(rng):
    """Both histogram backends must grow identical trees."""
    from mmlspark_tpu.models.gbdt.engine import (GBDTParams, fit_gbdt,
                                                 predict)
    x = rng.normal(size=(200, 6)).astype(np.float32)
    y = (x[:, 0] + 0.5 * x[:, 1] > 0).astype(np.float32)
    base = dict(num_iterations=10, max_depth=3, max_bin=16,
                objective="binary")
    e1 = fit_gbdt(x, y, GBDTParams(**base, hist_impl="segment"))
    e2 = fit_gbdt(x, y, GBDTParams(**base, hist_impl="pallas"))
    np.testing.assert_array_equal(np.asarray(e1.feature),
                                  np.asarray(e2.feature))
    np.testing.assert_array_equal(np.asarray(e1.threshold),
                                  np.asarray(e2.threshold))
    np.testing.assert_allclose(predict(e1, x), predict(e2, x), atol=1e-5)


@pytest.mark.extended
def test_flash_attention_gradients():
    """flash_attention must be differentiable (custom VJP: kernel forward,
    blockwise-recompute backward) and match blockwise gradients."""
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.ops.pallas_kernels import flash_attention
    from mmlspark_tpu.parallel.sequence import blockwise_attention

    rng = np.random.default_rng(0)
    B, T, H, D = 2, 64, 2, 8
    q, k, v = (jnp.asarray(rng.normal(size=(B, T, H, D)).astype(np.float32))
               for _ in range(3))
    for causal in (False, True):
        def loss_f(q, k, v, c=causal):
            return (flash_attention(q, k, v, causal=c) ** 2).sum()

        def loss_b(q, k, v, c=causal):
            return (blockwise_attention(q, k, v, causal=c) ** 2).sum()

        gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
        gb = jax.grad(loss_b, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gb):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-3)


@pytest.mark.extended
@pytest.mark.parametrize("causal", [False, True])
def test_flash_bf16_forward_and_grad_parity(rng, causal):
    """The on-chip dtype: bf16 operands into every MXU matmul, f32
    accumulation. Covers the casts that are no-ops in the f32 tests."""
    import jax
    q, k, v = _qkv(rng)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))

    out = flash_attention(qb, kb, vb, causal=causal, block_q=8, block_k=8)
    assert out.dtype == jnp.bfloat16
    ref = plain_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.asarray(ref), atol=3e-2, rtol=3e-2)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal, None, 8, 8)
                       .astype(jnp.float32))

    def loss_ref(q, k, v):
        return jnp.sum(plain_attention(q, k, v, causal=causal)
                       .astype(jnp.float32))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(qb, kb, vb)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        assert gf.dtype == jnp.bfloat16
        scale = max(1e-3, float(np.abs(np.asarray(gr)).max()))
        np.testing.assert_allclose(
            np.asarray(gf, dtype=np.float32) / scale,
            np.asarray(gr) / scale, atol=5e-2)


def test_compare_reduce_matches_segment_directly():
    """Direct parity of the scatter-free backend against segment_sum on
    the same inputs (ties, zero-weight rows, full uint8 id range) — the
    backend the engine's auto policy prefers for single-node builds."""
    import numpy as np

    from mmlspark_tpu.ops.pallas_kernels import (compare_reduce_histogram,
                                                 segment_histogram)
    rng = np.random.default_rng(5)
    n, d = 4000, 6
    bins = jnp.asarray(rng.integers(0, 256, size=(n, d)), jnp.int32)
    g = jnp.asarray(rng.normal(size=n), jnp.float32)
    h = jnp.asarray(np.abs(rng.normal(size=n)), jnp.float32)
    g = g.at[::9].set(0.0)                       # zero-weight rows
    a_g, a_h = compare_reduce_histogram(bins, g, h, 256)
    b_g, b_h = segment_histogram(bins, g, h, 256)
    np.testing.assert_allclose(np.asarray(a_g), np.asarray(b_g),
                               rtol=1e-6, atol=1e-5)
    np.testing.assert_allclose(np.asarray(a_h), np.asarray(b_h),
                               rtol=1e-6, atol=1e-5)


def test_mxu_node_histogram_matches_segment(rng):
    """The round-5 MXU kernel must match segment_sum per (node, feat, bin),
    including out-of-range node ids (discard slots) and row padding."""
    import jax.numpy as jnp
    from mmlspark_tpu.ops.pallas_kernels import (mxu_node_histogram,
                                                 segment_histogram)
    N, F, n_bins, n_nodes = 333, 5, 16, 3
    bins = rng.integers(0, n_bins, size=(N, F)).astype(np.int32)
    node = rng.integers(0, n_nodes + 2, size=N).astype(np.int32)  # some OOR
    g = rng.normal(size=N).astype(np.float32)
    h = rng.random(N).astype(np.float32)
    hg, hh = mxu_node_histogram(jnp.asarray(bins.T), jnp.asarray(node),
                                jnp.asarray(g), jnp.asarray(h),
                                n_nodes=n_nodes, n_bins=n_bins, block_n=128)
    in_r = node < n_nodes
    comb = jnp.asarray(node[:, None] * n_bins + bins)
    rg, rh = segment_histogram(comb, jnp.asarray(g * in_r),
                               jnp.asarray(h * in_r),
                               n_bins=(n_nodes + 2) * n_bins)
    rg = np.asarray(rg).reshape(F, n_nodes + 2, n_bins)[:, :n_nodes]
    rh = np.asarray(rh).reshape(F, n_nodes + 2, n_bins)[:, :n_nodes]
    np.testing.assert_allclose(np.asarray(hg), rg.transpose(1, 0, 2),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(hh), rh.transpose(1, 0, 2),
                               rtol=1e-5, atol=1e-4)


def test_gbdt_mxu_hist_matches_segment(rng):
    """Level- and leaf-wise fits must grow identical trees under the mxu
    backend (the TPU auto default) and the segment reference."""
    from mmlspark_tpu.models.gbdt.engine import (GBDTParams, fit_gbdt,
                                                 predict)
    x = rng.normal(size=(300, 6)).astype(np.float32)
    y = (x[:, 0] + 0.5 * x[:, 1] > 0).astype(np.float32)
    for extra in (dict(max_depth=3,),
                  dict(num_leaves=7, max_depth=0)):
        base = dict(num_iterations=5, max_bin=16, objective="binary",
                    **extra)
        e1 = fit_gbdt(x, y, GBDTParams(**base, hist_impl="segment"))
        e2 = fit_gbdt(x, y, GBDTParams(**base, hist_impl="mxu"))
        np.testing.assert_array_equal(np.asarray(e1.feature),
                                      np.asarray(e2.feature))
        np.testing.assert_array_equal(np.asarray(e1.threshold),
                                      np.asarray(e2.threshold))
        np.testing.assert_allclose(predict(e1, x), predict(e2, x),
                                   atol=1e-5)


def test_node_sums_matches_segment(rng):
    import jax.numpy as jnp
    from mmlspark_tpu.ops.pallas_kernels import node_sums
    N, L = 1000, 7
    node = jnp.asarray(rng.integers(0, L, N).astype(np.int32))
    g = jnp.asarray(rng.normal(size=N).astype(np.float32))
    h = jnp.asarray(rng.random(N).astype(np.float32))
    lg, lh = node_sums(node, g, h, L)
    sg, sh = node_sums(node, g, h, L, impl="segment")
    np.testing.assert_allclose(np.asarray(lg), np.asarray(sg), rtol=1e-6,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(lh), np.asarray(sh), rtol=1e-6,
                               atol=1e-5)


def test_explicit_segment_is_pure_segment(monkeypatch):
    """hist_impl='segment' must NEVER route through another backend (users
    pin it to bit-reproduce older fits); 'auto' resolves to the mxu kernel
    on TPU and to the compare hybrid elsewhere."""
    import jax
    import numpy as np

    from mmlspark_tpu.models.gbdt import engine
    calls = {"cr": 0, "mxu": 0}
    import mmlspark_tpu.ops.pallas_kernels as pk
    orig_cr = pk.compare_reduce_histogram
    orig_mxu = pk.mxu_node_histogram

    def spy_cr(*a, **k):
        calls["cr"] += 1
        return orig_cr(*a, **k)

    def spy_mxu(*a, **k):
        calls["mxu"] += 1
        return orig_mxu(*a, **k)
    monkeypatch.setattr(pk, "compare_reduce_histogram", spy_cr)
    monkeypatch.setattr(pk, "mxu_node_histogram", spy_mxu)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(300, 4)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.float32)
    p = engine.GBDTParams(num_iterations=2, max_depth=2, max_bin=15,
                          hist_impl="segment")
    engine.fit_gbdt(x, y, p)
    assert calls["cr"] == 0 and calls["mxu"] == 0
    p2 = engine.GBDTParams(num_iterations=2, max_depth=2, max_bin=15,
                           hist_impl="auto")
    engine.fit_gbdt(x, y, p2)
    if jax.default_backend() == "tpu":
        assert calls["mxu"] >= 1     # auto = the MXU kernel on TPU
    else:
        assert calls["cr"] >= 1      # hybrid used the uint8 path


# ---------------------------------------------- GBDT quantized predict


def _walk_levelwise(bins, feat, thr, leaf, depth):
    """numpy reference: heap descent over the quantized tables."""
    n = bins.shape[0]
    T, K, _ = feat.shape
    out = np.zeros((n, K), np.float32)
    for t in range(T):
        for k in range(K):
            pos = np.zeros(n, np.int64)
            for level in range(depth):
                node = 2 ** level - 1 + pos
                f = feat[t, k, node]
                go_right = bins[np.arange(n), f].astype(np.int64) \
                    > thr[t, k, node]
                pos = pos * 2 + go_right
            out[:, k] += leaf[t, k][pos]
    return out


def _walk_leafwise(bins, split, feat, thr, leaf):
    """numpy reference: replay the split sequence over the tables."""
    n = bins.shape[0]
    T, K, R = split.shape
    out = np.zeros((n, K), np.float32)
    for t in range(T):
        for k in range(K):
            pos = np.zeros(n, np.int64)
            for r in range(R):
                right = (pos == split[t, k, r]) & (
                    bins[np.arange(n), feat[t, k, r]].astype(np.int64)
                    > thr[t, k, r])
                pos[right] = r + 1
            out[:, k] += leaf[t, k][pos]
    return out


def test_gbdt_quant_levelwise_kernel_matches_reference(rng):
    """The tile-resident quantized predict kernel (interpret mode on
    CPU) vs a pure-numpy table walk — including the 255 route-all-left
    sentinel and non-tile-aligned (n, d)."""
    from mmlspark_tpu.ops.pallas_kernels import gbdt_predict_quant_levelwise
    T, K, depth, d, n = 7, 3, 4, 11, 777       # nothing tile-aligned
    nodes, leaves = 2 ** depth - 1, 2 ** depth
    bins = rng.integers(0, 32, size=(n, d)).astype(np.uint8)
    feat = rng.integers(0, d, size=(T, K, nodes)).astype(np.uint8)
    thr = rng.integers(0, 32, size=(T, K, nodes)).astype(np.uint8)
    thr[0, 0, 0] = 255                  # route-all-left sentinel
    leaf32 = rng.normal(size=(T, K, leaves)).astype(np.float32)
    leaf = jnp.asarray(leaf32).astype(jnp.bfloat16)
    out = gbdt_predict_quant_levelwise(
        jnp.asarray(bins.T), feat, thr, leaf, depth=depth, block_n=128)
    ref = _walk_levelwise(bins, feat, thr,
                          np.asarray(leaf, np.float32), depth)
    assert out.shape == (n, K)
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-6)


def test_gbdt_quant_leafwise_kernel_matches_reference(rng):
    """Leaf-wise twin, including -1 no-op split rounds (a stopped-early
    tree) which must never move any row."""
    from mmlspark_tpu.ops.pallas_kernels import gbdt_predict_quant_leafwise
    T, K, R, d, n = 5, 1, 9, 6, 333
    bins = rng.integers(0, 64, size=(n, d)).astype(np.uint8)
    split = np.stack([
        rng.integers(0, r + 1, size=(K, R)) for r in range(T)
    ]).astype(np.int32)
    split[2, :, 5:] = -1                # tree 2 stopped after 5 rounds
    feat = rng.integers(0, d, size=(T, K, R)).astype(np.uint8)
    thr = rng.integers(0, 64, size=(T, K, R)).astype(np.uint8)
    leaf32 = rng.normal(size=(T, K, R + 1)).astype(np.float32)
    leaf = jnp.asarray(leaf32).astype(jnp.bfloat16)
    out = gbdt_predict_quant_leafwise(
        jnp.asarray(bins.T), split, feat, thr, leaf, block_n=128)
    ref = _walk_leafwise(bins, split, feat, thr,
                         np.asarray(leaf, np.float32))
    assert out.shape == (n, K)
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-6)
