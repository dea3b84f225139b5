"""The flash attention kernels compiled for the chip, without the chip.

The TPU's compiler is installed in the sandbox and compiles for a described
(not attached) v5e: what Mosaic refuses (a slice off the tiling, more VMEM
than a kernel may use) fails here at no chip time, where interpret mode
passes it. Nothing runs, so nothing here is a result or a time.

The topology is described inside a fixture, never at import: only one process
may load the TPU's library, and every xdist worker imports this file. Keep
such tests in this one file.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from mmlspark_tpu.ops.pallas_kernels import flash_attention


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip is written to the persistent
    # cache and cannot be read back without one: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# where a head is copied to (B*H, T, D): the transposing copies of the program
# before the in-place layout (q, k, v, dO in, O, dq, dk, dv out, as the
# compiler shares and fuses them), by (Tq, Tk, D); the padded lengths' other
# copies are of padded sizes and not counted
_COPIES_AS_THEY_WERE = {(4096, 4096, 64): 12, (1000, 200, 64): 6,
                        (20, 20, 32): 6, (2048, 2048, 256): 6,
                        (8192, 8192, 256): 3}
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* (copy|transpose|fusion)\(")


def _operand_sized_copies(hlo, sizes):
    """The entry computation's `copy` and `transpose` instructions, and its
    fusions named for one, whose result has as many elements as q or k."""
    entry = hlo[hlo.index("ENTRY"):]
    found = []
    for line in entry.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name, dims, kind = m.groups()
        elements = 1
        for d in dims.split(","):
            elements *= int(d or 1)
        if elements in sizes and (kind != "fusion" or "copy" in name
                                  or "transpose" in name):
            found.append(name)
    return found


@pytest.mark.parametrize("B,Tq,Tk,H,D,causal,dtype", [
    pytest.param(8, 2048, 2048, 16, 128, True, jnp.bfloat16,
                 id="cgpt-cell"),
    pytest.param(8, 2048, 2048, 8, 256, True, jnp.bfloat16,
                 id="kimilinear-cell-latent-padded-to-256"),
    pytest.param(8, 4096, 4096, 4, 128, True, jnp.bfloat16,
                 id="chip-smoke-D"),
    pytest.param(8, 4096, 4096, 4, 128, False, jnp.bfloat16,
                 id="non-causal-resident-1024"),
    pytest.param(8, 4096, 4096, 8, 64, True, jnp.bfloat16, id="head-dim-64"),
    pytest.param(2, 8192, 8192, 2, 256, True, jnp.bfloat16,
                 id="head-dim-256-four-walked-tiles"),
    pytest.param(2, 5000, 5000, 2, 128, True, jnp.bfloat16,
                 id="padded-two-walked-tiles"),
    pytest.param(2, 200, 1000, 4, 128, False, jnp.bfloat16,
                 id="short-queries-padded-keys"),
    pytest.param(2, 1000, 200, 4, 64, True, jnp.bfloat16,
                 id="more-queries-than-keys"),
    pytest.param(8, 20, 20, 4, 32, True, jnp.bfloat16,
                 id="shorter-than-a-tile"),
    pytest.param(2, 4096, 4096, 4, 128, True, jnp.float32, id="float32"),
])
def test_flash_kernels_compile_for_v5e(one_chip, B, Tq, Tk, H, D, causal,
                                       dtype):
    """Forward and both backward kernels lower to Mosaic and fit VMEM at the
    blocks `_default_blocks` derives, and the optimised program holds the
    layout copies its head width asks for: no `copy` or `transpose` of a
    (B, T, H, D)-sized array beside the calls where a head is read in place
    (D of 128 lanes), the program as it was where it is not.

    q, k, v come in as the models hand them over, (B, T, H*D) arrays viewed
    as (B, T, H, D), and the gradients leave in that form: a (B, T, H, D)
    entry parameter is tiled over (H, D) and would be re-tiled on its way to
    either layout, which is the caller's cost and not the calls'."""
    q = jax.ShapeDtypeStruct((B, Tq, H * D), dtype, sharding=one_chip)
    k = jax.ShapeDtypeStruct((B, Tk, H * D), dtype, sharding=one_chip)

    def loss(q, k, v):
        q, k, v = (a.reshape(B, -1, H, D) for a in (q, k, v))
        out = flash_attention(q, k, v, causal, None, None, None, False)
        return jnp.sum(out.reshape(B, Tq, H * D).astype(jnp.float32))

    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, k, k)
    assert lowered.as_text().count("tpu_custom_call") == 3
    copies = _operand_sized_copies(lowered.compile().as_text(),
                                   {B * Tq * H * D, B * Tk * H * D})
    assert len(copies) == _COPIES_AS_THEY_WERE.get((Tq, Tk, D), 0), copies


_KERNEL_CALL = re.compile(
    r"%(flash_\w+?)(?:\.\d+)? = (.*?) custom-call\(.*"
    r"operand_layout_constraints=\{(.*?)\}, frontend_attributes")


def _kernel_widths(hlo):
    """{kernel: (last dimension of each operand, of each result)} of the
    compiled program's flash calls."""
    def lanes(shapes):
        return tuple(int(dims.split(",")[-1])
                     for dims in re.findall(r"\w+\[([\d,]+)\]", shapes))
    return {name: (lanes(operands), lanes(results))
            for name, results, operands in _KERNEL_CALL.findall(hlo)}


# what the three kernels of a latent head read and write, by last dimension:
# q, k, dq, dk at 256 lanes, v, O, dO, dv at 128; then the row statistics
_TWO_WIDTHS = {"flash_fwd": ((256, 256, 128), (128, 1)),
               "flash_dq": ((256, 256, 128, 128, 1, 1), (256,)),
               "flash_dkv": ((256, 256, 128, 128, 512, 512), (256, 128))}


@pytest.mark.parametrize("T", [pytest.param(4096, id="joyai-cell"),
                               pytest.param(2048, id="kimilinear-cell")])
def test_two_width_flash_kernels_compile_for_v5e(one_chip, T):
    """A latent head's calls at the two cells' sizes, (8, T, 8, 256 / 128):
    the three kernels lower to Mosaic and fit VMEM with v, O, dO and dv at
    v's width, and of the optimised program's six transposing copies (the
    six of `_COPIES_AS_THEY_WERE` at 256 lanes) four are of q's size, q and
    k in and dq and dk out, and two of v's, half as many bytes."""
    B, H, D, Dv = 8, 8, 256, 128
    q = jax.ShapeDtypeStruct((B, T, H * D), jnp.bfloat16, sharding=one_chip)
    v = jax.ShapeDtypeStruct((B, T, H * Dv), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        q, k, v = (a.reshape(B, T, H, -1) for a in (q, k, v))
        out = flash_attention(q, k, v, True, None, None, None, False)
        return jnp.sum(out.reshape(B, T, H * Dv).astype(jnp.float32))

    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, q, v)
    assert lowered.as_text().count("tpu_custom_call") == 3
    hlo = lowered.compile().as_text()
    assert _kernel_widths(hlo) == _TWO_WIDTHS
    assert [len(_operand_sized_copies(hlo, {B * T * H * w}))
            for w in (D, Dv)] == [4, 2]


@pytest.mark.parametrize("B,T,H,D", [
    pytest.param(8, 2048, 16, 128, id="cgpt-cell"),
    pytest.param(2, 4096, 4, 128, id="chip-smoke-D"),
])
def test_flash_kernels_compile_from_4d_arrays(one_chip, B, T, H, D):
    """A caller that hands over materialised (B, T, H, D) arrays at the
    in-place width (`chip_smoke.py` stage D does): the three kernels lower
    and the program compiles, with the re-tiling such arrays cost left to
    the compiler. Only compiled, as every case was before the layout."""
    q = jax.ShapeDtypeStruct((B, T, H, D), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, None, None, None,
                                       False).astype(jnp.float32))

    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, q, q)
    assert lowered.as_text().count("tpu_custom_call") == 3
    lowered.compile()


@pytest.mark.parametrize("H,D,in_place", [
    pytest.param(16, 128, True, id="cgpt-widths-in-place"),
    pytest.param(8, 64, False, id="head-dim-64-copied"),
])
def test_encoder_block_compiles_for_v5e(one_chip, monkeypatch, H, D,
                                        in_place):
    """One GPT-2 block, forward under `remat` and gradient. At
    `cgpt1p3b_train_stream`'s widths the three kernels read and write
    (B, T, H*D) arrays and the compiled program holds no array shaped
    (..., heads, head_dim) at all, so nothing is re-tiled or transposed
    between the projections and the calls. (A q cut out of a (B, T, 3H, D)
    view of the fused projection was re-tiled on its way in and dq on its
    way out: PERF.md section 6, PR 31.) At a width that is copied the block
    only has to lower and compile with its lane split."""
    from mmlspark_tpu.models import build_model
    from mmlspark_tpu.ops import pallas_kernels
    monkeypatch.setattr(pallas_kernels, "_interpret", lambda: False)
    B, T = 8, 2048
    model = build_model({
        "type": "transformer", "d_model": H * D, "heads": H, "layers": 1,
        "mlp_ratio": 4, "vocab_size": 50257, "max_len": T, "causal": True,
        "remat": True, "num_classes": 2, "attn_impl": "flash"})
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((2, T), jnp.int32)))
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), params)
    tokens = jax.ShapeDtypeStruct((B, T), jnp.int32, sharding=one_chip)
    hlo = jax.jit(jax.grad(lambda p, t: jnp.sum(model.apply(p, t)))).lower(
        params, tokens).compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 4
    if in_place:
        assert re.findall(rf"\w+\[[\d,]*,(?:{H}|{3 * H}),{D}\]", hlo) == []


def test_rotary_latent_layer_compiles_for_v5e(one_chip, monkeypatch):
    """`joyai_train_stream`'s latent layer at its own size (8 rows of 4,096
    positions, 8 heads, query/key 128 + 64 rotated, value 128, the query
    behind a 1,536-wide bottleneck), forward and gradient: the rotation is
    plain XLA before the three flash calls, and no operand of the compiled
    program is viewed by pairs, (..., 32, 2), a shape the chip would tile 64
    times over. The calls take two widths: q, k, dq and dk at 256 lanes
    (192 zero-padded) are the only (B*H, T, 256) arrays the program holds,
    and v, O, dO and dv cross the kernels at their own 128."""
    from mmlspark_tpu.models import kimi_linear as kl
    from mmlspark_tpu.ops import pallas_kernels
    monkeypatch.setattr(pallas_kernels, "_interpret", lambda: False)
    B, T, d = 8, 4096, 2048
    layer = kl.MLALayer(8, 512, 128, 64, 128,
                        kl.causal_attention("flash", 512), 1e-6,
                        jnp.bfloat16, 1536, 32e6)
    x = jax.ShapeDtypeStruct((B, T, d), jnp.bfloat16, sharding=one_chip)
    params = jax.eval_shape(lambda: layer.init(
        jax.random.PRNGKey(0), jnp.zeros((1, T, d), jnp.bfloat16)))
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), params)
    hlo = jax.jit(jax.grad(lambda p, x: jnp.sum(
        layer.apply(p, x).astype(jnp.float32)))).lower(
            params, x).compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 3
    assert re.findall(r"\w+\[[\d,]*,32,2\]", hlo) == []
    assert _kernel_widths(hlo) == _TWO_WIDTHS
    copied = re.findall(rf"^\s*(?:ROOT )?%(\S+) = \w+\[{B * 8},{T},256\]", hlo,
                        re.M)
    assert len(copied) == 4, copied           # q, k in; dq, dk out


def test_remat_blocks_keep_the_flash_residuals_on_v5e(one_chip, monkeypatch):
    """Two of `joyai_train_stream`'s blocks under `remat` (latent mixer,
    dense SwiGLU) at 8 rows of 4,096 positions, gradient: the backward pass
    keeps each flash call's result and log-sum-exp (`remat_block`'s policy)
    and recomputes the rest of the block, so the compiled program runs
    `flash_fwd` once a block, not twice, beside one `flash_dq` and one
    `flash_dkv`."""
    import functools
    import flax.linen as nn
    from mmlspark_tpu.models import kimi_linear as kl
    from mmlspark_tpu.ops import pallas_kernels
    monkeypatch.setattr(pallas_kernels, "_interpret", lambda: False)
    B, T, d = 8, 4096, 2048
    mixer = functools.partial(
        kl.MLALayer, 8, 512, 128, 64, 128, kl.causal_attention("flash", 512),
        1e-6, jnp.bfloat16, 1536, 32e6)
    mlp = functools.partial(kl.SwiGLU, 7168, jnp.bfloat16)

    class Two(nn.Module):
        @nn.compact
        def __call__(self, x):
            for i in range(2):
                x, _ = kl.remat_block()(mixer, mlp, 1e-6, jnp.bfloat16,
                                        name=f"block{i}")(x)
            return x

    model = Two()
    x = jax.ShapeDtypeStruct((B, T, d), jnp.bfloat16, sharding=one_chip)
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, T, d), jnp.bfloat16)))
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), params)
    hlo = jax.jit(jax.grad(lambda p, x: jnp.sum(
        model.apply(p, x).astype(jnp.float32)))).lower(
            params, x).compile().as_text()
    calls = re.findall(r"%(flash_\w+?)(?:\.\d+)? = ", hlo)
    assert sorted(calls) == ["flash_dkv"] * 2 + ["flash_dq"] * 2 + \
        ["flash_fwd"] * 2


def test_vocabulary_loss_walk_holds_no_whole_logits_on_v5e(one_chip):
    """Both heads' loss walk at the cell's size (8 x 4,096 positions onto
    16,160 vocabulary rows in chunks of 512), value and gradients: the
    compiled program holds (8, 512, 16160) chunks and no array with the
    batch's 32,768 positions beside the vocabulary, and its loops are the
    `while`s `lm_head_ms` reads: they carry the hidden states by chunks."""
    from mmlspark_tpu.models.joyai_llm_flash import chunked_token_losses
    B, T, d, V = 8, 4096, 2048, 16160
    shape = lambda s, t: jax.ShapeDtypeStruct(s, t, sharding=one_chip)

    def loss(h, scale, kernel, targets):
        return jnp.sum(chunked_token_losses(
            h, scale, kernel, targets, jnp.arange(T) < T - 1, eps=1e-6,
            chunk=512, dtype=jnp.bfloat16))

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        shape((B, T, d), jnp.bfloat16), shape((d,), jnp.float32),
        shape((d, V), jnp.float32), shape((B, T), jnp.int32)
    ).compile().as_text()
    assert f"[{B},512,{V}]" in hlo
    assert re.findall(rf"\[(?:{B * T}|{B},{T}),{V}\]", hlo) == []
    loops = [line for line in hlo.splitlines()
             if re.search(r"= \(.*\) while\(", line)]
    assert loops and all("[8,8,512,2048]" in line for line in loops)


def _kernel_heads(hlo):
    """{kernel: (first dimension of each operand, of each result)} of the
    compiled program's flash calls: B * heads on the copied layout."""
    def first(shapes):
        return tuple(int(dims.split(",")[0])
                     for dims in re.findall(r"\w+\[([\d,]+)\]", shapes))
    return {name: (first(operands), first(results))
            for name, results, operands in _KERNEL_CALL.findall(hlo)}


# what the three kernels of a grouped call read and write, by B * heads:
# q, dO, the row statistics, O and dq at the 8 query heads, k, v, dk and dv
# at the 2 key/value heads
_GROUPED = {"flash_fwd": ((64, 16, 16), (64, 64)),
            "flash_dq": ((64, 16, 16, 64, 64, 64), (64,)),
            "flash_dkv": ((64, 16, 16, 64, 64, 64), (16, 16))}


def test_grouped_flash_kernels_compile_for_v5e(one_chip):
    """`lfm2moe_train_stream`'s attention call at its own size, (8, 4096,
    8 query heads over 2 key/value heads, 64): the three kernels lower to
    Mosaic with the group walked inside them (flash_dkv's grid has one
    dimension more), K, V, dK and dV cross them at 2 heads, and the
    optimised program holds no K or V repeated to the 8 query heads."""
    B, T, H, Hkv, D = 8, 4096, 8, 2, 64
    q = jax.ShapeDtypeStruct((B, T, H * D), jnp.bfloat16, sharding=one_chip)
    k = jax.ShapeDtypeStruct((B, T, Hkv * D), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        q, k, v = (a.reshape(B, T, -1, D) for a in (q, k, v))
        out = flash_attention(q, k, v, True, None, None, None, False)
        return jnp.sum(out.reshape(B, T, H * D).astype(jnp.float32))

    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, k, k)
    assert lowered.as_text().count("tpu_custom_call") == 3
    hlo = lowered.compile().as_text()
    assert _kernel_heads(hlo) == _GROUPED
    # what `jnp.repeat` of K or V to the query heads would have made
    assert re.findall(rf"\[{B},{T},{Hkv},{H // Hkv},{D}\]", hlo) == []


def test_lfm2_step_compiles_and_fits_v5e(one_chip, monkeypatch):
    """`lfm2moe_train_stream`'s whole optimizer step as `fitStream` builds
    it (the benchmark's configuration, 8 rows of 4,096 ids, AdamW, `remat`,
    the per-token loss), compiled for a described v5e: the grouped flash
    kernels once each (the attention block keeps the forward's residuals),
    no (B * T, V) logits, and arguments + results + temporaries within the
    15.9 GB the issue gives the cell (the chip's `bytes_limit` is 16.9 GB:
    two generations of 12 bytes a parameter and one step's temporaries).
    The four expert layers walk tiles of 1,024 rows (`moe.tile_rows` of the
    deployment's 4,096 assignments a held expert): one forward and one
    backward tile loop a layer, `remat`'s second forward loop dead code."""
    import json
    import os
    from mmlspark_tpu.models import build_model, moe, trainer
    from mmlspark_tpu.ops import pallas_kernels
    monkeypatch.setattr(pallas_kernels, "_interpret", lambda: False)
    root = os.path.join(os.path.dirname(__file__), os.pardir)
    with open(os.path.join(root, "benchmark", "configs",
                           "lfm2_8b_a1b.json")) as f:
        config = json.load(f)
    meta = ("source", "input", "learner", "published", "reduced",
            "departures", "assumed", "rehearsal", "parameters")
    module = build_model({**{k: v for k, v in config.items()
                             if k not in meta}, "attn_impl": "flash"})
    lp, T = config["learner"], config["input"]["seq_len"]
    tx = trainer.make_optimizer(lp["optimizer"], lp["learningRate"], 0.9,
                                lp["weightDecay"])
    body = trainer._make_step_body(
        module, tx, trainer.make_loss(lp["loss"], per_example=True), True,
        0.0)
    placed = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                            sharding=one_chip)
    params = jax.tree.map(placed, jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, T), jnp.int32))))
    opt = jax.tree.map(placed, jax.eval_shape(tx.init, params))
    rows = jax.ShapeDtypeStruct((8,), jnp.float32, sharding=one_chip)
    compiled = jax.jit(body).lower(
        params, opt, jax.ShapeDtypeStruct((8, T), jnp.int32,
                                          sharding=one_chip),
        rows, rows).compile()
    hlo = compiled.as_text()
    assert sorted(re.findall(r"%(flash_\w+?)(?:\.\d+)? = ", hlo)) == \
        ["flash_dkv", "flash_dq", "flash_fwd"]
    assert _kernel_heads(hlo) == _GROUPED
    # the tile loops carry the (tokens, hidden) accumulator after the counter
    # (`lfm2_moe_grouped_ms`'s rule); the backward ones then the float32
    # gradients of the three weight stacks
    N, d, f = 8 * T, config["hidden_size"], config["moe_intermediate_size"]
    E, k = config["num_experts"], config["num_experts_per_tok"]
    carried = re.compile(rf"= \(s32\[\], f32\[{N},{d}\], (\w+\[[\d,]*\])")
    loops = [m.group(1) for line in hlo.splitlines() if " while(" in line
             for m in [carried.search(re.sub(r"\{[^}]*\}", "", line))] if m]
    assert sorted(loops) == [f"f32[{E},{d},{f}]"] * 4 + ["s32[]"] * 4
    # the assignment lists are padded by one tile: 1,024 rows, not 256
    assert moe.tile_rows(N * k // config["router_width"]) == 1024
    assert re.findall(rf"\[{N * k + 1024}\]", hlo)
    assert not re.findall(rf"\[{N * k + 256}\]", hlo)
    V = config["vocab_size"]
    assert re.findall(rf"\[(?:{8 * T}|8,{T}),{V}\]", hlo) == []
    m = compiled.memory_analysis()
    held = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert 11.9e9 < m.argument_size_in_bytes + m.output_size_in_bytes \
        < 12.1e9        # 499,955,968 parameters x 12 bytes, twice
    assert held < 15.9e9, held
