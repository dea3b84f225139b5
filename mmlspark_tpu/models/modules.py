"""Flax model zoo + declarative model configs.

Plays the role BrainScript plays for the reference's trainer (cntk-train/...
/BrainscriptBuilder.scala:16-100): a model is described by a small JSON-able
config dict, built into a flax module by ``build_model``. The reference's
model families (SURVEY.md §2.2): CIFAR ConvNet (notebook 401), ResNet for
image featurization (cntk-model / image-featurizer, notebook 301), MLP
(TrainClassifier), and a BiLSTM sequence tagger (notebook 304).

Every module supports **layer-name truncation**: ``apply(..., output_layer=
name)`` returns that intermediate activation — the mechanism behind headless-
net transfer learning (reference: ImageFeaturizer.scala:117-142 selects
``outputNodeName = layerNames(cutOutputLayers)``). ``layer_names()`` lists
valid names in forward order.

TPU notes: compute in bfloat16 (MXU-native) with float32 params; all shapes
static; no Python control flow on data.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


class _LayerTap:
    """Collects named activations and answers early-exit queries. Because
    output_layer is a *static* argument, the truncated net compiles to a
    program that simply stops at the tapped layer — dead layers are never
    built, matching the reference's AsComposite truncation for free."""

    def __init__(self, output_layer: Optional[str]):
        self.target = output_layer
        self.result = None

    def tap(self, name: str, value):
        if self.target is not None and name == self.target and self.result is None:
            self.result = value
        return value

    @property
    def done(self) -> bool:
        return self.result is not None


class MLPNet(nn.Module):
    """Multilayer perceptron (TrainClassifier's MLP algorithm analog)."""
    hidden: Sequence[int] = (128, 64)
    num_classes: int = 2
    dtype: Any = jnp.bfloat16

    def layer_names(self):
        return [f"dense{i}" for i in range(len(self.hidden))] + ["logits"]

    @nn.compact
    def __call__(self, x, output_layer: Optional[str] = None):
        tap = _LayerTap(output_layer)
        x = x.astype(self.dtype).reshape(x.shape[0], -1)
        for i, h in enumerate(self.hidden):
            x = tap.tap(f"dense{i}", nn.relu(nn.Dense(h, dtype=self.dtype)(x)))
            if tap.done:
                return tap.result.astype(jnp.float32)
        x = tap.tap("logits", nn.Dense(self.num_classes, dtype=self.dtype)(x))
        return x.astype(jnp.float32)


class ConvNet(nn.Module):
    """CIFAR-style ConvNet — the notebook-401 training target (the reference
    trains it via BrainScript ConvNet config on GPU VMs)."""
    channels: Sequence[int] = (32, 32, 64, 64)
    dense: int = 512
    num_classes: int = 10
    dtype: Any = jnp.bfloat16

    def layer_names(self):
        names = [f"conv{i}" for i in range(len(self.channels))]
        return names + ["dense", "logits"]

    @nn.compact
    def __call__(self, x, output_layer: Optional[str] = None):
        tap = _LayerTap(output_layer)
        x = x.astype(self.dtype)
        for i, ch in enumerate(self.channels):
            x = nn.Conv(ch, (3, 3), dtype=self.dtype)(x)
            x = tap.tap(f"conv{i}", nn.relu(x))
            if tap.done:
                return tap.result.astype(jnp.float32)
            if i % 2 == 1:
                x = nn.max_pool(x, (2, 2), strides=(2, 2))
        x = x.reshape(x.shape[0], -1)
        x = tap.tap("dense", nn.relu(nn.Dense(self.dense, dtype=self.dtype)(x)))
        if tap.done:
            return tap.result.astype(jnp.float32)
        x = tap.tap("logits", nn.Dense(self.num_classes, dtype=self.dtype)(x))
        return x.astype(jnp.float32)


class _FrozenAffine(nn.Module):
    """BatchNorm in EVAL mode as a per-channel affine: y = x*scale + bias.

    Exactly torch ``bn.eval()`` when scale = gamma/sqrt(var+eps) and
    bias = beta - mean*scale — ``models.import_weights`` folds a foreign
    checkpoint's running statistics into these two vectors, which is what
    makes imported nets bit-faithful feature extractors (and is pure
    elementwise math XLA fuses into the preceding conv)."""
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        c = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (c,))
        bias = self.param("bias", nn.initializers.zeros, (c,))
        return x * scale.astype(self.dtype) + bias.astype(self.dtype)


def _norm_layer(norm: str, c: int, dtype):
    """The normalization the net trains with ("group", batch-independent,
    shards cleanly) or the affine an imported eval-mode net needs
    ("frozen")."""
    if norm == "frozen":
        return _FrozenAffine(dtype=dtype)
    return nn.GroupNorm(num_groups=None, group_size=c, dtype=dtype)


def _conv_pad(padding: str, kernel: int):
    """flax "SAME" (default) vs torch's fixed symmetric padding — for
    stride-2 convs they disagree on WHERE the pixels land (SAME pads
    (k-1)//2 low / k//2 high, torch k//2 both sides), so imported torch
    nets need the torch layout to reproduce activations exactly."""
    if padding == "torch":
        p = kernel // 2
        return ((p, p), (p, p))
    return "SAME"


class _BasicBlock(nn.Module):
    filters: int
    strides: int
    dtype: Any
    norm: str = "group"
    padding: str = "same"

    @nn.compact
    def __call__(self, x):
        y = nn.Conv(self.filters, (3, 3), (self.strides, self.strides),
                    padding=_conv_pad(self.padding, 3),
                    use_bias=False, dtype=self.dtype)(x)
        y = nn.relu(_norm_layer(self.norm, y.shape[-1], self.dtype)(y))
        y = nn.Conv(self.filters, (3, 3),
                    padding=_conv_pad(self.padding, 3),
                    use_bias=False, dtype=self.dtype)(y)
        y = _norm_layer(self.norm, y.shape[-1], self.dtype)(y)
        if x.shape != y.shape:
            x = nn.Conv(self.filters, (1, 1), (self.strides, self.strides),
                        use_bias=False, dtype=self.dtype)(x)
            if self.norm == "frozen":   # torch normalizes the projection too
                x = _FrozenAffine(dtype=self.dtype)(x)
        return nn.relu(x + y)


class _BottleneckBlock(nn.Module):
    """1x1 reduce -> 3x3 -> 1x1 expand (ResNet-50-family block)."""
    filters: int            # output width (the expanded 4x width)
    strides: int
    dtype: Any
    norm: str = "group"
    padding: str = "same"

    @nn.compact
    def __call__(self, x):
        inner = self.filters // 4
        y = nn.Conv(inner, (1, 1), use_bias=False, dtype=self.dtype)(x)
        y = nn.relu(_norm_layer(self.norm, y.shape[-1], self.dtype)(y))
        y = nn.Conv(inner, (3, 3), (self.strides, self.strides),
                    padding=_conv_pad(self.padding, 3),
                    use_bias=False, dtype=self.dtype)(y)
        y = nn.relu(_norm_layer(self.norm, y.shape[-1], self.dtype)(y))
        y = nn.Conv(self.filters, (1, 1), use_bias=False, dtype=self.dtype)(y)
        y = _norm_layer(self.norm, y.shape[-1], self.dtype)(y)
        if x.shape != y.shape:
            x = nn.Conv(self.filters, (1, 1), (self.strides, self.strides),
                        use_bias=False, dtype=self.dtype)(x)
            if self.norm == "frozen":   # torch normalizes the projection too
                x = _FrozenAffine(dtype=self.dtype)(x)
        return nn.relu(x + y)


class ResNet(nn.Module):
    """ResNet family — the flagship model.

    Default config is the CIFAR ResNet (depth = 6n+2: 20, 32, 56...). With
    ``block='bottleneck'``, per-stage depths and an ImageNet stem it builds
    the ResNet-50 class used by the reference's ImageFeaturizer (SURVEY.md
    §2.2: headless-net transfer learning cuts layers off the top; our
    ``layer_names()``/``output_layer`` is that mechanism).

    Uses per-channel GroupNorm (LayerNorm-style) instead of BatchNorm so the
    forward pass is batch-independent and shards cleanly over the data axis
    without cross-device batch statistics.
    """
    blocks_per_stage: Any = 3          # int, or per-stage list e.g. [3,4,6,3]
    widths: Sequence[int] = (16, 32, 64)
    num_classes: int = 10
    block: str = "basic"               # basic | bottleneck
    stem: str = "cifar"                # cifar (3x3) | imagenet (7x7/2 + pool)
    dtype: Any = jnp.bfloat16
    norm: str = "group"                # group (train) | frozen (imported eval)
    padding: str = "same"              # same (XLA) | torch (imported nets)
    #: per-channel affine applied to the RAW input before the stem —
    #: imported nets fold their preprocessing (e.g. torchvision's
    #: (x/255 - mean)/std) here so the padded border still sees the
    #: normalized zero exactly as torch does
    input_norm: bool = False

    def _depths(self):
        if isinstance(self.blocks_per_stage, int):
            return [self.blocks_per_stage] * len(self.widths)
        depths = list(self.blocks_per_stage)
        if len(depths) != len(self.widths):
            raise ValueError(
                f"blocks_per_stage has {len(depths)} stages but widths has "
                f"{len(self.widths)} — set both (e.g. resnet50: "
                f"blocks_per_stage=[3,4,6,3], widths=[256,512,1024,2048])")
        return depths

    def layer_names(self):
        names = ["stem"]
        for s, depth in enumerate(self._depths()):
            names += [f"stage{s}_block{b}" for b in range(depth)]
        return names + ["pool", "logits"]

    @nn.compact
    def __call__(self, x, output_layer: Optional[str] = None):
        if self.block not in ("basic", "bottleneck"):
            raise ValueError(f"block must be basic|bottleneck, "
                             f"got {self.block!r}")
        if self.stem not in ("cifar", "imagenet"):
            raise ValueError(f"stem must be cifar|imagenet, got {self.stem!r}")
        if self.norm not in ("group", "frozen"):
            raise ValueError(f"norm must be group|frozen, got {self.norm!r}")
        if self.padding not in ("same", "torch"):
            raise ValueError(f"padding must be same|torch, "
                             f"got {self.padding!r}")
        Block = _BasicBlock if self.block == "basic" else _BottleneckBlock
        stem_width = (self.widths[0] // 4 if self.block == "bottleneck"
                      else self.widths[0])
        tap = _LayerTap(output_layer)
        x = x.astype(self.dtype)
        if self.input_norm:
            x = _FrozenAffine(dtype=self.dtype, name="input_norm")(x)
        if self.stem == "imagenet":
            x = nn.Conv(stem_width, (7, 7), (2, 2),
                        padding=_conv_pad(self.padding, 7),
                        use_bias=False, dtype=self.dtype)(x)
        else:
            x = nn.Conv(stem_width, (3, 3),
                        padding=_conv_pad(self.padding, 3),
                        use_bias=False, dtype=self.dtype)(x)
        x = nn.relu(_norm_layer(self.norm, x.shape[-1], self.dtype)(x))
        if self.stem == "imagenet":
            x = nn.max_pool(x, (3, 3), strides=(2, 2),
                            padding=("SAME" if self.padding == "same"
                                     else ((1, 1), (1, 1))))
        x = tap.tap("stem", x)
        if tap.done:
            return tap.result.astype(jnp.float32)
        for s, (width, depth) in enumerate(zip(self.widths, self._depths())):
            for b in range(depth):
                strides = 2 if (s > 0 and b == 0) else 1
                x = tap.tap(f"stage{s}_block{b}",
                            Block(width, strides, self.dtype,
                                  self.norm, self.padding)(x))
                if tap.done:
                    return tap.result.astype(jnp.float32)
        x = tap.tap("pool", jnp.mean(x, axis=(1, 2)))
        if tap.done:
            return tap.result.astype(jnp.float32)
        x = tap.tap("logits", nn.Dense(self.num_classes, dtype=self.dtype)(x))
        return x.astype(jnp.float32)


class BiLSTMTagger(nn.Module):
    """Bidirectional LSTM sequence tagger (notebook-304 analog: medical
    entity extraction ran a pre-trained Keras BiLSTM through CNTKModel).

    Input: int32 token ids (B, T). Output: per-token logits (B, T, classes).
    Uses lax.scan-backed flax RNN (static unroll-free, jit-friendly).
    """
    vocab_size: int = 10000
    embed_dim: int = 128
    hidden: int = 128
    num_classes: int = 8
    dtype: Any = jnp.bfloat16

    def layer_names(self):
        return ["embed", "bilstm", "logits"]

    @nn.compact
    def __call__(self, tokens, output_layer: Optional[str] = None):
        tap = _LayerTap(output_layer)
        x = tap.tap("embed", nn.Embed(self.vocab_size, self.embed_dim,
                                      dtype=self.dtype)(tokens))
        if tap.done:
            return tap.result.astype(jnp.float32)
        fwd = nn.RNN(nn.LSTMCell(self.hidden, dtype=self.dtype))(x)
        bwd = nn.RNN(nn.LSTMCell(self.hidden, dtype=self.dtype),
                     reverse=True, keep_order=True)(x)
        x = tap.tap("bilstm", jnp.concatenate([fwd, bwd], axis=-1))
        if tap.done:
            return tap.result.astype(jnp.float32)
        x = tap.tap("logits", nn.Dense(self.num_classes, dtype=self.dtype)(x))
        return x.astype(jnp.float32)


class _EncoderBlock(nn.Module):
    """One pre-norm transformer block: attention + (dense | MoE) FFN."""
    d_model: int
    heads: int
    mlp_ratio: int
    dtype: Any
    attention: Callable            # (q, k, v) -> o, injected by the encoder
    num_experts: int = 0
    expert_top_k: int = 2
    capacity_factor: float = 1.25

    @nn.compact
    def __call__(self, x, row_mask=None):
        B, T, _ = x.shape
        H, D = self.heads, self.d_model // self.heads
        h = nn.LayerNorm(dtype=self.dtype)(x)
        qkv = nn.Dense(3 * self.d_model, use_bias=False, dtype=self.dtype)(h)
        # thirds of the projection's last dimension, then viewed by heads:
        # the compiler tiles an array it has to materialise over its last
        # two dimensions, so a third cut out of a (B, T, 3H, D) view would
        # be re-tiled on its way to kernels that read (B, T, H*D) in place
        q, k, v = (a.reshape(B, T, H, D)
                   for a in jnp.split(qkv, 3, axis=-1))
        a = self.attention(q, k, v).reshape(B, T, self.d_model)
        x = x + nn.Dense(self.d_model, use_bias=False, dtype=self.dtype)(a)
        h = nn.LayerNorm(dtype=self.dtype)(x)
        if self.num_experts > 0:
            from .moe import MoEMLP
            h = MoEMLP(num_experts=self.num_experts,
                       d_hidden=self.mlp_ratio * self.d_model,
                       top_k=self.expert_top_k,
                       capacity_factor=self.capacity_factor,
                       dtype=self.dtype)(h, row_mask=row_mask)
        else:
            h = nn.Dense(self.mlp_ratio * self.d_model, dtype=self.dtype)(h)
            h = nn.Dense(self.d_model, dtype=self.dtype)(nn.gelu(h))
        return x + h


class TransformerEncoder(nn.Module):
    """Transformer encoder for long-context sequence work — the model family
    the reference lacks entirely (SURVEY.md §5: no attention, no sequence
    parallelism; its only sequence model is the notebook-304 BiLSTM). Built
    so context scales: attention is pluggable — ``attn_fn`` injects a
    sequence-parallel form (parallel.sequence.make_sp_attention: ring over
    ppermute, or Ulysses all-to-all) without touching the module. Default
    ``attn_impl='auto'`` picks the Pallas flash kernel on TPU (block_size is
    then ignored — the kernel tiles itself) and single-device blockwise
    (FlashAttention-recurrence, O(T) memory, honors block_size) elsewhere.
    ``remat=True`` rematerializes each block on the backward pass
    (jax.checkpoint): activation memory drops from O(layers*T) to O(T) at
    ~1/3 extra FLOPs — the standard long-context trade.

    TPU sizing note: pick ``d_model/heads`` (head_dim) = 128 where model
    quality allows — the MXU contracts 128-deep, so head_dim 64 runs the
    attention matmuls at roughly half rate (the deficit is structural,
    not a kernel issue).

    Input: int32 token ids (B, T). Output: (B, num_classes) when
    ``pool='mean'``, else per-token (B, T, num_classes).
    """
    vocab_size: int = 10000
    d_model: int = 128
    heads: int = 4
    layers: int = 2
    mlp_ratio: int = 4
    num_classes: int = 2
    max_len: int = 2048
    causal: bool = False
    pool: str = "mean"            # "mean" | "none"
    dtype: Any = jnp.bfloat16
    attn_fn: Optional[Callable] = None
    attn_impl: str = "auto"        # auto | blockwise | flash (Pallas kernel)
    mesh: Any = None               # the Mesh the caller jits under (flash)
    block_size: int = 512
    num_experts: int = 0           # > 0 swaps the FFN for a MoE block (EP)
    expert_top_k: int = 2
    capacity_factor: float = 1.25
    remat: bool = False            # jax.checkpoint each block (dense FFN only)

    def layer_names(self):
        return ["embed"] + [f"block{i}" for i in range(self.layers)] + ["logits"]

    def _attention(self, q, k, v):
        if self.attn_fn is not None:
            return self.attn_fn(q, k, v)
        impl = self.attn_impl
        if impl == "auto":
            # the Pallas kernel on tpu; blockwise on the cpu test backend
            # (interpret-mode Pallas is a correctness tool, not a path)
            from ..core.env import on_tpu
            impl = "flash" if on_tpu() else "blockwise"
        if impl == "flash":
            from ..ops.pallas_kernels import flash_attention
            flash = functools.partial(flash_attention, causal=self.causal)
            mesh = self.mesh
            if (mesh is None or mesh.size == 1
                    or q.shape[0] % mesh.shape["data"] != 0):
                # one-device programs, flax's 2-row eager init included
                return flash(q, k, v)
            # GSPMD cannot partition a Mosaic call ("wrap the call in a
            # shard_map"): under a multi-device mesh the kernel runs per
            # batch shard
            spec = P("data", None, None, None)
            return jax.shard_map(flash, mesh=mesh, in_specs=(spec,) * 3,
                                 out_specs=spec, check_vma=False)(q, k, v)
        from ..parallel.sequence import blockwise_attention
        return blockwise_attention(q, k, v, block_size=self.block_size,
                                   causal=self.causal)

    @nn.compact
    def __call__(self, tokens, output_layer: Optional[str] = None,
                 row_mask=None):
        tap = _LayerTap(output_layer)
        B, T = tokens.shape
        if T > self.max_len:
            raise ValueError(f"sequence length {T} exceeds max_len "
                             f"{self.max_len}; XLA would silently clamp the "
                             f"position gather")
        if self.d_model % self.heads != 0:
            raise ValueError(f"d_model ({self.d_model}) must be divisible "
                             f"by heads ({self.heads})")
        if self.remat and self.num_experts > 0:
            raise ValueError("remat with MoE blocks is unsupported (the sown "
                             "aux loss does not survive rematerialization)")
        x = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype)(tokens)
        pos = nn.Embed(self.max_len, self.d_model, dtype=self.dtype)(
            jnp.arange(T)[None, :])
        x = tap.tap("embed", x + pos)
        if tap.done:
            return tap.result.astype(jnp.float32)
        Block = nn.remat(_EncoderBlock) if self.remat else _EncoderBlock
        for i in range(self.layers):
            # explicit name: the param tree is identical with and without
            # remat, so the two variants can load each other's params (note:
            # this block refactor itself renamed transformer param paths —
            # acceptable pre-release, nothing persisted exists)
            blk = Block(d_model=self.d_model, heads=self.heads,
                        mlp_ratio=self.mlp_ratio, dtype=self.dtype,
                        attention=self._attention,
                        num_experts=self.num_experts,
                        expert_top_k=self.expert_top_k,
                        capacity_factor=self.capacity_factor,
                        name=f"block{i}")
            x = tap.tap(f"block{i}", blk(x, row_mask))
            if tap.done:
                return tap.result.astype(jnp.float32)
        x = nn.LayerNorm(dtype=self.dtype)(x)
        if self.pool not in ("mean", "none"):
            raise ValueError(f"pool must be 'mean' or 'none', got "
                             f"{self.pool!r}")
        if self.pool == "mean":
            x = jnp.mean(x, axis=1)
        x = tap.tap("logits", nn.Dense(self.num_classes, dtype=self.dtype)(x))
        return x.astype(jnp.float32)


# ---------------------------------------------------------------- registry

# families whose input is int token ids (callers must cast features to int32)
TOKEN_MODELS = ("bilstm", "transformer", "kimi_linear", "joyai_llm_flash",
                "lfm2_moe")

MODEL_BUILDERS: dict[str, Callable[..., nn.Module]] = {
    "mlp": lambda cfg: MLPNet(
        hidden=tuple(cfg.get("hidden", (128, 64))),
        num_classes=cfg.get("num_classes", 2),
        dtype=jnp.dtype(cfg.get("dtype", jnp.bfloat16))),
    "convnet": lambda cfg: ConvNet(
        channels=tuple(cfg.get("channels", (32, 32, 64, 64))),
        dense=cfg.get("dense", 512),
        num_classes=cfg.get("num_classes", 10),
        dtype=jnp.dtype(cfg.get("dtype", jnp.bfloat16))),
    "resnet": lambda cfg: ResNet(
        blocks_per_stage=cfg.get("blocks_per_stage", 3),
        widths=tuple(cfg.get("widths", (16, 32, 64))),
        num_classes=cfg.get("num_classes", 10),
        block=cfg.get("block", "basic"),
        stem=cfg.get("stem", "cifar"),
        dtype=jnp.dtype(cfg.get("dtype", jnp.bfloat16)),
        norm=cfg.get("norm", "group"),
        padding=cfg.get("padding", "same"),
        input_norm=cfg.get("input_norm", False)),
    # the reference ImageFeaturizer's headline model (ResNet-50, ImageNet)
    "resnet50": lambda cfg: ResNet(
        blocks_per_stage=tuple(cfg.get("blocks_per_stage", (3, 4, 6, 3))),
        widths=tuple(cfg.get("widths", (256, 512, 1024, 2048))),
        num_classes=cfg.get("num_classes", 1000),
        block="bottleneck", stem="imagenet",
        dtype=jnp.dtype(cfg.get("dtype", jnp.bfloat16)),
        norm=cfg.get("norm", "group"),
        padding=cfg.get("padding", "same"),
        input_norm=cfg.get("input_norm", False)),
    "bilstm": lambda cfg: BiLSTMTagger(
        vocab_size=cfg.get("vocab_size", 10000),
        embed_dim=cfg.get("embed_dim", 128),
        hidden=cfg.get("hidden", 128),
        num_classes=cfg.get("num_classes", 8),
        dtype=jnp.dtype(cfg.get("dtype", jnp.bfloat16))),
    "transformer": lambda cfg, attn_fn=None, mesh=None: TransformerEncoder(
        vocab_size=cfg.get("vocab_size", 10000),
        d_model=cfg.get("d_model", 128),
        heads=cfg.get("heads", 4),
        layers=cfg.get("layers", 2),
        mlp_ratio=cfg.get("mlp_ratio", 4),
        num_classes=cfg.get("num_classes", 2),
        max_len=cfg.get("max_len", 2048),
        causal=cfg.get("causal", False),
        pool=cfg.get("pool", "mean"),
        block_size=cfg.get("block_size", 512),
        attn_impl=cfg.get("attn_impl", "auto"),
        num_experts=cfg.get("num_experts", 0),
        expert_top_k=cfg.get("expert_top_k", 2),
        capacity_factor=cfg.get("capacity_factor", 1.25),
        remat=cfg.get("remat", False),
        dtype=jnp.dtype(cfg.get("dtype", jnp.bfloat16)),
        attn_fn=attn_fn, mesh=mesh),
    # the hybrid family: delta-rule linear attention, NoPE latent attention,
    # dropless share-aware experts (models/kimi_linear.py; keys as in the
    # model's public config.json)
    "kimi_linear": lambda cfg: _kimi_linear(cfg),   # defined below
    # rotary latent attention behind a query bottleneck in every layer, the
    # same experts, a vocabulary head with a per-token loss and a
    # multi-token-prediction module (models/joyai_llm_flash.py)
    "joyai_llm_flash": lambda cfg: _joyai_llm_flash(cfg),
    # gated short convolutions and grouped-query attention with per-head
    # QK-norm and whole-head rotary, the same experts without a shared one,
    # a vocabulary head tied to the embedding (models/lfm2_moe.py)
    "lfm2_moe": lambda cfg: _lfm2_moe(cfg),
}


def _kimi_linear(cfg):
    from .kimi_linear import build
    return build(cfg)


def _joyai_llm_flash(cfg):
    from .joyai_llm_flash import build
    return build(cfg)


def _lfm2_moe(cfg):
    from .lfm2_moe import build
    return build(cfg)


#: the key that counts the routed experts held, by family (other configs may
#: carry these keys and route nothing)
_EXPERTS_KEY = {"transformer": "num_experts", "kimi_linear": "num_experts",
                "joyai_llm_flash": "n_routed_experts",
                "lfm2_moe": "num_experts"}


def has_experts(config: dict) -> bool:
    """Whether the configuration's model routes tokens over experts: the
    families that count experts held (`_EXPERTS_KEY`; other configs may carry
    the key), which are also the ones whose ``__call__`` takes a
    ``row_mask``."""
    key = _EXPERTS_KEY.get(config.get("type"))
    return key is not None and config.get(key, 0) > 0


def build_model(config: dict, attn_fn: Optional[Callable] = None,
                mesh=None) -> nn.Module:
    """config: {"type": <family>, ...family kwargs...} -> flax module.

    ``attn_fn`` (transformer only): inject a sequence-parallel attention
    callable (parallel.sequence.make_sp_attention) — kept out of the config
    dict so configs stay JSON-serializable. ``mesh`` (transformer only): the
    mesh the caller jits the module under, so the flash kernel can run per
    batch shard; without it the module lowers for one device."""
    cfg = dict(config)
    mtype = cfg.pop("type")
    if mtype not in MODEL_BUILDERS:
        raise KeyError(f"unknown model type {mtype!r}; "
                       f"have {sorted(MODEL_BUILDERS)}")
    if mtype == "transformer":
        return MODEL_BUILDERS[mtype](cfg, attn_fn=attn_fn, mesh=mesh)
    return MODEL_BUILDERS[mtype](cfg)


def example_input(config: dict, batch: int = 2):
    """A tiny correctly-shaped input for init/compile checks."""
    mtype = config["type"]
    if mtype == "mlp":
        return jnp.zeros((batch, config.get("input_dim", 16)), jnp.float32)
    if mtype in ("convnet", "resnet", "resnet50"):
        default_hw = 64 if mtype == "resnet50" else 32
        h = config.get("height", default_hw)
        w = config.get("width", default_hw)
        c = config.get("channels_in", 3)
        return jnp.zeros((batch, h, w, c), jnp.float32)
    if mtype in TOKEN_MODELS:
        return jnp.zeros((batch, config.get("seq_len", 16)), jnp.int32)
    raise KeyError(mtype)
