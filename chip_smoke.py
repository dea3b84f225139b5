#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main paths once, in ONE process, through the entry points a user
calls, at the full width of models the repo supports (depth and rows are
modest, widths are not), with data and weights made from a seed:

  A  trainer   TpuLearner.fit (ResNet-20, uint8 32x32x3 rows) on the
               device-resident scan path, then the host-feed + prefetch
               path; TpuModel.transform.
  B  server    FusedServingStep -> serve_continuous; concurrent HTTP POSTs;
               save_bundle -> load_bundle warm.
  C  gbdt      LightGBMClassifier.fit depth-wise and leaf-wise at
               262,144 x 28; transform, default vs predictImpl="dense".
  D  attention the long-context transformer config at T=4096 through
               TpuLearner.fit; flash_attention vs blockwise_attention,
               forward and gradient.
  K  kernels   every Pallas program on the default TPU paths lowers to a
               Mosaic custom call, and interpret mode is off.

Any failed check raises: there is no try/except that carries on. The script
sets no platform: where JAX finds no TPU it exits non-zero and prints no
result. The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
There is one path and there are no options: every stage runs, at the sizes
fixed below.

    python3 chip_smoke.py
"""

import base64
import functools
import json
import math
import shutil
import sys
import tempfile
import threading
import time
import urllib.request

import numpy as np

SEED = 0
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class Clock:
    """Per-stage wall seconds, with XLA compile seconds (backend compile or
    persistent-cache retrieval, as JAX reports them) kept apart."""

    def __init__(self):
        import jax.monitoring
        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.stages = {}
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event, secs, **_):
        if event == BACKEND_COMPILE_EVENT:
            self.compile_s += secs
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def run(self, name, fn, *args):
        c0, n0, h0 = self.compile_s, self.compiles, self.cache_hits
        t0 = time.perf_counter()
        out = fn(*args)
        self.stages[name] = {
            "wall_s": round(time.perf_counter() - t0, 2),
            "compile_s": round(self.compile_s - c0, 2),
            "programs": self.compiles - n0,
            "cache_hits": self.cache_hits - h0}
        print(f"[{name}] passed {json.dumps(self.stages[name])}", flush=True)
        return out


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def _finite(a, what):
    a = np.asarray(a, np.float32)
    check(np.isfinite(a).all(), f"{what}: non-finite values")
    return a


# ------------------------------------------------------------- A · trainer

RESNET_CFG = {"type": "resnet", "num_classes": 10}      # ResNet-20, 16/32/64
BATCH = 1024
TRAIN_ROWS = 16384          # x EPOCHS = 80 optimizer steps on the scan path
EPOCHS = 5
FEED_STEPS = 4              # host-feed + prefetch path
SCORE_ROWS = 2048


def _image_rows(n, rng):
    """uint8 32x32x3 rows whose class shows: a per-class template of 8x8
    colour blocks under noise, so a few optimizer steps lower the loss."""
    templates = np.random.default_rng(SEED + 1).integers(
        0, 256, size=(10, 4, 4, 3)).repeat(8, axis=1).repeat(8, axis=2)
    y = rng.integers(0, 10, size=n)
    noise = rng.integers(0, 256, size=(n, 32, 32, 3))
    x = ((templates[y] + noise) // 2).astype(np.uint8)
    return x, y.astype(np.int64)


def _image_df(x, y=None):
    from mmlspark_tpu import DataFrame
    from mmlspark_tpu.core.schema import make_image_row
    col = np.empty(len(x), dtype=object)
    for i, img in enumerate(x):
        col[i] = make_image_row(f"mem://{i}", 32, 32, 3, img)
    data = {"image": col}
    if y is not None:
        data["label"] = y
    return DataFrame(data)


def stage_trainer():
    import jax
    from mmlspark_tpu.models import TpuLearner
    from mmlspark_tpu.parallel import mesh as meshlib
    rng = np.random.default_rng(SEED)
    x, y = _image_rows(TRAIN_ROWS, rng)
    df = _image_df(x, y)

    def learner():
        return (TpuLearner().setModelConfig(RESNET_CFG)
                .setFeaturesCol("image").setLabelCol("label")
                .setBatchSize(BATCH).setOptimizer("adam")
                .setLearningRate(2e-3).setSeed(SEED))

    # how the framework places one batch: sharded over every device
    mesh = meshlib.create_mesh()
    placed = meshlib.shard_batch(x[:BATCH], mesh)
    shards = placed.addressable_shards
    print(f"    batch placement: {len(shards)} addressable shard(s) of "
          f"{shards[0].data.shape} over mesh {dict(mesh.shape)}", flush=True)
    check(len(shards) == len(jax.devices()),
          f"batch sharded over {len(shards)} of {len(jax.devices())} devices")

    # host-feed + DevicePrefetcher path: the data cap forced below the
    # dataset — also the early-training loss the scan fit must beat
    feed = (learner().setEpochs(1).setDeviceDataCap(1)
            .fit(df.limit(FEED_STEPS * BATCH)))
    loss_early = float(feed._final_loss)
    # device-resident scan path
    model = learner().setEpochs(EPOCHS).fit(df)
    loss_end = float(model._final_loss)
    print(f"    loss after {FEED_STEPS} feed-path steps {loss_early:.4f}; "
          f"after {EPOCHS * (TRAIN_ROWS // BATCH)} scan-path steps "
          f"{loss_end:.4f}", flush=True)
    check(math.isfinite(loss_early) and math.isfinite(loss_end),
          "non-finite training loss")
    check(loss_end < loss_early and loss_end < math.log(10),
          "loss did not go down")

    xt, yt = _image_rows(SCORE_ROWS, rng)
    scores = _finite(np.stack(list(
        model.transform(_image_df(xt)).col("scores"))), "transform scores")
    check(scores.shape == (SCORE_ROWS, 10), f"scores shape {scores.shape}")
    acc = float((scores.argmax(1) == yt).mean())
    print(f"    transform: {SCORE_ROWS} rows -> {scores.shape}, accuracy "
          f"{acc:.3f}", flush=True)
    check(acc > 0.5, f"accuracy {acc} after training")
    return model, xt, scores


# -------------------------------------------------------------- B · server

def _post(url, payload):
    req = urllib.request.Request(url, data=payload)
    with urllib.request.urlopen(req, timeout=120) as r:
        return r.status, r.read().decode()


def _metric(text, name):
    vals = [float(line.rsplit(" ", 1)[1]) for line in text.splitlines()
            if line.startswith(name) and not line.startswith("#")]
    check(vals, f"/metrics has no {name}")
    return sum(vals)


N_REQUESTS = 48


def stage_server(model, rows, scores):
    from mmlspark_tpu import telemetry
    from mmlspark_tpu.io.serving import (BucketPolicy, FusedServingStep,
                                         load_bundle, save_bundle,
                                         serve_continuous)
    telemetry.enable()
    policy = BucketPolicy(max_batch=64, min_bucket=8)
    step = FusedServingStep(RESNET_CFG, model.getModelParams(), policy=policy,
                            row_shape=(32, 32, 3), in_dtype=np.uint8,
                            output="argmax")
    source, loop = serve_continuous(step, warm=True)
    try:
        replies = [None] * N_REQUESTS

        def client(i):
            replies[i] = _post(source.url,
                               base64.b64encode(rows[i].tobytes()))

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(N_REQUESTS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        check(all(r is not None for r in replies), "a client got no reply")
        check(all(code == 200 for code, _ in replies),
              f"non-200 replies: {[c for c, _ in replies if c != 200]}")
        labels = np.array([json.loads(body)["label"] for _, body in replies])
        # the reference is TpuModel.transform on the same rows; a bucket of
        # 8..64 rows and a 2048-row chunk are different XLA programs, so
        # only a near-tie between the top two scores may flip the argmax
        top2 = np.sort(scores[:N_REQUESTS], axis=1)[:, -2:]
        clear = (top2[:, 1] - top2[:, 0]) > 0.05
        check(clear.mean() >= 0.8, "too few rows with a clear argmax")
        same = labels == scores[:N_REQUESTS].argmax(1)
        check(same[clear].all(),
              f"served labels differ from transform on rows "
              f"{np.nonzero(~same & clear)[0].tolist()}")
        with urllib.request.urlopen(source.url + "metrics", timeout=30) as r:
            metrics = r.read().decode()
        misses = _metric(metrics, "mmlspark_serving_exec_cache_misses_total")
        hits = _metric(metrics, "mmlspark_serving_exec_cache_hits_total")
        print(f"    {N_REQUESTS} concurrent POSTs: all 200, "
              f"{int(same.sum())}/{N_REQUESTS} labels equal transform "
              f"({int(clear.sum())} clear), exec cache hits {hits:.0f} "
              f"misses {misses:.0f}", flush=True)
        check(misses == 0, f"{misses} buckets compiled on live traffic")
    finally:
        loop.stop()
        source.close()

    bundle_dir = tempfile.mkdtemp(prefix="chip_smoke_bundle_")
    try:
        save_bundle(bundle_dir, step)
        loaded = load_bundle(bundle_dir)
        warm = loaded.warm_buckets()
        print(f"    bundle reload: warm buckets {warm}, compiles "
              f"{loaded.compiles()}", flush=True)
        check(warm == list(policy.buckets) and loaded.compiles() == 0,
              f"bundle reload not warm: {warm} of {policy.buckets}")
        out = loaded.score_rows(rows[:8], 8)
        check((out == step.score_rows(rows[:8], 8)).all(),
              "reloaded executable disagrees with the live one")
        check(loaded.compiles() == 0, "reloaded step compiled on dispatch")
    finally:
        shutil.rmtree(bundle_dir, ignore_errors=True)
        telemetry.disable()


# ---------------------------------------------------------------- C · gbdt

GBDT_ROWS, GBDT_FEATURES = 262_144, 28


def stage_gbdt():
    from mmlspark_tpu import DataFrame
    from mmlspark_tpu.models.gbdt import LightGBMClassifier
    rng = np.random.default_rng(SEED)
    n = GBDT_ROWS
    x = rng.normal(size=(n, GBDT_FEATURES)).astype(np.float32)
    logit = x[:, 0] * x[:, 1] + np.sin(2 * x[:, 2]) + 0.5 * x[:, 3]
    y = (logit + 0.3 * rng.normal(size=n) > 0).astype(np.int64)
    df = DataFrame({"features": x, "label": y})
    base_rate = max(y.mean(), 1 - y.mean())
    for name, est in (
            ("depth-wise maxDepth=5",
             LightGBMClassifier().setGrowthPolicy("depthwise").setMaxDepth(5)),
            ("leaf-wise numLeaves=31",
             LightGBMClassifier().setNumLeaves(31))):
        model = est.setNumIterations(8).fit(df)
        prob = _finite(np.stack(list(
            model.transform(df).col("probability"))), name)
        dense = _finite(np.stack(list(
            model.setPredictImpl("dense").transform(df).col("probability"))),
            name + " dense")
        check(prob.shape == (n, 2), f"{name}: probability shape {prob.shape}")
        gap = float(np.abs(prob - dense).max())
        acc = float((prob.argmax(1) == y).mean())
        print(f"    {name}: accuracy {acc:.3f} (base rate {base_rate:.3f}), "
              f"default vs dense max |dp| {gap:.2e}", flush=True)
        check(acc > base_rate + 0.05, f"{name}: did not learn ({acc})")
        # the default scores through the quantized kernel (bf16 leaf
        # tables): inside the documented band, and not bit-identical to
        # the f32 dense walk — identical would mean the kernel never ran
        check(0 < gap <= 2e-3, f"{name}: default vs dense gap {gap}")
        check((prob.argmax(1) == dense.argmax(1)).mean() > 0.9999,
              f"{name}: argmax differs between default and dense")


# ----------------------------------------------------------- D · attention

SEQ_LEN, SEQ_BATCH, SEQ_ROWS = 4096, 8, 32
LONGCTX_CFG = {"type": "transformer", "vocab_size": 32000, "d_model": 512,
               "heads": 4, "layers": 4, "num_classes": 8, "max_len": SEQ_LEN,
               "causal": True, "remat": True, "attn_impl": "auto"}


def stage_attention():
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu import DataFrame
    from mmlspark_tpu.models import TpuLearner, build_model
    from mmlspark_tpu.ops.pallas_kernels import flash_attention
    from mmlspark_tpu.parallel.sequence import blockwise_attention
    rng = np.random.default_rng(SEED)
    cfg, T, batch = LONGCTX_CFG, SEQ_LEN, SEQ_BATCH
    tokens = rng.integers(0, 32000, size=(SEQ_ROWS, T)).astype(np.int32)
    labels = (tokens[:, 0] % 8).astype(np.int64)
    model = (TpuLearner().setModelConfig(cfg).setBatchSize(batch)
             .setEpochs(1).setOptimizer("adam").setLearningRate(1e-3)
             .setSeed(SEED)
             .fit(DataFrame({"features": tokens, "label": labels})))
    loss = float(model._final_loss)
    print(f"    transformer d512 h4 L4 T={T} batch {batch}: "
          f"{SEQ_ROWS // batch} steps, loss {loss:.4f}", flush=True)
    check(math.isfinite(loss), "non-finite transformer loss")
    scores = _finite(np.stack(list(model.transform(
        DataFrame({"features": tokens[:batch]})).col("scores"))),
        "transformer scores")
    check(scores.shape == (batch, 8), f"transformer scores {scores.shape}")
    # attn_impl="auto" must have put the Pallas kernel in the program
    hlo = jax.jit(build_model(cfg).apply).lower(
        jax.tree_util.tree_map(jnp.asarray, model.getModelParams()),
        jnp.asarray(tokens[:1])).as_text()
    check("tpu_custom_call" in hlo,
          "attn_impl='auto' lowered without a Mosaic call")

    # one batch, kernel against the blockwise reference in f32; the
    # tolerances are tests/test_pallas_kernels.py's bf16 ones
    q, k, v = (jnp.asarray(rng.normal(size=(2, T, 4, 128)), jnp.float32)
               for _ in range(3))
    qb, kb, vb = (a.astype(jnp.bfloat16) for a in (q, k, v))

    def loss_of(attn):
        return lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32) ** 2)

    flash = functools.partial(flash_attention, causal=True)
    ref = functools.partial(blockwise_attention, block_size=512, causal=True)
    out = _finite(jax.jit(flash)(qb, kb, vb), "flash forward")
    out_ref = np.asarray(jax.jit(ref)(q, k, v))
    err_f = float(np.abs(out - out_ref).max())
    check(np.allclose(out, out_ref, atol=3e-2, rtol=3e-2),
          f"flash forward off by {err_f}")
    g = jax.jit(jax.grad(loss_of(flash), argnums=(0, 1, 2)))(qb, kb, vb)
    g_ref = jax.jit(jax.grad(loss_of(ref), argnums=(0, 1, 2)))(q, k, v)
    errs = []
    for a, b in zip(g, g_ref):
        b = np.asarray(b)
        scale = max(1e-3, float(np.abs(b).max()))
        errs.append(float(np.abs(_finite(a, "flash grad") - b).max()) / scale)
    print(f"    flash vs blockwise (2,{T},4,128) causal: forward max |d| "
          f"{err_f:.2e}, gradient max |d|/scale {max(errs):.2e}", flush=True)
    check(max(errs) <= 5e-2, f"flash gradient off by {errs}")


# ------------------------------------------------------------- K · kernels

def stage_kernels():
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.ops import pallas_kernels as pk
    check(pk._interpret() is False, "Pallas kernels are in interpret mode")
    rng = np.random.default_rng(SEED)
    n, d = 4096, 28
    bins = jnp.asarray(rng.integers(0, 255, size=(d, n)), jnp.uint8)
    g = jnp.asarray(rng.normal(size=n), jnp.float32)
    node = jnp.asarray(rng.integers(0, 4, size=n), jnp.int32)
    T, K = 4, 1
    u8 = lambda hi, shape: rng.integers(0, hi, size=shape).astype(np.uint8)
    leaf = lambda L: jnp.asarray(rng.normal(size=(T, K, L)), jnp.bfloat16)
    split = rng.integers(0, 1, size=(T, K, 30)).astype(np.int32)
    q = jnp.asarray(rng.normal(size=(1, 1024, 4, 128)), jnp.bfloat16)
    attn_loss = lambda q, k, v: jnp.sum(
        pk.flash_attention(q, k, v, causal=True).astype(jnp.float32))
    programs = {
        "mxu_node_histogram": (1, lambda: jax.jit(functools.partial(
            pk.mxu_node_histogram, n_nodes=4, n_bins=255)).lower(
                bins.astype(jnp.int32), node, g, g)),
        "gbdt_predict_quant_levelwise": (1, lambda: jax.jit(
            lambda b: pk.gbdt_predict_quant_levelwise(
                b, u8(d, (T, K, 31)), u8(255, (T, K, 31)), leaf(32),
                depth=5)).lower(bins)),
        "gbdt_predict_quant_leafwise": (1, lambda: jax.jit(
            lambda b: pk.gbdt_predict_quant_leafwise(
                b, split, u8(d, (T, K, 30)), u8(255, (T, K, 30)),
                leaf(31))).lower(bins)),
        "flash_attention fwd": (1, lambda: jax.jit(functools.partial(
            pk.flash_attention, causal=True)).lower(q, q, q)),
        "flash_attention fwd + dq + dk/dv": (3, lambda: jax.jit(jax.grad(
            attn_loss, argnums=(0, 1, 2))).lower(q, q, q)),
    }
    for name, (want, lower) in programs.items():
        lowered = lower()
        calls = lowered.as_text().count("tpu_custom_call")
        check(calls >= want, f"{name}: {calls} Mosaic calls, expected {want}")
        lowered.compile()
        print(f"    {name}: {calls} Mosaic tpu_custom_call, compiled",
              flush=True)


# -------------------------------------------------------------------- main

def main() -> int:
    t_start = time.perf_counter()

    import jax
    import jaxlib
    devices = jax.devices()
    dev0 = devices[0]
    for dev in devices:
        if dev.platform != "tpu":
            print(f"chip_smoke: device {dev} is platform {dev.platform!r}, "
                  f"not 'tpu'; nothing was run", file=sys.stderr)
            return 1
    clock = Clock()
    import mmlspark_tpu
    from mmlspark_tpu import native
    try:
        import libtpu
        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = "not importable"
    print(f"platform: {dev0.platform}\ndevice_kind: {dev0.device_kind}\n"
          f"device_count: {len(devices)}\njax: {jax.__version__} jaxlib: "
          f"{jaxlib.__version__} libtpu: {libtpu_version}\n"
          f"compile_cache_dir: {jax.config.jax_compilation_cache_dir}\n"
          f"native_library_built: {native.available()}\n"
          f"package: {mmlspark_tpu.__file__}", flush=True)

    model, rows, scores = clock.run("A trainer", stage_trainer)
    clock.run("B server", stage_server, model, rows, scores)
    clock.run("C gbdt", stage_gbdt)
    clock.run("D attention", stage_attention)
    clock.run("K kernels", stage_kernels)

    print(json.dumps({
        "stages": clock.stages,
        "total_wall_s": round(time.perf_counter() - t_start, 2),
        "total_compile_s": round(clock.compile_s, 2),
        "programs": clock.compiles, "cache_hits": clock.cache_hits}),
        flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev0.platform, "kind": dev0.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
