"""North-star benchmark: CIFAR-10 ResNet-20 training throughput (imgs/sec/chip).

Meant for the TPU chip (the reference publishes no throughput numbers —
notebook 401 trains a CIFAR ConvNet via CNTK/MPI on GPU VMs; this is the
TPU-native replacement path). Synthetic CIFAR-shaped data (the metric is
compute throughput, not accuracy). Prints ONE JSON line, which names the
``platform`` and ``device_kind`` it ran on: on the cpu backend the shapes
shrink to a smoke size and the value is not a chip number (ROADMAP S1
replaces this script with the benchmark the driver keeps).

Uses the SAME fast path TpuLearner.fit() uses: the epoch data is device-
resident (uint8, the framework's image wire format), the host ships only a
tiny shuffle plan (rotation + window permutation), and a whole epoch of
optimizer steps runs per XLA dispatch via lax.scan with donated
params/opt_state (models/trainer._make_scan_epoch_fn). Round 1 ran one
jitted step per dispatch; a per-step RANDOM GATHER from HBM costs a
multiple of a train step (near-scalar for 1-byte rows), so shuffling is
rotation+window-permutation instead.
"""

import json
import os
import time

import numpy as np

#: bench output schema version (the ``--all`` document; the perf gate —
#: ``python -m mmlspark_tpu.perf`` — parses this and the per-round
#: harness records interchangeably)
SCHEMA = "mmlspark-bench/v1"

#: ``--baseline`` override: a BENCH/run JSON file or a directory holding
#: the BENCH_r*.json trajectory (None = discover via mmlspark_tpu.perf)
_BASELINE = None


def _baseline_value(metric: str):
    """Most recent prior measurement of ``metric`` from the BENCH_r*.json
    trajectory (None when no round has recorded it) — every run prints
    its ratio vs. the last round. Discovery is delegated to
    ``mmlspark_tpu.perf.history``: the explicit ``--baseline`` file/dir
    first, else the cwd and its parents, else the checkout this script
    lives in (the harness cwd is NOT the repo root — the old
    look-next-to-the-script glob never resolved there when the script
    was staged elsewhere, which is why five rounds of BENCH history all
    say ``vs_baseline: null``)."""
    from mmlspark_tpu.perf import history as H
    if _BASELINE and os.path.isfile(_BASELINE):
        rec = H.load_record(_BASELINE)
        m = rec["metrics"].get(metric)
        return m["value"] if m else None
    if _BASELINE:
        d = _BASELINE
    else:
        d = H.find_history_dir(os.path.dirname(os.path.abspath(__file__)))
    if not d:
        return None
    return H.latest_value(H.load_history(d), metric)


def _with_baseline(result: dict) -> dict:
    """Fill ``vs_baseline`` (value / last recorded round) in a metric
    dict that doesn't already carry one."""
    if result.get("vs_baseline") is None and result.get("value"):
        base = _baseline_value(result["metric"])
        if base:
            result["vs_baseline"] = round(result["value"] / base, 3)
    return result


def main(profile: bool = False, mixed: bool = False):
    import jax
    import optax
    from mmlspark_tpu import telemetry
    from mmlspark_tpu.models import build_model
    from mmlspark_tpu.models.trainer import (_make_scan_epoch_fn, make_loss)
    from mmlspark_tpu.parallel import mesh as meshlib

    if profile:
        # device-profiling mode: cost analysis + compile accounting +
        # live-buffer sampling via telemetry.profiler (adds sync points;
        # the default no-flag run keeps the plain async dispatch timing)
        telemetry.profiler.enable()

    batch = 12288         # r1 sweep: 1024->110k, 4096->119k, 8192->123k;
    # r3 sweep on the quiet chip: 8192->134k, 12288->136.6k (best),
    # 14336->134k, 16384->119k (HBM pressure)
    k_steps = 20          # optimizer steps (windows) per epoch dispatch
    n_dispatch = 3        # timed dispatches (K*n = 60 steps)
    if jax.default_backend() == "cpu":
        # smoke scale: the CPU backend exists to validate the pipeline
        # (and --profile's cost/compile/HBM accounting), not to publish
        # numbers — TPU shapes above are untouched
        batch, k_steps, n_dispatch = 32, 2, 1
    n_rows = k_steps * batch  # device-resident epoch (uint8: ~720 MiB
    # + one margin batch; 16384-batch sweeps already hit HBM pressure)

    module = build_model({"type": "resnet", "num_classes": 10})
    mesh = meshlib.create_mesh()
    rng = np.random.default_rng(0)
    x = rng.integers(0, 256, size=(n_rows, 32, 32, 3)).astype(np.uint8)
    y = rng.integers(0, 10, size=n_rows).astype(np.int32)
    params = module.init(jax.random.PRNGKey(0), x[:1].astype(np.float32))
    tx = optax.sgd(0.01, momentum=0.9)
    params = meshlib.put_replicated(params, mesh)
    opt_state = jax.jit(tx.init)(params)
    loss_fn = make_loss("cross_entropy", per_example=True)
    # ``mixed`` = the train_bf16 scenario: the fused loss-scaling step
    # (models/precision.py) with (params, opt_state, scale_state)
    # donated — the roofline twin of the default bf16-compute run
    scale_state = None
    raw_scan = _make_scan_epoch_fn(module, tx, loss_fn, False, 0.0, mesh,
                                   batch, mixed=mixed)
    if mixed:
        from mmlspark_tpu.models.precision import init_scale_state
        scale_state = init_scale_state()
    scan_fn = telemetry.profiler.wrap(
        raw_scan, "bench.scan_epoch_bf16" if mixed else "bench.scan_epoch")

    def run_scan(p, o, s, starts):
        if s is None:
            p, o, loss = scan_fn(p, o, x_dev, y_dev, w_dev, starts)
            return p, o, None, loss
        return scan_fn(p, o, s, x_dev, y_dev, w_dev, starts)

    margin = lambda a: np.concatenate([a, a[:batch]], axis=0)
    x_dev = meshlib.shard_batch(margin(x), mesh)
    y_dev = meshlib.shard_batch(margin(y), mesh)
    w_dev = meshlib.shard_batch(np.ones(n_rows + batch, np.float32), mesh)
    base = np.arange(k_steps, dtype=np.int32) * batch
    def plan(seed):
        r = np.random.default_rng(seed)
        return ((base[r.permutation(k_steps)] + r.integers(0, n_rows))
                % n_rows).astype(np.int32)

    # compile + warmup; a host-side value fetch (float()) is the hard sync
    # that brackets the timing.
    params, opt_state, scale_state, loss = run_scan(params, opt_state,
                                                    scale_state, plan(1))
    float(loss)

    t0 = time.perf_counter()
    with telemetry.trace.span("fit", model="resnet20", path="scan") as fsp:
        for d in range(n_dispatch):
            with telemetry.trace.span("fit/step", dispatch=d,
                                      steps=k_steps) as sp:
                params, opt_state, scale_state, loss = run_scan(
                    params, opt_state, scale_state, plan(2 + d))
                sp.set_sync(loss)
        fsp.set_sync(loss)
    float(loss)  # hard sync: forces the whole chain to complete
    dt = time.perf_counter() - t0

    # the batch shards over every attached chip -> divide for per-chip
    imgs_per_sec = n_dispatch * k_steps * batch / dt / mesh.size
    result = _with_baseline({
        "metric": ("train_bf16_imgs_per_sec_per_chip" if mixed else
                   "cifar10_resnet20_train_imgs_per_sec_per_chip"),
        "value": round(imgs_per_sec, 1),
        "unit": "imgs/sec/chip",
        "vs_baseline": None,
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
    })
    print(json.dumps(result))
    if profile:
        # the device-profile line: per-dispatch FLOPs/bytes, compile
        # count + seconds + causes, achieved FLOP/s vs roofline peak,
        # live-buffer HBM peak
        print(json.dumps({"profile": telemetry.profiler.report()}))
    if telemetry.enabled():
        # second line: the step-breakdown context future BENCH_*.json
        # rounds carry (never emitted in the default disabled mode, so the
        # one-metric-line contract is unchanged there)
        print(json.dumps({"telemetry": telemetry.snapshot()}))
        from mmlspark_tpu.core.env import telemetry_trace_path
        path = telemetry_trace_path() or "bench_trace.jsonl"
        n_ev = telemetry.trace.export_chrome_trace(path)
        print(json.dumps({"trace_file": path, "events": n_ev}))
    return result


def _async_ckpt_comparison():
    """Step-loop cost of checkpointing at a 10x-tighter interval: p50/p90
    step-to-step CADENCE (start-to-start deltas of ``fit/step`` spans,
    warm epochs only — a synchronous save stalls the loop BETWEEN spans,
    so span durations alone would hide it) for (a) no checkpoints, (b)
    synchronous every-step checkpoints, (c) ASYNC every-step
    checkpoints. The claim the number defends: async keeps p50 within
    noise of no-checkpointing while the replay window shrinks to one
    step."""
    import tempfile

    import mmlspark_tpu.telemetry as telemetry
    from mmlspark_tpu import DataFrame
    from mmlspark_tpu.core.utils import object_column
    from mmlspark_tpu.models import TpuLearner

    rng = np.random.default_rng(1)
    n, bs = 512, 64                        # 8 steps/epoch
    x = rng.normal(size=(n, 256)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.int64)
    df = DataFrame({"features": object_column([r for r in x]), "label": y})
    telemetry.enable()
    out = {}
    try:
        for mode, every, asyn in (("none", 0, False),
                                  ("sync_every1", 1, False),
                                  ("async_every1", 1, True)):
            ck = tempfile.mkdtemp(prefix=f"ckpt_cmp_{mode}_")
            learner = (TpuLearner()
                       .setModelConfig({"type": "mlp",
                                        "hidden": [512, 512],
                                        "num_classes": 2})
                       .setEpochs(3).setBatchSize(bs).setLearningRate(0.05)
                       .setDeviceDataCap(1)      # the per-step feed path
                       .setCheckpointDir(ck if every else "")
                       .setCheckpointEverySteps(every)
                       .setAsyncCheckpoint(asyn))
            telemetry.trace.clear()
            t0 = time.perf_counter()
            learner.fit(df)
            wall = time.perf_counter() - t0
            starts = sorted(
                e["ts"] / 1e6 for e in telemetry.trace.events()
                if e.get("name") == "fit/step" and e.get("ph") == "X"
                and e.get("args", {}).get("epoch", 0) >= 1)  # warm only
            deltas = sorted(b - a for a, b in zip(starts, starts[1:]))

            def pct(q, d=deltas):
                return (round(d[min(len(d) - 1, int(q * len(d)))], 5)
                        if d else None)

            out[mode] = {"p50_step_s": pct(0.5), "p90_step_s": pct(0.9),
                         "steps": len(deltas), "wall_s": round(wall, 2)}
    finally:
        telemetry.disable()
    base = out["none"]["p50_step_s"] or 0
    if base:
        out["p50_async_vs_none"] = round(
            out["async_every1"]["p50_step_s"] / base, 3)
        out["p50_sync_vs_none"] = round(
            out["sync_every1"]["p50_step_s"] / base, 3)
    return out


def _straggler_scenario():
    """Proactive-eviction chaos scenario: a 4-host fit where one host's
    heartbeat progress is throttled 5x (a delayed-but-alive straggler,
    paced by a ``delay`` fault at ``elastic.step``). The rolling-MAD
    detector flags it, the sustained flag promotes to an EVICT verdict,
    and the coordinator drops the slow host at the next committed
    checkpoint boundary — verdict->first-step-on-the-smaller-mesh is
    ``chaos_straggler_recovery_seconds``."""
    import tempfile
    import threading

    import jax
    from mmlspark_tpu import DataFrame
    from mmlspark_tpu.core.utils import object_column
    from mmlspark_tpu.models import TpuLearner
    from mmlspark_tpu.resilience import faults
    from mmlspark_tpu.resilience.elastic import ElasticFitCoordinator

    n_hosts = min(4, len(jax.devices()))
    rng = np.random.default_rng(1)
    n, bs, epochs = 512, 16, 3                 # 32 steps/epoch
    x = rng.normal(size=(n, 16)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.int64)
    df = DataFrame({"features": object_column([r for r in x]),
                    "label": y})
    ck = tempfile.mkdtemp(prefix="chaos_straggler_")
    learner = (TpuLearner()
               .setModelConfig({"type": "mlp", "hidden": [32, 16],
                                "num_classes": 2})
               .setEpochs(epochs).setBatchSize(bs).setLearningRate(0.05)
               .setDeviceDataCap(1)
               .setCheckpointDir(ck).setCheckpointEverySteps(4))
    # delay (NOT error) at elastic.step: the fleet is healthy, just
    # paced — the one slow host is simulated by throttling its
    # heartbeat progress 5x below
    faults.configure("elastic.step:delay:1.0:0.04", seed=11)
    coord = ElasticFitCoordinator(learner, n_hosts=n_hosts, grace=0.4,
                                  heartbeat_interval=0.05,
                                  evict_after=2)
    victim = f"host{n_hosts - 1}"       # never host0: the coordinator
    coord.heartbeats[victim].throttle(5)
    t0 = time.perf_counter()
    try:
        model = coord.fit(df)
    finally:
        faults.clear()
    dt = time.perf_counter() - t0
    recovery = next((a["evict_recovery_s"] for a in coord.attempts
                     if "evict_recovery_s" in a), None)
    evicted = sorted(coord.supervisor.dead_hosts())
    assert np.isfinite(model._final_loss)
    return {
        "steps_per_sec": round(len(coord.committed) / dt, 1),
        "evicted": evicted,
        "attempts": len(coord.attempts),
        "metric": _with_baseline({
            "metric": "chaos_straggler_recovery_seconds",
            "value": None if recovery is None else round(recovery, 3),
            "unit": "s", "vs_baseline": None}),
    }


def chaos_train():
    """Elastic-training chaos scenario: a 4-host (simulated device-group)
    fit with 10% injected step faults loses one host mid-run (shrink
    re-mesh), then the victim RELAUNCHES with a joining heartbeat and
    grows the mesh back at the next checkpoint boundary; a second fit
    EVICTS a delayed-but-alive straggler at a checkpoint boundary.
    Reports the verdict->recovered time for all three directions plus
    the async-ckpt step-time comparison; the last printed line is one
    mmlspark-bench/v1 document the perf gate tracks
    (chaos_train_recovery_seconds, chaos_grow_recovery_seconds,
    chaos_straggler_recovery_seconds)."""
    # the scenario needs >= 4 devices to host 4 failure domains; on the
    # CPU backend force the virtual device count BEFORE jax imports
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import tempfile
    import threading

    import jax
    from mmlspark_tpu import DataFrame
    from mmlspark_tpu.core.utils import object_column
    from mmlspark_tpu.models import TpuLearner
    from mmlspark_tpu.resilience import faults
    from mmlspark_tpu.resilience.elastic import ElasticFitCoordinator

    n_hosts = min(4, len(jax.devices()))
    if n_hosts < 2:
        raise SystemExit("--chaos-train needs >= 2 devices to lose one")
    rng = np.random.default_rng(0)
    n, bs, epochs = 512, 16, 2                 # 32 steps/epoch
    x = rng.normal(size=(n, 16)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.int64)
    df = DataFrame({"features": object_column([r for r in x]),
                    "label": y})
    ck = tempfile.mkdtemp(prefix="chaos_train_")
    learner = (TpuLearner()
               .setModelConfig({"type": "mlp", "hidden": [32, 16],
                                "num_classes": 2})
               .setEpochs(epochs).setBatchSize(bs).setLearningRate(0.05)
               .setDeviceDataCap(1)            # the per-step feed path
               .setCheckpointDir(ck).setCheckpointEverySteps(8)
               .setAsyncCheckpoint(True))
    # 10% step faults (absorbed by the retry-once policy) + a per-step
    # delay that paces the fit past the verdict window — recovery_s is
    # the metric, the paced steps/sec is reported for context only
    faults.configure("elastic.step:error:0.1;trainer.step:delay:1.0:0.03",
                     seed=7)
    coord = ElasticFitCoordinator(learner, n_hosts=n_hosts, grace=0.3,
                                  heartbeat_interval=0.05,
                                  rejoin_grace=0.15)

    victim = f"host{n_hosts // 2}"
    done = threading.Event()

    def chaos_script():
        # phase 1: preempt the victim at the first step checkpoint
        while not done.is_set():
            if any("_s" in f for f in os.listdir(ck)
                   if f.endswith(".msgpack")):
                coord.heartbeats[victim].kill()
                break
            time.sleep(0.005)
        # phase 2: once the shrink re-mesh is underway, RELAUNCH the
        # victim — its joining heartbeat earns a grow verdict and the
        # mesh grows back at the next checkpoint boundary
        while not done.is_set():
            if len(coord.attempts) >= 2:
                coord.relaunch_host(victim)
                return
            time.sleep(0.005)

    t = threading.Thread(target=chaos_script, daemon=True)
    t.start()
    t0 = time.perf_counter()
    try:
        model = coord.fit(df)
    finally:
        done.set()
        faults.clear()
    dt = time.perf_counter() - t0
    steps_total = len(coord.committed)
    recovery = next((a["recovery_s"] for a in coord.attempts
                     if "recovery_s" in a), None)
    grow_recovery = next((a["grow_recovery_s"] for a in coord.attempts
                          if "grow_recovery_s" in a), None)
    replayed = steps_total - epochs * (n // bs)
    assert np.isfinite(model._final_loss)
    async_cmp = _async_ckpt_comparison()
    straggler = _straggler_scenario()
    metrics = [
        _with_baseline({
            "metric": "chaos_train_recovery_seconds",
            "value": None if recovery is None else round(recovery, 3),
            "unit": "s", "vs_baseline": None}),
        _with_baseline({
            "metric": "chaos_grow_recovery_seconds",
            "value": (None if grow_recovery is None
                      else round(grow_recovery, 3)),
            "unit": "s", "vs_baseline": None}),
        straggler.pop("metric"),
    ]
    doc = {
        "schema": SCHEMA,
        "bench": "chaos-train",
        "backend": jax.default_backend(),
        "steps_per_sec": round(steps_total / dt, 1),
        "steps_total": steps_total,
        "steps_replayed": replayed,
        "hosts": "->".join(str(len(a["hosts"])) for a in coord.attempts),
        "attempts": len(coord.attempts),
        "dead": sorted(coord.supervisor.dead_hosts()),
        "async_ckpt": async_cmp,
        "straggler": straggler,
        "metrics": metrics,
    }
    print(json.dumps(doc))


def gbdt_scenario():
    """GBDT fit + predict wall-clock (the engine's two hot paths). TPU
    runs the bench_gbdt.py 1M-row shape; the CPU backend runs a smoke
    scale that validates the pipeline, mirrors bench.py's own CPU
    policy, and keeps ``--all`` runnable in CI."""
    import jax
    from mmlspark_tpu.models.gbdt import engine
    from mmlspark_tpu.models.gbdt.engine import GBDTParams, fit_gbdt

    if jax.default_backend() == "cpu":
        n, d, iters, depth = 20_000, 16, 10, 4
    else:
        n, d, iters, depth = 1_000_000, 28, 100, 5
    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, d)).astype(np.float32)
    logit = x[:, 0] * 2 + x[:, 1] - x[:, 2] * 0.5 + rng.normal(0, 0.5, n)
    y = (logit > 0).astype(np.float32)
    p = GBDTParams(num_iterations=iters, max_depth=depth,
                   objective="binary")

    def timed_fit():
        t0 = time.perf_counter()
        ens = fit_gbdt(x, y, p)
        np.asarray(ens.leaf).sum()      # hard sync (async dispatch)
        return time.perf_counter() - t0, ens

    _cold, ens = timed_fit()            # compile pass
    fit_s = min(timed_fit()[0] for _ in range(2))
    np.asarray(engine.predict(ens, x)).sum()    # predict compile
    t0 = time.perf_counter()
    np.asarray(engine.predict(ens, x)).sum()
    pred_s = time.perf_counter() - t0
    cfg = f"{n} rows x {d} cols, {iters} iters, depth {depth}"
    out = [_with_baseline({"metric": "gbdt_fit_seconds",
                           "value": round(fit_s, 3), "unit": "s",
                           "vs_baseline": None, "config": cfg}),
           _with_baseline({"metric": "gbdt_predict_seconds",
                           "value": round(pred_s, 3), "unit": "s",
                           "vs_baseline": None, "config": cfg})]
    for r in out:
        print(json.dumps(r))
    return out


def gbdt_predict_quant_scenario():
    """Quantized ensemble predict (``predict_impl='pallas'``): SoA
    uint8/bf16 test tables walked by the tile-resident kernel
    (ops/pallas_kernels.py). On CPU the kernel runs in interpret mode —
    the number validates the path and parity, not speed; the TPU round
    is where the metric earns its keep against ``gbdt_predict_seconds``."""
    import jax
    from mmlspark_tpu.models.gbdt import engine
    from mmlspark_tpu.models.gbdt.engine import GBDTParams, fit_gbdt

    if jax.default_backend() == "cpu":
        # 30 iters, not the gbdt scenario's 10: the ≤1e-3 parity bound
        # is on summed raw scores, and a 10-tree sum is small enough
        # that the per-leaf bf16 rounding (≤ 2^-9 relative) doesn't
        # wash out against it — the committed test configs
        # (tests/test_gbdt.py TestQuantizedPredict) set the bar
        n, d, iters, depth = 8_000, 12, 30, 5
    else:
        n, d, iters, depth = 1_000_000, 28, 100, 5
    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, d)).astype(np.float32)
    logit = x[:, 0] * 2 + x[:, 1] - x[:, 2] * 0.5 + rng.normal(0, 0.5, n)
    y = (logit > 0).astype(np.float32)
    ens = fit_gbdt(x, y, GBDTParams(num_iterations=iters, max_depth=depth,
                                    objective="binary"))
    dense = engine.predict_raw(ens, x, predict_impl="dense")
    np.asarray(engine.predict_raw(ens, x, predict_impl="pallas")).sum()
    t0 = time.perf_counter()
    quant = engine.predict_raw(ens, x, predict_impl="pallas")
    np.asarray(quant).sum()
    quant_s = time.perf_counter() - t0
    # never publish a number for a path that lost parity
    rel = float(np.abs(quant - dense).max() / np.abs(dense).max())
    assert rel <= 1e-3, f"quantized predict parity broke: rel={rel}"
    out = [_with_baseline({
        "metric": "gbdt_predict_quant_seconds",
        "value": round(quant_s, 3), "unit": "s", "vs_baseline": None,
        "rel_err_vs_dense": round(rel, 6),
        "config": f"{n} rows x {d} cols, {iters} iters, depth {depth}, "
                  f"{'interpret' if jax.default_backend() != 'tpu' else 'mosaic'}"})]
    print(json.dumps(out[0]))
    return out


def serving_scenario():
    """Closed-loop serving latency/throughput through the real HTTP ->
    micro-batching -> pjit path (``serve_pipeline``): N threaded clients
    each posting back-to-back. bench_serving.py remains the deep serving
    bench (load levels, chaos, tracing); this is the always-on number
    the perf gate tracks."""
    import base64
    import threading
    import urllib.request

    import jax
    from mmlspark_tpu import DataFrame
    from mmlspark_tpu.core.utils import object_column
    from mmlspark_tpu.io.http import serve_pipeline
    from mmlspark_tpu.models import TpuModel, build_model

    if jax.default_backend() == "cpu":
        dim, hidden, clients, per_client = 64, [32], 4, 12
    else:
        dim, hidden, clients, per_client = 3072, [256, 128], 16, 25
    cfg = {"type": "mlp", "hidden": hidden, "num_classes": 10}
    module = build_model(cfg)
    params = module.init(jax.random.PRNGKey(0),
                         np.zeros((1, dim), np.float32))
    model = (TpuModel().setModelConfig(cfg).setModelParams(params)
             .setInputCol("features"))
    model.warmup(DataFrame({"features": object_column(
        [np.zeros(dim, np.float32)])}), max_rows=64)

    class _Scorer:
        def prepare(self, df):
            feats = [np.frombuffer(base64.b64decode(v), dtype=np.float32)
                     for v in df.col("value")]
            return df.withColumn("features", object_column(feats))

        def transform(self, df):
            scored = model.transform(df)
            replies = [json.dumps({"label": int(np.argmax(s))})
                       for s in scored.col("scores")]
            return scored.withColumn("reply", object_column(replies))

    rng = np.random.default_rng(0)
    payload = base64.b64encode(
        rng.normal(size=dim).astype(np.float32).tobytes())
    scorer = _Scorer()
    source, loop = serve_pipeline(scorer, max_batch=64,
                                  prepare=scorer.prepare)

    def post(timeout=60.0):
        req = urllib.request.Request(source.url, data=payload)
        with urllib.request.urlopen(req, timeout=timeout) as r:
            assert r.status == 200, r.status
            r.read()

    try:
        post(timeout=120)               # warmup: no request pays compile
        lat: list = []
        failures: list = []
        lock = threading.Lock()

        def client():
            mine, bad = [], []
            for _ in range(per_client):
                t0 = time.perf_counter()
                try:
                    post(timeout=30.0)
                    mine.append(time.perf_counter() - t0)
                except Exception as e:
                    bad.append(repr(e))
            with lock:
                lat.extend(mine)
                failures.extend(bad)

        threads = [threading.Thread(target=client)
                   for _ in range(clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        if failures:    # never print numbers over a shrunken sample
            raise RuntimeError(f"{len(failures)} failed requests, "
                               f"e.g. {failures[0]}")
        lat_ms = np.sort(np.array(lat)) * 1e3
        conf = (f"mlp{hidden} dim {dim}, {clients} clients x "
                f"{per_client} reqs")
        out = [_with_baseline({
                   "metric": "serving_closed_loop_p99_ms",
                   "value": round(float(np.percentile(lat_ms, 99)), 2),
                   "unit": "ms", "vs_baseline": None,
                   "p50_ms": round(float(np.percentile(lat_ms, 50)), 2),
                   "config": conf}),
               _with_baseline({
                   "metric": "serving_closed_loop_rps",
                   "value": round(len(lat) / wall, 1),
                   "unit": "req/sec", "vs_baseline": None,
                   "config": conf})]
        for r in out:
            print(json.dumps(r))
        return out
    finally:
        loop.stop()
        source.close()


def pipeline_fused_scenario():
    """Cross-stage XLA fusion (core/capture.py): a 3-stage impute →
    assemble → predict PipelineModel scored as the staged per-stage
    chain vs ONE fused program. Reports wall time for both, plus the
    dispatch-count and boundary-transfer-bytes deltas the fusion
    refactor exists to shrink (N dispatches → number-of-segments;
    intra-segment transfer bytes → 0). Parity is asserted before any
    number is published."""
    import jax
    from mmlspark_tpu import DataFrame, Pipeline
    from mmlspark_tpu.core import capture as capturelib
    from mmlspark_tpu.models.classical import LogisticRegression
    from mmlspark_tpu.stages.basic import FastVectorAssembler
    from mmlspark_tpu.stages.data_stages import CleanMissingData

    if jax.default_backend() == "cpu":
        n, d, repeats = 50_000, 16, 5
    else:
        n, d, repeats = 1_000_000, 64, 5
    rng = np.random.default_rng(0)
    cols = {f"f{i}": rng.normal(size=n) for i in range(d)}
    for i in range(0, d, 3):
        cols[f"f{i}"][::11] = np.nan
    y = (cols["f1"] > 0).astype(np.int64)
    df = DataFrame({**cols, "label": y})
    feats = [f"f{i}" for i in range(d)]
    pm = Pipeline().setStages((
        CleanMissingData().setInputCols(feats),
        FastVectorAssembler().setInputCols(feats).setOutputCol("features"),
        LogisticRegression().setMaxIter(20),
    )).fit(df)

    def _t(fn):
        t0 = time.perf_counter()
        fn()
        return time.perf_counter() - t0

    def timed(fn):
        fn()                            # warm (compiles)
        return min(_t(fn) for _ in range(repeats))

    pm.setFusePipeline(False)
    staged_probs = np.stack(list(pm.transform(df).col("probability")))
    staged_s = timed(lambda: pm.transform(df))
    pm.setFusePipeline(True)
    from mmlspark_tpu import telemetry
    was_enabled = telemetry.enabled()
    telemetry.enable()      # the transfer-bytes counters are the point
    try:
        tb = capturelib._m_transfer
        in0 = tb.labels(direction="in", phase="transform").value
        out0 = tb.labels(direction="out", phase="transform").value
        fused_probs = np.stack(list(pm.transform(df).col("probability")))
        in1 = tb.labels(direction="in", phase="transform").value
        out1 = tb.labels(direction="out", phase="transform").value
    finally:
        if not was_enabled:
            telemetry.disable()
    fused_s = timed(lambda: pm.transform(df))
    # never publish numbers for a fused path that lost parity
    err = float(np.abs(fused_probs - staged_probs).max())
    assert err <= 1e-4, f"fused pipeline parity broke: {err}"
    (entry,) = pm._seg_cache.values()
    pf = entry["pf"]
    assert pf.compiles == 1, pf.compiles   # ONE program for all 3 stages
    cfg = (f"{n} rows x {d} cols, impute->assemble->LR, "
           f"{len(pm.getStages())} stages -> 1 segment")
    out = [_with_baseline({
               "metric": "pipeline_fused_seconds",
               "value": round(fused_s, 4), "unit": "s",
               "vs_baseline": None,
               "speedup_vs_staged": round(staged_s / fused_s, 2),
               "segment_compiles": pf.compiles,
               "fused_dispatches_per_transform": 1,
               "staged_dispatches_per_transform": len(pm.getStages()),
               "boundary_bytes_in": int(in1 - in0),
               "boundary_bytes_out": int(out1 - out0),
               "max_abs_err_vs_staged": err,
               "config": cfg}),
           _with_baseline({
               "metric": "pipeline_staged_seconds",
               "value": round(staged_s, 4), "unit": "s",
               "vs_baseline": None, "config": cfg})]
    for r in out:
        print(json.dumps(r))
    return out


def pipeline_fit_fused_scenario():
    """Fit-side pipeline fusion (Pipeline.fusePipeline on the FIT path):
    a featurize→TpuLearner pipeline fit as the staged chain (host
    assembly, f32-widened epoch uploads) vs the fused program (raw
    wire-dtype uploads, featurize folded into every train dispatch).
    Parity is asserted on the fitted params, ONE compile per fused
    program (flat across every epoch) and a kill-and-resume leg are
    asserted, and fit-phase H2D bytes must be strictly below the staged
    path before any number is published."""
    import tempfile

    import jax
    from mmlspark_tpu import DataFrame, Pipeline, telemetry
    from mmlspark_tpu.core import capture as capturelib
    from mmlspark_tpu.models.trainer import TpuLearner
    from mmlspark_tpu.stages.basic import FastVectorAssembler

    if jax.default_backend() == "cpu":
        n, d, epochs, bs = 100_000, 24, 3, 8192
    else:
        n, d, epochs, bs = 2_000_000, 64, 3, 16384
    rng = np.random.default_rng(0)
    cols = {f"f{i}": rng.integers(-30, 30, size=n).astype(np.int8)
            for i in range(d)}
    label = (np.sum([cols[f"f{i}"] for i in range(4)], axis=0) > 0)
    df = DataFrame({**cols, "label": label.astype(np.int32)})
    feats = [f"f{i}" for i in range(d)]

    def pipe(fuse, ckpt=""):
        lr = (TpuLearner()
              .setModelConfig({"type": "mlp", "hidden": (32,),
                               "num_classes": 2})
              .setEpochs(epochs).setBatchSize(bs).setSeed(3)
              .setLearningRate(0.05).setShuffle(True))
        if ckpt:
            lr.setCheckpointDir(ckpt)
        asm = (FastVectorAssembler().setInputCols(feats)
               .setOutputCol("features"))
        return Pipeline().setStages((asm, lr)).setFusePipeline(fuse), lr

    def leaves_digest(model):
        import hashlib
        h = hashlib.sha256()
        for leaf in jax.tree_util.tree_leaves(
                model.getOrDefault("modelParams")):
            h.update(np.asarray(leaf).tobytes())
        return h.hexdigest()

    was_enabled = telemetry.enabled()
    telemetry.enable()          # the fit-phase H2D counters are the point
    try:
        tb = capturelib._m_transfer
        trainer_tb = None
        from mmlspark_tpu.models import trainer as trainerlib
        trainer_tb = trainerlib._m_transfer_bytes

        p0, _ = pipe(False)
        b0 = trainer_tb.value
        t0 = time.perf_counter()
        pm_staged = p0.fit(df)
        staged_s = time.perf_counter() - t0
        staged_h2d = trainer_tb.value - b0

        p1, lr1 = pipe(True)
        b1 = trainer_tb.value
        fin0 = tb.labels(direction="in", phase="fit").value
        t0 = time.perf_counter()
        pm_fused = p1.fit(df)
        fused_s = time.perf_counter() - t0
        fused_h2d = trainer_tb.value - b1
        fit_in = tb.labels(direction="in", phase="fit").value - fin0

        # never publish numbers for a fused fit that lost parity: same
        # data, same seed -> identical fitted params (f32 exact for the
        # small-int wire values)
        d_staged = leaves_digest(pm_staged.getOrDefault("stages")[-1])
        d_fused = leaves_digest(pm_fused.getOrDefault("stages")[-1])
        assert d_staged == d_fused, "fused fit parity broke"
        # ONE compile per fused program, flat across every epoch
        progs = list(lr1._fused_programs.values())
        assert progs, "fused fit never engaged"
        for pf in progs:
            assert pf.compiles == 1, (pf.name, pf.compiles, pf.causes)
        # raw wire rows must beat the staged f32-widened uploads
        assert fused_h2d < staged_h2d, (fused_h2d, staged_h2d)

        # kill-and-resume: an interrupted fused fit picked up by a fresh
        # learner stays on the fused path with its ONE compile
        with tempfile.TemporaryDirectory() as ck:
            pk, _ = pipe(True, ckpt=ck)
            pk.getOrDefault("stages")[-1].setEpochs(max(1, epochs - 1))
            pk.fit(df)                       # "killed" after epochs-1
            pr, lrr = pipe(True, ckpt=ck)
            pm_res = pr.fit(df)              # resumes the final epoch
            for pf in lrr._fused_programs.values():
                assert pf.compiles == 1, (pf.name, pf.compiles, pf.causes)
            assert leaves_digest(pm_res.getOrDefault("stages")[-1]) \
                == d_fused, "resume broke bit-exactness"
    finally:
        if not was_enabled:
            telemetry.disable()

    cfg = (f"{n} rows x {d} int8 cols, assemble->mlp(32), "
           f"{epochs} epochs, batch {bs}")
    out = [_with_baseline({
               "metric": "pipeline_fit_fused_seconds",
               "value": round(fused_s, 4), "unit": "s",
               "vs_baseline": None,
               "speedup_vs_staged": round(staged_s / fused_s, 2),
               "fit_h2d_bytes_fused": int(fused_h2d),
               "fit_h2d_bytes_staged": int(staged_h2d),
               "fit_phase_transfer_in_bytes": int(fit_in),
               "segment_compiles": 1,
               "config": cfg}),
           _with_baseline({
               "metric": "pipeline_fit_staged_seconds",
               "value": round(staged_s, 4), "unit": "s",
               "vs_baseline": None, "config": cfg})]
    for r in out:
        print(json.dumps(r))
    return out


def loader_scenario():
    """Data-ingest throughput: disk -> threaded JPEG decode/resize ->
    staging -> device (the bench_loader.py pipeline at suite scale).
    Skipped (not failed) when OpenCV is absent — the loader's decode
    path requires it."""
    import tempfile

    import cv2                          # noqa: F401  (corpus writer)
    import jax
    from mmlspark_tpu.io.loader import device_image_batches
    from mmlspark_tpu.native import available

    n_images, batch = ((128, 32) if jax.default_backend() == "cpu"
                       else (1024, 128))
    src_hw, out_hw = (256, 256), (224, 224)
    rng = np.random.default_rng(0)
    with tempfile.TemporaryDirectory() as tmp:
        paths = []
        for i in range(n_images):
            img = rng.integers(0, 256, (*src_hw, 3), dtype=np.uint8)
            p = os.path.join(tmp, f"img_{i:05d}.jpg")
            cv2.imwrite(p, img)
            paths.append(p)
        warm = None
        for warm, _, _ in device_image_batches(paths[:batch], batch,
                                               *out_hw):
            pass
        if warm is not None:
            np.asarray(warm)
        t0 = time.perf_counter()
        total, last = 0, None
        for dev_batch, ok, count in device_image_batches(paths, batch,
                                                         *out_hw):
            total += int(ok[:count].sum())
            last = dev_batch
        _ = np.asarray(last)            # the final transfer must land
        dt = time.perf_counter() - t0
    out = [_with_baseline({
        "metric": "loader_jpeg_to_device_imgs_per_sec",
        "value": round(total / dt, 1), "unit": "imgs/sec",
        "vs_baseline": None, "native_decoder": available(),
        "config": f"{n_images} x {src_hw[0]}px jpeg -> {out_hw[0]}px, "
                  f"batch {batch}"})]
    print(json.dumps(out[0]))
    return out


def tune_fleet_scenario():
    """Fleet hyperparameter search throughput: the ASHA trial scheduler
    (automl/trials.py) running in-process workers over breast_cancer x
    LogisticRegression. Reports settled trials/hour alongside the
    winner's cross-validated accuracy — the quality floor that makes the
    throughput number comparable across rounds (a faster schedule that
    ships a worse model is a regression, not a win)."""
    from sklearn.datasets import load_breast_cancer

    from mmlspark_tpu import DataFrame, telemetry
    from mmlspark_tpu.automl import TuneHyperparameters
    from mmlspark_tpu.models import LogisticRegression

    x, y = load_breast_cancer(return_X_y=True)
    feats = np.empty(len(x), dtype=object)
    for i in range(len(x)):
        feats[i] = x[i, :10].astype(np.float32)
    df = DataFrame({"features": feats, "label": y.astype(np.int64)})

    num_runs, workers, rungs = 8, 4, [2, 4, 8]
    telemetry.enable()
    tuner = (TuneHyperparameters()
             .setModels((LogisticRegression().setMaxIter(10),))
             .setEvaluationMetric("accuracy")
             .setNumFolds(3).setNumRuns(num_runs).setSeed(3)
             .setBackend("fleet").setNumWorkers(workers)
             .setAsha({"eta": 2, "rungs": rungs, "max_seconds": 600}))
    t0 = time.perf_counter()
    model = tuner.fit(df)
    dt = time.perf_counter() - t0

    quality = float(model.getBestMetric())
    floor = 0.80
    assert quality >= floor, (
        f"fleet tune quality {quality:.4f} fell below the {floor} floor "
        f"— the trials/hour number is meaningless at this accuracy")
    cfg = (f"{num_runs} trials x LogisticRegression, {workers} workers, "
           f"eta 2, rungs {rungs}, quality floor {floor}")
    out = [_with_baseline({
               "metric": "tune_trials_per_hour",
               "value": round(num_runs / dt * 3600.0, 1),
               "unit": "trials/hour", "vs_baseline": None,
               "config": cfg}),
           _with_baseline({
               "metric": "tune_fleet_best_accuracy",
               "value": round(quality, 4), "unit": "accuracy",
               "vs_baseline": None, "config": cfg})]
    for r in out:
        print(json.dumps(r))
    return out


def suite(profile: bool = False):
    """``--all``: every scenario, one versioned schema document (the
    last printed line; the perf gate's input). A scenario whose optional
    dependency is missing is recorded as skipped, not failed — CI boxes
    without OpenCV still gate the other hot paths."""
    import jax

    scenarios = (("train", lambda: [main(profile=profile)]),
                 ("train_bf16",
                  lambda: [main(profile=profile, mixed=True)]),
                 ("gbdt", gbdt_scenario),
                 ("gbdt_predict_quant", gbdt_predict_quant_scenario),
                 ("pipeline_fused", pipeline_fused_scenario),
                 ("pipeline_fit_fused", pipeline_fit_fused_scenario),
                 ("serving", serving_scenario),
                 ("tune_fleet", tune_fleet_scenario),
                 ("loader", loader_scenario))
    scen_out: dict = {}
    metrics: list = []
    for name, fn in scenarios:
        t0 = time.perf_counter()
        try:
            results = fn()
        except ImportError as e:
            scen_out[name] = {"skipped": f"missing dependency: {e}"}
            continue
        scen_out[name] = {"wall_s": round(time.perf_counter() - t0, 2),
                          "metrics": [r["metric"] for r in results]}
        metrics.extend(results)
    doc = {"schema": SCHEMA,
           "backend": jax.default_backend(),
           "chips": jax.device_count(),
           "scenarios": scen_out,
           "metrics": metrics}
    print(json.dumps(doc))
    return doc


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--profile", action="store_true",
                    help="capture XLA cost analysis, compile accounting "
                         "and live-buffer HBM peaks (telemetry.profiler); "
                         "prints an extra {\"profile\": ...} JSON line")
    ap.add_argument("--chaos-train", action="store_true",
                    help="elastic-training chaos scenario: kill one "
                         "simulated host mid-fit under 10%% step faults; "
                         "reports steps/sec + recovery seconds "
                         "(docs/reliability.md, elastic training)")
    ap.add_argument("--all", action="store_true",
                    help="multi-scenario suite (train, train_bf16 mixed-"
                         "precision, GBDT fit/predict, quantized predict, "
                         "serving closed-loop, tune_fleet ASHA trial "
                         "scheduling, loader); the last line is "
                         "one mmlspark-bench/v1 JSON document the perf "
                         "gate (python -m mmlspark_tpu.perf) checks "
                         "against the BENCH_r*.json history")
    ap.add_argument("--baseline", metavar="PATH", default=None,
                    help="vs_baseline source: a BENCH/run JSON file or a "
                         "directory holding BENCH_r*.json (default: "
                         "search cwd + parents, then this checkout)")
    args = ap.parse_args()
    if args.baseline:
        _BASELINE = args.baseline
    if args.chaos_train:
        chaos_train()
    elif args.all:
        suite(profile=args.profile)
    else:
        main(profile=args.profile)
