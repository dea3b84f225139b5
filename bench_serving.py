"""Serving benchmark: HTTP -> continuous batching -> pjit inference on the
real chip (the reference's serving story is DistributedHTTPSource feeding
CNTKModel, SURVEY.md §2.4/§3.5 — no published latency/throughput numbers).

Measures end-to-end client-observed latency (p50/p99) and sustained
throughput for a ResNet-20 scorer behind `serve_pipeline`, with uint8 image
payloads (the wire format TpuModel.transferDtype optimizes). Prints one
JSON line per load level; the last line is the headline.

``--open-loop`` runs the PRODUCTION-SHAPED benchmark instead: an
open-loop arrival process (Poisson, or bursty on/off — requests arrive on
the schedule whether or not earlier ones finished, unlike the closed loop
above whose clients self-throttle) drives BOTH serving engines over the
same model and schedule:

  * ``polling``    — the seed's micro-batch loop (`serve_pipeline`:
                     getBatch drains whatever arrived, per-row f32 host
                     decode);
  * ``continuous`` — the shape-bucket continuous-batching engine
                     (`io/serving`: max-wait bucket formation, fused
                     decode->pad->pjit->unpad step, AOT-warm buckets);

and reports **goodput** (200-replies within the deadline per second) and
p50/p99/p999 latency under saturation. The last line is one
``mmlspark-bench/v1`` document carrying the `serving_open_loop_*`
metrics.

``--chaos`` runs the resilience scenario instead: the PROCESS fleet
(`serve_fleet` + FleetSupervisor) under a 10% injected `fleet.poll` error
rate plus one mid-run worker kill. Clients post through a RetryPolicy (the
documented client contract under worker loss) and the report adds
`recovery_s` — wall time from the kill until the restarted worker's URL
serves a request again — plus the retry/restart counters.
"""

import argparse
import base64
import json
import threading
import time

import numpy as np


class _ImageScorer:
    """(id, value) -> reply: decode base64 uint8 image batch, score.

    ``prepare`` (the per-row base64 decode + feature assembly) is split
    from ``transform`` (the pjit score) so the serving loop's prefetch
    thread decodes the NEXT micro-batch while the current one runs on
    device."""

    def __init__(self, cfg=None, params=None):
        import jax
        from mmlspark_tpu.models import TpuModel, build_model
        cfg = cfg or {"type": "resnet", "num_classes": 10}
        module = build_model(cfg)
        if params is None:
            params = module.init(jax.random.PRNGKey(0),
                                 np.zeros((1, 32, 32, 3), np.float32))
        self.model = (TpuModel().setModelConfig(cfg).setModelParams(params)
                      .setInputCol("features").setTransferDtype("bfloat16")
                      .setInputShape((3, 32, 32)))
        from mmlspark_tpu import DataFrame
        from mmlspark_tpu.core.utils import object_column
        ex = DataFrame({"features": object_column(
            [np.zeros(32 * 32 * 3, np.float32)])})
        self.model.warmup(ex, max_rows=256)  # no request pays a compile

    def prepare(self, df):
        from mmlspark_tpu.core.utils import object_column
        imgs = [np.frombuffer(base64.b64decode(v), dtype=np.uint8)
                .reshape(32, 32, 3).astype(np.float32).ravel()
                for v in df.col("value")]
        return df.withColumn("features", object_column(imgs))

    def transform(self, df):
        from mmlspark_tpu.core.utils import object_column
        scored = self.model.transform(df)
        replies = [json.dumps({"label": int(np.argmax(s))})
                   for s in scored.col("scores")]
        return scored.withColumn("reply", object_column(replies))


class _ChaosScorer(_ImageScorer):
    """Fleet transformer: prepare + transform fused (the ReplayServingLoop
    has no separate prepare stage)."""

    def transform(self, df):
        return super().transform(self.prepare(df))


def chaos_main(fault_rate: float = 0.1, clients: int = 8,
               per_client: int = 30, trace: bool = False):
    """Fleet chaos run: injected poll faults + one worker kill mid-run.
    ``trace=True`` additionally enables distributed tracing in every
    process (workers inherit MMLSPARK_TPU_TELEMETRY), collects each
    process's span buffer at the end, and merges them into one
    per-request Chrome trace (serving_trace.jsonl)."""
    import os
    import tempfile
    from mmlspark_tpu import telemetry
    from mmlspark_tpu.io.http.fleet import serve_fleet
    from mmlspark_tpu.resilience import faults
    from mmlspark_tpu.resilience.policy import RetryPolicy
    import urllib.request

    telemetry.enable()
    if trace:
        # spawned worker processes read the env at import — this is how
        # their ingress spans (and the traceparent envelope) turn on
        os.environ["MMLSPARK_TPU_TELEMETRY"] = "1"
    if fault_rate > 0:
        faults.configure(f"fleet.poll:error:{fault_rate}", seed=0)
    rng = np.random.default_rng(0)
    payload = base64.b64encode(
        rng.integers(0, 256, 32 * 32 * 3, dtype=np.uint8).tobytes())

    source, loop = serve_fleet(_ChaosScorer(), n_workers=2, supervise=True,
                               probe_interval=0.1)
    urls = [w.url for w in source.workers]

    def post(url, timeout=30.0):
        req = urllib.request.Request(url, data=payload)
        with urllib.request.urlopen(req, timeout=timeout) as r:
            assert r.status == 200, r.status
            return r.read()

    try:
        post(urls[0], timeout=180)       # warmup: compile on worker 0
        post(urls[1], timeout=60)

        lat: list = []
        failures: list = []
        lock = threading.Lock()

        def worker(ci):
            policy = RetryPolicy(name="bench.client", max_attempts=60,
                                 base_delay=0.05, max_delay=0.5,
                                 deadline=60.0, seed=ci)
            mine, bad = [], []
            for _ in range(per_client):
                t0 = time.perf_counter()
                try:
                    policy.run(lambda _a: post(urls[ci % 2], timeout=5.0))
                    mine.append(time.perf_counter() - t0)
                except Exception as e:
                    bad.append(repr(e))
            with lock:
                lat.extend(mine)
                failures.extend(bad)

        threads = [threading.Thread(target=worker, args=(ci,))
                   for ci in range(clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        time.sleep(1.0)
        t_kill = time.perf_counter()
        source.killWorker(0)             # the mid-run worker kill
        # recovery = kill -> the same URL serves again (supervisor restart)
        recovery = None
        deadline = time.monotonic() + 60
        while recovery is None and time.monotonic() < deadline:
            try:
                post(urls[0], timeout=2.0)
                recovery = time.perf_counter() - t_kill
            except Exception:
                time.sleep(0.05)
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        if failures:
            raise RuntimeError(f"{len(failures)} lost requests under "
                               f"chaos, e.g. {failures[0]}")
        snap = telemetry.snapshot()

        def total(name):
            return sum(s["value"]
                       for s in snap.get(name, {}).get("series", []))

        lat_ms = np.sort(np.array(lat)) * 1e3
        result = {
            "metric": "serving_resnet20_fleet_chaos",
            "fault_rate": fault_rate,
            "clients": clients,
            "requests": len(lat),
            "lost": 0,
            "throughput_rps": round(len(lat) / wall, 1),
            "p50_ms": round(float(np.percentile(lat_ms, 50)), 1),
            "p99_ms": round(float(np.percentile(lat_ms, 99)), 1),
            "recovery_s": None if recovery is None else round(recovery, 2),
            "faults_injected": total("mmlspark_faults_injected_total"),
            "retries": total("mmlspark_retry_attempts_total"),
            "worker_restarts": total(
                "mmlspark_supervisor_worker_restarts_total"),
        }
        if trace:
            # one Chrome-trace file per process -> one merged per-request
            # tree: every hop of a request shares its trace_id, spans
            # nest via parent_span_id (load in Perfetto)
            tdir = tempfile.mkdtemp(prefix="fleet_trace_")
            paths = source.collect_traces(tdir)
            out = "serving_trace.jsonl"
            merged = telemetry.merge_traces(paths, out)
            traced = {(e.get("args") or {}).get("trace_id")
                      for e in merged} - {None}
            result.update(trace_file=out, trace_events=len(merged),
                          trace_processes=len(paths),
                          requests_traced=len(traced))
        print(json.dumps(result))
        return result
    finally:
        loop.stop()
        faults.clear()
        telemetry.disable()


def arrival_times(process: str, rate: float, duration: float,
                  seed: int = 0, burst_duty: float = 0.25,
                  burst_period: float = 1.0) -> np.ndarray:
    """Open-loop arrival schedule (seconds from t0).

    ``poisson``: exponential inter-arrivals at ``rate``/s. ``bursty``:
    the same MEAN rate delivered as on/off square-wave bursts —
    ``burst_duty`` of each ``burst_period`` carries Poisson arrivals at
    ``rate / burst_duty`` (4x the mean by default), the rest is silent;
    the tail-latency scenario continuous batching + admission control
    exist for. Deterministic per (process, rate, duration, seed)."""
    rng = np.random.default_rng(seed)
    out = []
    if process == "poisson":
        t = rng.exponential(1.0 / rate)
        while t < duration:
            out.append(t)
            t += rng.exponential(1.0 / rate)
    elif process == "bursty":
        on_rate = rate / burst_duty
        k = 0
        while k * burst_period < duration:
            t = k * burst_period + rng.exponential(1.0 / on_rate)
            stop = min(k * burst_period + burst_duty * burst_period,
                       duration)
            while t < stop:
                out.append(t)
                t += rng.exponential(1.0 / on_rate)
            k += 1
    else:
        raise ValueError(f"arrival process must be poisson|bursty, "
                         f"got {process!r}")
    return np.asarray(out)


def run_open_loop(url, payload: bytes, schedule: np.ndarray,
                  deadline: float = 1.0, pool: int = 64) -> dict:
    """Drive one serving URL with an open-loop schedule from a bounded
    client pool; returns goodput + latency percentiles + failure
    taxonomy. A reply counts toward GOODPUT only when it is a 200 within
    ``deadline`` of its scheduled arrival; 503 sheds, late replies,
    errors, and timeouts all count offered-but-not-good. When every pool
    client is busy the schedule slips (recorded as ``slipped`` — the
    practical bound on offered concurrency). ``url`` may be a callable
    ``() -> url`` so elastic-fleet scenarios pick a live replica per
    request."""
    import urllib.error
    import urllib.request

    idx = {"i": 0}
    lock = threading.Lock()
    lat: list = []        # good-reply latencies (from scheduled arrival)
    counts = {"good": 0, "shed": 0, "late": 0, "error": 0, "slipped": 0}

    def client():
        while True:
            with lock:
                i = idx["i"]
                if i >= len(schedule):
                    return
                idx["i"] = i + 1
            target = t0 + schedule[i]
            now = time.perf_counter()
            if now < target:
                time.sleep(target - now)
            elif now - target > 0.001:
                with lock:
                    counts["slipped"] += 1
            try:
                u = url() if callable(url) else url
                req = urllib.request.Request(u, data=payload)
                with urllib.request.urlopen(req, timeout=deadline) as r:
                    ok = r.status == 200
                    r.read()
            except urllib.error.HTTPError as e:
                with lock:
                    counts["shed" if e.code == 503 else "error"] += 1
                continue
            except Exception:
                with lock:
                    counts["error"] += 1
                continue
            dt = time.perf_counter() - target
            with lock:
                if ok and dt <= deadline:
                    counts["good"] += 1
                    lat.append(dt)
                else:
                    counts["late" if ok else "error"] += 1

    threads = [threading.Thread(target=client) for _ in range(pool)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    lat_ms = np.sort(np.asarray(lat)) * 1e3 if lat else np.array([0.0])
    return {
        "offered": len(schedule),
        "offered_rps": round(len(schedule) / wall, 1),
        "goodput_rps": round(counts["good"] / wall, 1),
        "good": counts["good"], "shed": counts["shed"],
        "late": counts["late"], "errors": counts["error"],
        "slipped": counts["slipped"],
        "p50_ms": round(float(np.percentile(lat_ms, 50)), 1),
        "p99_ms": round(float(np.percentile(lat_ms, 99)), 1),
        "p999_ms": round(float(np.percentile(lat_ms, 99.9)), 1),
        "wall_s": round(wall, 2),
    }


def open_loop_main(rate: float, duration: float, arrival: str = "poisson",
                   deadline: float = 1.0, pool: int = 64,
                   smoke: bool = False, max_batch: int = 256,
                   max_wait: float = 0.005, max_queue_depth: int = 1024,
                   engines=("polling", "continuous")):
    """The production-shaped comparison: same model, same payloads, same
    open-loop schedule against the polling loop and the continuous-
    batching engine; prints one JSON line per engine and the
    mmlspark-bench/v1 document last."""
    import jax
    from mmlspark_tpu.io.http import serve_pipeline
    from mmlspark_tpu.io.serving import (BucketPolicy, FusedServingStep,
                                         serve_continuous)
    from mmlspark_tpu.models import build_model

    cfg = ({"type": "convnet", "channels": (4, 4), "dense": 16,
            "num_classes": 10} if smoke
           else {"type": "resnet", "num_classes": 10})
    module = build_model(cfg)
    params = module.init(jax.random.PRNGKey(0),
                         np.zeros((1, 32, 32, 3), np.float32))
    rng = np.random.default_rng(0)
    payload = base64.b64encode(
        rng.integers(0, 256, 32 * 32 * 3, dtype=np.uint8).tobytes())
    schedule = arrival_times(arrival, rate, duration)
    results: dict = {}

    if "polling" in engines:
        scorer = _ImageScorer(cfg, params)   # warmup() precompiles
        source, loop = serve_pipeline(scorer, max_batch=max_batch,
                                      prepare=scorer.prepare,
                                      max_queue_depth=max_queue_depth)
        try:
            results["polling"] = run_open_loop(source.url, payload,
                                               schedule, deadline, pool)
        finally:
            loop.stop()
            source.close()
        print(json.dumps({"engine": "polling", "arrival": arrival,
                          "rate": rate, **results["polling"]}))

    if "continuous" in engines:
        import urllib.request
        from mmlspark_tpu import telemetry
        from mmlspark_tpu.telemetry.federation import (FederatedSampler,
                                                       FleetScraper)
        from mmlspark_tpu.telemetry.timeseries import \
            percentile_from_buckets
        step = FusedServingStep(cfg, params,
                                policy=BucketPolicy(max_batch=max_batch),
                                row_shape=(32, 32, 3),
                                in_dtype=np.uint8, output="argmax")
        source, loop = serve_continuous(step, max_wait=max_wait,
                                        max_queue_depth=max_queue_depth)
        try:
            for _ in range(4):      # compile + settle before either run
                try:
                    urllib.request.urlopen(
                        urllib.request.Request(source.url, data=payload),
                        timeout=30).read()
                except Exception:
                    pass
            # attribution-off baseline: telemetry dark, tail sampling
            # disarmed. Ledger stamping itself is always on, so the p50
            # delta against the instrumented run below prices span
            # emission + the phase histogram + tail sampling — the
            # attribution overhead docs/observability.md budgets at
            # <= 2% on p50.
            base = run_open_loop(source.url, payload, schedule, deadline,
                                 pool)
            # fleet-view vs driver-view: sample the server's own request
            # histogram and scrape it back over HTTP, exactly the way
            # fleet federation sees a worker — the divergence between the
            # merged (server-side) percentiles and the client-observed
            # ones is the part of latency the server never sees (connect
            # + queueing in the kernel + bucket-grid quantization)
            telemetry.timeseries.start(interval=0.25)
            telemetry.trace.enable_tail_sampling(quantile=0.95,
                                                 max_retained=128)
            scraper = FleetScraper(
                targets=[("serving", f"{source.url}timeseries")],
                interval=0.25, sampler=FederatedSampler(interval=0.25))
            scraper.scrape_once()   # seed round: baselines, zero deltas
            cont = results["continuous"] = run_open_loop(
                source.url, payload, schedule, deadline, pool)
            time.sleep(0.6)         # let the sampler tick the last rows
            scraper.scrape_once()
            for q, label in ((0.50, "p50"), (0.99, "p99")):
                p = scraper.sampler.worker_percentile(
                    "serving", "mmlspark_http_request_seconds", q,
                    window=duration + 120.0)
                if p is not None:
                    cont[f"fleet_{label}_ms"] = round(p * 1e3, 1)
            if base["p50_ms"] > 0:
                cont["attribution_overhead_pct"] = round(
                    (cont["p50_ms"] - base["p50_ms"])
                    / base["p50_ms"] * 100.0, 2)
            # per-phase breakdown from the ledger-fed histogram: the
            # instrumented run is the only traffic since telemetry came
            # up, so cumulative bucket counts ARE the run's deltas
            snap = telemetry.registry.snapshot()
            fam = snap.get("mmlspark_serving_phase_seconds", {})
            for s in fam.get("series", []):
                phase = s.get("labels", {}).get("phase")
                if phase not in ("queue", "pad", "device", "readback"):
                    continue
                for q, label in ((0.50, "p50"), (0.99, "p99")):
                    p = percentile_from_buckets(s["buckets"], q)
                    if p is not None:
                        cont[f"phase_{phase}_{label}_ms"] = round(
                            p * 1e3, 2)
            # the ledger phases partition each request, so their _sum
            # totals reconcile with the server-observed request-latency
            # _sum (ratio < 1: the slice after "reply" — the reply-write
            # syscall — is the only part the ledger never sees)
            phase_sum = sum(s.get("sum", 0.0)
                            for s in fam.get("series", []))
            req_sum = sum(
                s.get("sum", 0.0)
                for s in snap.get("mmlspark_http_request_seconds",
                                  {}).get("series", []))
            if req_sum > 0:
                cont["phase_sum_ratio"] = round(phase_sum / req_sum, 3)
            cont["exemplar_linked"] = int(
                ' # {trace_id="' in scraper.sampler.prometheus_text())
            fetched = 0
            for tid in reversed(telemetry.trace.retained_ids()):
                try:
                    with urllib.request.urlopen(
                            f"{source.url}debug/trace/{tid}",
                            timeout=5) as r:
                        fetched = int(r.status == 200
                                      and bool(json.loads(r.read())
                                               .get("events")))
                    break
                except Exception:
                    continue
            cont["trace_fetch_ok"] = fetched
        finally:
            telemetry.trace.disable_tail_sampling()
            telemetry.timeseries.stop()
            loop.stop()
            source.close()
        print(json.dumps({"engine": "continuous", "arrival": arrival,
                          "rate": rate, **results["continuous"]}))

    metrics = []
    cont = results.get("continuous")
    poll = results.get("polling")
    if cont:
        extra = {}
        if poll and poll["goodput_rps"]:
            extra["vs_polling"] = round(
                cont["goodput_rps"] / poll["goodput_rps"], 2)
        metrics.append({"metric": "serving_open_loop_goodput_rps",
                        "value": cont["goodput_rps"], "unit": "req/s",
                        "arrival": arrival, "rate": rate, **extra})
        for q in ("p50", "p99", "p999"):
            metrics.append({"metric": f"serving_open_loop_{q}_ms",
                            "value": cont[f"{q}_ms"], "unit": "ms",
                            "arrival": arrival, "rate": rate})
        for q in ("p50", "p99"):
            if f"fleet_{q}_ms" not in cont:
                continue
            metrics.append({"metric": f"serving_open_loop_fleet_{q}_ms",
                            "value": cont[f"fleet_{q}_ms"], "unit": "ms",
                            "arrival": arrival, "rate": rate})
            metrics.append(
                {"metric": f"serving_open_loop_view_divergence_{q}_ms",
                 "value": round(cont[f"{q}_ms"] - cont[f"fleet_{q}_ms"],
                                1),
                 "unit": "ms", "arrival": arrival, "rate": rate})
        for phase in ("queue", "pad", "device", "readback"):
            for q in ("p50", "p99"):
                key = f"phase_{phase}_{q}_ms"
                if key in cont:
                    metrics.append(
                        {"metric": f"serving_open_loop_{key}",
                         "value": cont[key], "unit": "ms",
                         "arrival": arrival, "rate": rate})
        if "phase_sum_ratio" in cont:
            metrics.append({"metric": "serving_open_loop_phase_sum_ratio",
                            "value": cont["phase_sum_ratio"],
                            "unit": "ratio", "arrival": arrival,
                            "rate": rate})
        if "attribution_overhead_pct" in cont:
            ov = cont["attribution_overhead_pct"]
            metrics.append(
                {"metric": "serving_open_loop_attribution_overhead_pct",
                 "value": ov, "unit": "%", "budget_pct": 2.0,
                 "ok": bool(ov <= 2.0), "arrival": arrival,
                 "rate": rate})
        for key in ("exemplar_linked", "trace_fetch_ok"):
            if key in cont:
                metrics.append({"metric": f"serving_open_loop_{key}",
                                "value": cont[key], "unit": "bool",
                                "arrival": arrival, "rate": rate})
    if poll:
        metrics.append({"metric": "serving_open_loop_polling_goodput_rps",
                        "value": poll["goodput_rps"], "unit": "req/s",
                        "arrival": arrival, "rate": rate})
    doc = {"schema": "mmlspark-bench/v1", "bench": "serving_open_loop",
           "backend": jax.default_backend(), "metrics": metrics}
    print(json.dumps(doc))
    return doc


def chaos_serve_main(rate: float = 300.0, duration: float = 8.0,
                     deadline: float = 0.5, pool: int = 48,
                     smoke: bool = False, seed: int = 0):
    """The elastic-serving chaos scenario: one bursty open-loop load
    against the SLO-driven autoscaled fleet, with a throttled-straggler
    window and a mid-run worker kill -9 layered on top. The fleet must
    GROW under the spike (new workers warm from the AOT bundle — zero
    compiles), reconcile the killed worker back into the same lineage,
    and SHRINK by graceful drain once the load ends. Emits
    ``serving_chaos_{recovery_seconds,goodput_rps}`` in one
    mmlspark-bench/v1 doc."""
    import tempfile
    import urllib.request
    import jax
    from mmlspark_tpu import telemetry
    from mmlspark_tpu.io.http.fleet import ProcessHTTPSource, _Worker
    from mmlspark_tpu.io.http.worker import WorkerServer
    from mmlspark_tpu.io.serving import (BucketPolicy, FusedServingStep,
                                         save_bundle)
    from mmlspark_tpu.models import build_model
    from mmlspark_tpu.resilience import faults
    from mmlspark_tpu.resilience.autoscale import ServingAutoscaler
    from mmlspark_tpu.resilience.reconciler import FleetReconciler
    from mmlspark_tpu.telemetry.slo import SLOEngine
    from mmlspark_tpu.telemetry.timeseries import TimeSeriesSampler

    telemetry.enable()
    cfg = ({"type": "convnet", "channels": (4, 4), "dense": 16,
            "num_classes": 10} if smoke
           else {"type": "resnet", "num_classes": 10})
    module = build_model(cfg)
    params = module.init(jax.random.PRNGKey(0),
                         np.zeros((1, 32, 32, 3), np.float32))
    step = FusedServingStep(cfg, params,
                            policy=BucketPolicy(max_batch=64,
                                                min_bucket=8),
                            row_shape=(32, 32, 3), in_dtype=np.uint8,
                            output="argmax")
    bundle_dir = tempfile.mkdtemp(prefix="serving_chaos_bundle_")
    save_bundle(bundle_dir, step)

    def compiles():
        snap = telemetry.snapshot()
        return sum(s["value"] for s in snap.get(
            "mmlspark_profiler_compiles", {}).get("series", []))

    compiles0 = compiles()
    # in-process bundle workers: the warm-start + drain semantics of the
    # subprocess fleet without paying a JAX import per spawned replica
    servers: list = []

    def spawn(wi, old):
        if old is not None:
            for ws in servers:
                if ws.control_port == old.control:
                    try:
                        ws.close()
                    except Exception:
                        pass
        ws = WorkerServer("127.0.0.1",
                          port=old.port if old is not None else 0,
                          control_port=old.control if old is not None
                          else 0, bundle=bundle_dir)
        servers.append(ws)
        return _Worker("127.0.0.1", ws.source.port, ws.control_port,
                       spawn=False)

    source = ProcessHTTPSource(workers=[spawn(0, None)])
    sampler = TimeSeriesSampler(interval=0.2).start()
    slo = SLOEngine([{"name": "serve-latency", "kind": "latency",
                      "hist": "mmlspark_http_request_seconds",
                      "threshold_s": deadline / 5.0, "target": 0.99,
                      "windows": (0.8, 1.6)}], sampler=sampler)
    rec = FleetReconciler(source, 1, spawn=spawn, min_workers=1,
                          max_workers=3, interval=0.05,
                          probe_interval=0.05,
                          drain_timeout=15.0).start()
    rec.supervisor.probe_timeout = 0.5
    rec.supervisor.restart_backoff = 0.05
    asc = ServingAutoscaler(slo, rec, grow_window=0.4,
                            shrink_window=2.0, cooldown=1.0,
                            idle_rows_per_worker=0.5,
                            interval=0.1).start()

    rng = np.random.default_rng(seed)
    payload = base64.b64encode(
        rng.integers(0, 256, 32 * 32 * 3, dtype=np.uint8).tobytes())
    schedule = arrival_times("bursty", rate, duration, seed=seed)
    pick = {"i": 0}

    def url():
        urls = source.urls
        if not urls:
            return f"http://127.0.0.1:{source.workers[0].port}/"
        pick["i"] += 1
        return urls[pick["i"] % len(urls)]

    recovery = {"s": None}

    def scenario():
        # straggler window: the serving path slows (alive, just slow)
        time.sleep(duration * 0.3)
        faults.configure("serving.batch:delay:0.5:0.05", seed=seed)
        time.sleep(duration * 0.2)
        faults.clear()
        # kill -9 worker 0 mid-load; recovery = kill -> same URL serves
        port0 = source.workers[0].port
        servers[0].close()
        t_kill = time.perf_counter()
        dead_url = f"http://127.0.0.1:{port0}/"
        deadline_t = time.monotonic() + 30
        while time.monotonic() < deadline_t:
            try:
                req = urllib.request.Request(dead_url, data=payload)
                with urllib.request.urlopen(req, timeout=1.0) as r:
                    if r.status == 200:
                        recovery["s"] = time.perf_counter() - t_kill
                        return
            except Exception:
                time.sleep(0.05)

    chaos = threading.Thread(target=scenario)
    chaos.start()
    result = run_open_loop(url, payload, schedule, deadline, pool)
    chaos.join(timeout=60)

    # idle: the fleet shrinks back to the floor by graceful drain
    deadline_t = time.monotonic() + 20
    while not (rec.observed() == 1 and rec.converged()) \
            and time.monotonic() < deadline_t:
        time.sleep(0.1)
    snap = telemetry.snapshot()

    def total(name):
        return sum(s["value"] for s in snap.get(name, {}).get(
            "series", []))

    verdicts = {tuple(sorted(s["labels"].items()))[0][1]: s["value"]
                for s in snap.get("mmlspark_autoscale_verdicts",
                                  {}).get("series", [])}
    headline = {
        "metric": "serving_chaos", "arrival": "bursty", "rate": rate,
        **result,
        "recovery_s": (None if recovery["s"] is None
                       else round(recovery["s"], 2)),
        "grow_verdicts": int(verdicts.get("grow", 0)),
        "shrink_verdicts": int(verdicts.get("shrink", 0)),
        "workers_retired": int(total("mmlspark_fleet_workers_retired")),
        "final_workers": rec.observed(),
        "compiles_during_traffic": int(compiles() - compiles0),
    }
    print(json.dumps(headline))
    asc.stop()
    rec.stop()
    sampler.stop()
    for ws in servers:
        try:
            ws.close()
        except Exception:
            pass
    source.close()
    faults.clear()
    telemetry.disable()
    metrics = [{"metric": "serving_chaos_goodput_rps",
                "value": result["goodput_rps"], "unit": "req/s",
                "arrival": "bursty", "rate": rate},
               {"metric": "serving_chaos_recovery_seconds",
                "value": headline["recovery_s"], "unit": "s"}]
    doc = {"schema": "mmlspark-bench/v1", "bench": "serving_chaos",
           "backend": jax.default_backend(), "metrics": metrics}
    print(json.dumps(doc))
    return doc


def main():
    import requests
    from mmlspark_tpu.io.http import serve_pipeline

    rng = np.random.default_rng(0)
    payload = base64.b64encode(
        rng.integers(0, 256, 32 * 32 * 3, dtype=np.uint8).tobytes())

    scorer = _ImageScorer()
    source, loop = serve_pipeline(scorer, max_batch=256,
                                  prepare=scorer.prepare)
    try:
        # warmup (compile)
        r = requests.post(source.url, data=payload, timeout=120)
        assert r.status_code == 200, r.text

        headline = None
        for clients, per_client in ((4, 50), (16, 50), (64, 25)):
            lat: list[float] = []
            failures: list[str] = []
            lock = threading.Lock()

            def worker():
                mine, bad = [], []
                for _ in range(per_client):
                    t0 = time.perf_counter()
                    r = requests.post(source.url, data=payload, timeout=60)
                    mine.append(time.perf_counter() - t0)
                    if r.status_code != 200:
                        bad.append(f"{r.status_code}: {r.text[:120]}")
                with lock:
                    lat.extend(mine)
                    failures.extend(bad)

            threads = [threading.Thread(target=worker)
                       for _ in range(clients)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            if failures:   # fail loudly; never print numbers over a
                raise RuntimeError(  # silently shrunken sample
                    f"{len(failures)} failed requests, e.g. {failures[0]}")
            assert len(lat) == clients * per_client
            lat_ms = np.sort(np.array(lat)) * 1e3
            result = {
                "metric": "serving_resnet20_http",
                "clients": clients,
                "throughput_rps": round(len(lat) / wall, 1),
                "p50_ms": round(float(np.percentile(lat_ms, 50)), 1),
                "p99_ms": round(float(np.percentile(lat_ms, 99)), 1),
            }
            print(json.dumps(result))
            headline = result
        return headline
    finally:
        loop.stop()
        source.close()


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--chaos", action="store_true",
                    help="fleet chaos mode: 10%% injected poll faults + "
                         "one mid-run worker kill; reports p50/p99 and "
                         "recovery time")
    ap.add_argument("--fault-rate", type=float, default=0.1)
    ap.add_argument("--clients", type=int, default=8,
                    help="concurrent chaos/trace clients")
    ap.add_argument("--per-client", type=int, default=30,
                    help="requests per chaos/trace client")
    ap.add_argument("--trace", action="store_true",
                    help="distributed-tracing mode: runs the fleet "
                         "scenario with per-process span capture and "
                         "merges every hop into serving_trace.jsonl "
                         "(one trace_id per request; combine with "
                         "--chaos for the fault-injected run)")
    ap.add_argument("--chaos-serve", action="store_true",
                    help="elastic-fleet chaos scenario: bursty spike + "
                         "throttled straggler + worker kill -9 against "
                         "the SLO-driven autoscaled fleet; reports "
                         "goodput, recovery seconds, grow/shrink "
                         "verdicts and emits an mmlspark-bench/v1 doc "
                         "(serving_chaos_*)")
    ap.add_argument("--open-loop", action="store_true",
                    help="open-loop arrival benchmark: polling loop vs "
                         "continuous-batching engine over the same "
                         "Poisson/bursty schedule; reports goodput + "
                         "p50/p99/p999 and emits an mmlspark-bench/v1 "
                         "doc")
    ap.add_argument("--rate", type=float, default=500.0,
                    help="open-loop mean arrival rate (req/s)")
    ap.add_argument("--duration", type=float, default=20.0,
                    help="open-loop schedule length (s)")
    ap.add_argument("--arrival", default="poisson",
                    choices=("poisson", "bursty"))
    ap.add_argument("--deadline-ms", type=float, default=1000.0,
                    help="goodput SLO: a reply counts only if it is a "
                         "200 within this many ms of its scheduled "
                         "arrival")
    ap.add_argument("--pool", type=int, default=64,
                    help="open-loop client pool size (the offered-"
                         "concurrency bound)")
    ap.add_argument("--max-wait", type=float, default=0.005,
                    help="continuous batcher max-wait deadline (s)")
    ap.add_argument("--max-batch", type=int, default=256)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny convnet + short schedule (CPU CI "
                         "validation of the open-loop harness)")
    args = ap.parse_args()
    if args.chaos_serve:
        chaos_serve_main(rate=args.rate, duration=args.duration,
                         deadline=args.deadline_ms / 1e3,
                         pool=args.pool, smoke=args.smoke)
    elif args.open_loop:
        open_loop_main(rate=args.rate, duration=args.duration,
                       arrival=args.arrival,
                       deadline=args.deadline_ms / 1e3, pool=args.pool,
                       smoke=args.smoke, max_batch=args.max_batch,
                       max_wait=args.max_wait)
    elif args.chaos or args.trace:
        chaos_main(fault_rate=args.fault_rate if args.chaos else 0.0,
                   clients=args.clients, per_client=args.per_client,
                   trace=args.trace)
    else:
        main()
