"""Data-ingest benchmark: disk -> C++ threaded decode -> staging buffer ->
HBM (SURVEY.md §7 hard part (a): the reference's ingest is element-wise JNI
copies at CNTKModel.scala:67-74 plus scp/getmerge data movement; here whole
batches stream through `io/loader.py` + `native/csrc/loader.cc`).

Writes a synthetic JPEG corpus once, then measures images/sec into device
memory (decode + resize + transfer, pipelined). Prints one JSON line.
"""

import json
import os
import tempfile
import time

import numpy as np

N_IMAGES = 1024
SRC_HW = (256, 256)
OUT_HW = (224, 224)
BATCH = 128


def _corpus(tmp: str) -> list[str]:
    import cv2
    rng = np.random.default_rng(0)
    paths = []
    for i in range(N_IMAGES):
        img = rng.integers(0, 256, (*SRC_HW, 3), dtype=np.uint8)
        p = os.path.join(tmp, f"img_{i:05d}.jpg")
        cv2.imwrite(p, img)
        paths.append(p)
    return paths


def bench_arrow():
    """Arrow->staging->HBM host path (io/arrow.py + native interleave) vs
    the Python-row conversion it replaces. Prints one JSON line."""
    import jax
    try:
        import pyarrow as pa
    except ImportError:
        print(json.dumps({"metric": "arrow_ingest_host_path",
                          "skipped": "pyarrow not installed"}))
        return

    from mmlspark_tpu.io.arrow import batch_to_matrix
    from mmlspark_tpu.native import available

    n, d, chunk = 1 << 20, 32, 1 << 16
    rng = np.random.default_rng(0)
    t = pa.table({f"x{j}": rng.normal(size=n).astype(np.float32)
                  for j in range(d)})
    feats = [f"x{j}" for j in range(d)]
    batches = t.to_batches(max_chunksize=chunk)
    mb = n * d * 4 / 2**20

    # (a) the old shape of the path: per-row Python objects, then a stack
    b0 = batches[0]
    t0 = time.perf_counter()
    rows = [np.array([b0.column(j)[i].as_py() for j in range(d)],
                     dtype=np.float32) for i in range(b0.num_rows)]
    _ = np.stack(rows)
    t_rows = (time.perf_counter() - t0) * (n / b0.num_rows)

    # (b) columnar: zero-copy views + threaded C++ interleave into staging
    buf = np.empty((chunk, d), np.float32)
    t0 = time.perf_counter()
    for b in batches:
        batch_to_matrix(b, feats, out=buf)
    t_col = time.perf_counter() - t0

    # (c) + device transfer
    t0 = time.perf_counter()
    last = None
    for b in batches:
        last = jax.device_put(np.array(batch_to_matrix(b, feats, out=buf)))
    np.asarray(last)
    t_dev = time.perf_counter() - t0

    print(json.dumps({
        "metric": "arrow_ingest_host_path",
        "value": round(mb / t_col, 1),
        "unit": "MB/sec host-side (columnar+interleave)",
        "python_row_path_MBps": round(mb / t_rows, 1),
        "speedup_vs_row_conversion": round(t_rows / t_col, 1),
        "end_to_end_to_device_MBps": round(mb / t_dev, 1),
        "native_interleave": available(),
        "backend": jax.default_backend(),
        "config": f"{n} rows x {d} f32 cols, {chunk}-row record batches",
    }))


def bench_feed_overlap():
    """Feed-path overlap report: a short host-feed fit (deviceDataCap=1
    forces the per-step feed path) with the async prefetcher on, then the
    telemetry snapshot's time breakdown. Overlap is WORKING when the
    consumer-stall total (time the step loop waited on the prefetcher) is
    well under the host-prep total (index/pad/mask/H2D time, which runs on
    the prefetch thread behind device compute). Prints one JSON line."""
    import jax
    from mmlspark_tpu import telemetry
    from mmlspark_tpu.core.dataframe import DataFrame
    from mmlspark_tpu.core.utils import object_column
    from mmlspark_tpu.models import TpuLearner

    was_enabled = telemetry.enabled()
    telemetry.enable()
    try:
        rng = np.random.default_rng(0)
        n, bs, epochs = 4096, 512, 2
        x = rng.normal(size=(n, 3 * 32 * 32)).astype(np.float32)
        y = rng.integers(0, 10, size=n).astype(np.int64)
        df = DataFrame({"features": object_column([r for r in x]),
                        "label": y})
        learner = (TpuLearner()
                   .setModelConfig({"type": "convnet", "channels": [16, 32],
                                    "dense": 64, "num_classes": 10,
                                    "height": 32, "width": 32})
                   .setInputShape((3, 32, 32))
                   .setEpochs(epochs).setBatchSize(bs)
                   .setDeviceDataCap(1))      # force the host-feed path
        t0 = time.perf_counter()
        learner.fit(df)
        dt = time.perf_counter() - t0

        snap = telemetry.snapshot()

        def series_sum(name):
            fam = snap.get(name, {}).get("series") or [{}]
            return float(fam[0].get("sum", 0.0))

        host_prep = series_sum("mmlspark_prefetch_produce_seconds")
        step = series_sum("mmlspark_trainer_step_seconds")
        stall = series_sum("mmlspark_prefetch_consumer_stall_seconds")
        print(json.dumps({
            "metric": "feed_path_prefetch_overlap",
            "value": round(host_prep - stall, 3),
            "unit": "sec of host prep hidden behind device compute",
            "host_prep_sec": round(host_prep, 3),
            "step_sec": round(step, 3),
            "consumer_stall_sec": round(stall, 3),
            "overlap_ok": bool(stall < host_prep),
            "imgs_per_sec": round(epochs * (n // bs) * bs / dt, 1),
            "backend": jax.default_backend(),
            "config": f"{n} rows x 3072 f32, batch {bs}, {epochs} epochs, "
                      f"prefetchDepth=2",
        }))
    finally:
        if not was_enabled:
            telemetry.disable()


def main():
    import jax

    from mmlspark_tpu.io.loader import device_image_batches
    from mmlspark_tpu.native import available

    with tempfile.TemporaryDirectory() as tmp:
        paths = _corpus(tmp)
        # warmup pass primes file cache + threads; sync the final async
        # device_put so no in-flight transfer leaks into the timed region
        warm = None
        for warm, _, _ in device_image_batches(paths[:BATCH * 2], BATCH,
                                               *OUT_HW):
            pass
        if warm is not None:
            np.asarray(warm)

        t0 = time.perf_counter()
        total = 0
        last = None
        for dev_batch, ok, count in device_image_batches(
                paths, BATCH, *OUT_HW):
            total += int(ok[:count].sum())
            last = dev_batch
        _ = np.asarray(last)  # hard sync: the final transfer must land
        dt = time.perf_counter() - t0

        print(json.dumps({
            "metric": "ingest_jpeg_decode_resize_to_hbm",
            "value": round(total / dt, 1),
            "unit": "imgs/sec",
            "backend": jax.default_backend(),
            "native_decoder": available(),
            "images": total,
            "config": f"{SRC_HW[0]}px jpeg -> {OUT_HW[0]}px, batch {BATCH}",
        }))


if __name__ == "__main__":
    main()
    bench_arrow()
    bench_feed_overlap()
