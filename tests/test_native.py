"""Native runtime (C++ libmmltpu) tests: decode parity against cv2, the
threaded prefetch loader's ordering/masking contract, CSV parser parity
against numpy, and the device-feed pipeline end to end.

The reference trusts its native layer via prebuilt jars (NativeLoader.java);
ours is in-repo, so parity with the battle-tested decoders is the test."""

import os

import numpy as np
import pytest

from mmlspark_tpu import native
from mmlspark_tpu.io import (device_image_batches, image_batches,
                             list_images, read_csv, read_csv_matrix)

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native toolchain unavailable")


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(42)


class TestDecode:
    def test_png_bit_exact(self, rng):
        import cv2
        img = rng.integers(0, 256, (33, 47, 3), dtype=np.uint8)
        _, enc = cv2.imencode(".png", img)
        out = native.decode_image(enc.tobytes())
        assert np.array_equal(out, img)

    def test_bmp_bit_exact(self, rng):
        import cv2
        img = rng.integers(0, 256, (21, 17, 3), dtype=np.uint8)
        _, enc = cv2.imencode(".bmp", img)
        assert np.array_equal(native.decode_image(enc.tobytes()), img)

    def test_jpeg_matches_cv2(self, rng):
        import cv2
        img = rng.integers(0, 256, (40, 56, 3), dtype=np.uint8)
        _, enc = cv2.imencode(".jpg", img, [cv2.IMWRITE_JPEG_QUALITY, 90])
        ours = native.decode_image(enc.tobytes())
        theirs = cv2.imdecode(enc, cv2.IMREAD_COLOR)
        # same underlying libjpeg -> identical; allow a whisker anyway
        assert np.abs(ours.astype(int) - theirs.astype(int)).max() <= 1

    def test_ppm(self, rng):
        img = rng.integers(0, 256, (9, 11, 3), dtype=np.uint8)
        raw = b"P6\n# comment\n11 9\n255\n" + img[:, :, ::-1].tobytes()
        assert np.array_equal(native.decode_image(raw), img)

    def test_grayscale_jpeg_upconverts(self, rng):
        import cv2
        gray = rng.integers(0, 256, (20, 20), dtype=np.uint8)
        _, enc = cv2.imencode(".jpg", gray)
        out = native.decode_image(enc.tobytes())
        assert out.shape == (20, 20, 3)

    def test_garbage_returns_none(self):
        assert native.decode_image(b"not an image at all....") is None
        assert native.decode_image(b"") is None

    def test_truncated_png_returns_none(self, rng):
        import cv2
        img = rng.integers(0, 256, (30, 30, 3), dtype=np.uint8)
        _, enc = cv2.imencode(".png", img)
        assert native.decode_image(enc.tobytes()[:40]) is None


class TestResize:
    def test_matches_cv2_linear(self, rng):
        import cv2
        img = rng.integers(0, 256, (37, 53, 3), dtype=np.uint8)
        ours = native.resize_bilinear(img, 24, 31)
        theirs = cv2.resize(img, (31, 24), interpolation=cv2.INTER_LINEAR)
        diff = np.abs(ours.astype(int) - theirs.astype(int))
        assert diff.max() <= 1  # rounding-mode differences only

    def test_identity(self, rng):
        img = rng.integers(0, 256, (16, 16, 3), dtype=np.uint8)
        assert np.array_equal(native.resize_bilinear(img, 16, 16), img)

    def test_upscale_shape(self, rng):
        img = rng.integers(0, 256, (8, 8, 1), dtype=np.uint8)
        assert native.resize_bilinear(img, 32, 24).shape == (32, 24, 1)


@pytest.fixture(scope="module")
def image_dir(tmp_path_factory, rng):
    import cv2
    d = tmp_path_factory.mktemp("imgs")
    for i in range(10):
        img = rng.integers(0, 256, (20 + i, 30 - i, 3), dtype=np.uint8)
        cv2.imwrite(str(d / f"img{i:02d}.png"), img)
    (d / "broken.png").write_bytes(b"\x89PNGgarbage")
    return str(d)


class TestBatchLoader:
    def test_order_counts_and_mask(self, image_dir):
        paths = list_images(image_dir)
        assert len(paths) == 11  # 10 good + 1 broken
        seen, ok_total = 0, 0
        for buf, ok, count in image_batches(paths, batch=4, height=16,
                                            width=16, threads=3):
            assert buf.shape == (4, 16, 16, 3)
            # padding slots beyond count are not-ok and zero
            assert not ok[count:].any()
            assert (buf[count:] == 0).all()
            seen += count
            ok_total += int(ok[:count].sum())
        assert seen == 11
        assert ok_total == 10

    def test_failed_decode_is_zero_filled(self, image_dir):
        paths = [os.path.join(image_dir, "broken.png")]
        [(buf, ok, count)] = list(image_batches(paths, 2, 8, 8))
        assert count == 1 and not ok[0]
        assert (buf[0] == 0).all()

    def test_content_matches_direct_decode(self, image_dir):
        import cv2
        paths = [p for p in list_images(image_dir)
                 if "broken" not in p][:3]
        batches = list(image_batches(paths, batch=3, height=12, width=12,
                                     threads=2))
        buf, ok, count = batches[0]
        for i, p in enumerate(paths):
            img = cv2.imread(p, cv2.IMREAD_COLOR)
            want = native.resize_bilinear(img, 12, 12)
            assert np.array_equal(buf[i], want)

    def test_empty_path_list(self):
        assert list(image_batches([], batch=4, height=8, width=8)) == []

    def test_non_native_format_falls_back_to_cv2(self, tmp_path, rng):
        # tiff is outside the C++ decoder's set; the native loader path must
        # patch it in via cv2 so results never depend on the toolchain
        import cv2
        img = rng.integers(0, 256, (14, 14, 3), dtype=np.uint8)
        p = str(tmp_path / "pic.tif")
        cv2.imwrite(p, img)
        [(buf, ok, count)] = list(image_batches([p], 2, 14, 14))
        assert count == 1 and ok[0]
        assert np.array_equal(buf[0], img)

    def test_device_feed_batches_do_not_alias_staging(self, image_dir):
        # device arrays must stay valid after the staging buffer is reused
        paths = [p for p in list_images(image_dir) if "broken" not in p]
        got = [np.asarray(dev[:count])
               for dev, ok, count in device_image_batches(
                   paths, batch=2, height=10, width=10)]
        flat = np.concatenate(got)
        want = []
        for buf, ok, count in image_batches(paths, 2, 10, 10):
            want.append(buf[:count].copy())
        assert np.array_equal(flat, np.concatenate(want))

    def test_device_feed(self, image_dir):
        import jax.numpy as jnp
        paths = list_images(image_dir)
        total = 0
        for dev, ok, count in device_image_batches(
                paths, batch=4, height=16, width=16,
                transform=lambda b: b.astype(np.float32) / 255.0):
            assert isinstance(dev, jnp.ndarray)
            assert dev.dtype == jnp.float32
            assert float(dev.max()) <= 1.0
            total += count
        assert total == len(paths)


class TestCsv:
    def test_parity_with_numpy(self, tmp_path, rng):
        mat = rng.normal(size=(200, 7)).astype(np.float32)
        p = tmp_path / "data.csv"
        np.savetxt(p, mat, delimiter=",", fmt="%.6e")
        out = read_csv_matrix(str(p))
        assert out.shape == (200, 7)
        np.testing.assert_allclose(out, mat, rtol=1e-5, atol=1e-30)

    def test_header_sniffing_and_names(self, tmp_path):
        p = tmp_path / "d.csv"
        p.write_text("alpha,beta\n1,2\n3,4\n")
        df = read_csv(str(p))
        assert df.columns == ["alpha", "beta"]
        np.testing.assert_array_equal(df.col("alpha"), [1.0, 3.0])

    def test_no_header(self, tmp_path):
        p = tmp_path / "d.csv"
        p.write_text("1,2\n3,4\n")
        df = read_csv(str(p))
        assert df.columns == ["c0", "c1"]
        assert len(df) == 2

    def test_missing_and_bad_fields_are_nan(self, tmp_path):
        p = tmp_path / "d.csv"
        p.write_text("1,,x\n4,5,6\n")
        m = read_csv_matrix(str(p))
        assert np.isnan(m[0, 1]) and np.isnan(m[0, 2])
        assert m[1, 2] == 6.0

    def test_scientific_and_negative(self, tmp_path):
        p = tmp_path / "d.csv"
        p.write_text("-1.5e-3,2.25E2\n")
        m = read_csv_matrix(str(p))
        np.testing.assert_allclose(m[0], [-0.0015, 225.0], rtol=1e-6)

    def test_crlf_and_blank_lines(self, tmp_path):
        p = tmp_path / "d.csv"
        p.write_bytes(b"1,2\r\n\r\n3,4\r\n")
        m = read_csv_matrix(str(p))
        assert m.shape == (2, 2)
        np.testing.assert_array_equal(m, [[1, 2], [3, 4]])

    def test_tab_delimited(self, tmp_path):
        p = tmp_path / "d.tsv"
        p.write_text("1\t2\n3\t4\n")
        m = read_csv_matrix(str(p), delim="\t")
        np.testing.assert_array_equal(m, [[1, 2], [3, 4]])

    def test_single_column_file(self, tmp_path):
        p = tmp_path / "one.csv"
        p.write_text("1\n2\n3\n")
        m = read_csv_matrix(str(p))
        assert m.shape == (3, 1)

    def test_single_column_fallback_path(self, tmp_path, monkeypatch):
        # numpy fallback (no native lib) must not transpose (n,) -> (1,n)
        from mmlspark_tpu.io import csv as csvmod
        monkeypatch.setattr(csvmod.native, "read_csv",
                            lambda *a, **k: None)
        p = tmp_path / "one.csv"
        p.write_text("v\n1\n2\n3\n")
        df = read_csv(str(p))
        assert df.columns == ["v"] and len(df) == 3

    def test_large_parallel_chunking(self, tmp_path, rng):
        # enough rows that every parser thread gets a chunk
        mat = rng.integers(0, 1000, size=(5000, 3)).astype(np.float32)
        p = tmp_path / "big.csv"
        np.savetxt(p, mat, delimiter=",", fmt="%.1f")
        out = read_csv_matrix(str(p), threads=4)
        np.testing.assert_allclose(out, mat)


class TestLoaderOverlap:
    """The loader's REASON to exist is overlap: C++ decode threads fill the
    prefetch queue while the consumer computes (on TPU, while the chip
    runs). Throughput depends on the host->device link, so this asserts
    the overlap itself, hardware-free: a
    consumer that sleeps s per batch (device compute uses no host CPU) must
    finish in well under decode_time + sleep_time."""

    def _mk_corpus(self, tmp_path, n=48, hw=384):
        import cv2
        rng = np.random.default_rng(0)
        paths = []
        for i in range(n):
            img = rng.integers(0, 255, (hw, hw, 3), dtype=np.uint8)
            p = str(tmp_path / f"img_{i:03d}.jpg")
            assert cv2.imwrite(p, img)
            paths.append(p)
        return paths

    def test_decode_overlaps_consumer_compute(self, tmp_path):
        import time

        from mmlspark_tpu.io.loader import image_batches

        paths = self._mk_corpus(tmp_path)
        batch = 8
        n_batches = len(paths) // batch

        def run(sleep_per_batch: float) -> float:
            t0 = time.perf_counter()
            seen = 0
            for buf, ok, count in image_batches(paths, batch, 128, 128,
                                                threads=2, prefetch=4):
                assert ok.all()
                seen += count
                if sleep_per_batch:
                    time.sleep(sleep_per_batch)
            assert seen == len(paths)
            return time.perf_counter() - t0

        run(0.0)                      # warm the page cache / lib load
        t_decode = run(0.0)           # pure decode wall-clock
        s = max(t_decode / n_batches, 0.02)   # compute ~= decode per batch
        serial_sum = t_decode + s * n_batches
        t_overlap = run(s)
        # perfect overlap ~= max(decode, sleep) + one batch; zero overlap
        # = serial_sum. The 0.8 bound means at least ~20% of the serial
        # time was hidden — impossible unless decode ran DURING the sleeps.
        assert t_overlap < 0.8 * serial_sum, (
            f"no decode/compute overlap: overlapped {t_overlap:.3f}s vs "
            f"serial {serial_sum:.3f}s (decode {t_decode:.3f}s, "
            f"sleep {s * n_batches:.3f}s)")
