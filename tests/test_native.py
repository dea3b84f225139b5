"""Native runtime (C++ libmmltpu) tests: decode parity against cv2, the
threaded prefetch loader's ordering/masking contract, CSV parser parity
against numpy, and the device-feed pipeline end to end.

The reference trusts its native layer via prebuilt jars (NativeLoader.java);
ours is in-repo, so parity with the battle-tested decoders is the test."""

import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from mmlspark_tpu import native
from mmlspark_tpu.io import (device_image_batches, image_batches,
                             list_images, read_csv, read_csv_matrix)

# skipped only where the library is absent by design (disabled, or no
# toolchain); a build or load that failed runs the tests, and they fail
pytestmark = pytest.mark.skipif(
    native.unavailable_quietly(), reason=str(native.unavailable_reason()))


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
    runs). Asserted from what the loader counts, not from a clock: a
    consumer that stays OUT of next() sees the queue of decoded batches
    fill to the prefetch window, which cannot happen unless decode runs
    while the consumer does something else."""

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

    @pytest.mark.parametrize("threads,prefetch", [(2, 4), (4, 2), (1, 1)])
    def test_decode_overlaps_consumer_compute(self, tmp_path, threads,
                                              prefetch):
        paths = self._mk_corpus(tmp_path)
        batch = 8
        n_batches = len(paths) // batch
        # the window covers the thread pool, or threads past it never run
        window = max(prefetch, threads)
        seen = 0
        with native.BatchLoader(paths, batch, 128, 128, threads=threads,
                                prefetch=prefetch) as ld:
            batches = iter(ld)
            for bi in range(n_batches):
                # the consumer "computes": the window fills behind its back,
                # and never past its bound (memory stays O(prefetch))
                want = min(window, n_batches - bi)
                deadline = time.monotonic() + 60
                while ld.ready() < want and time.monotonic() < deadline:
                    time.sleep(0.005)
                assert ld.ready() == want, (
                    f"batch {bi}: {ld.ready()} decoded batches waiting "
                    f"while the consumer was away, expected {want}")
                _, ok, count = next(batches)
                assert ok.all()
                seen += count
        assert seen == len(paths)


# ------------------------------------------------- build: one writer, loud

_NATIVE_DIR = os.path.dirname(native.__file__)
_REPO = os.path.dirname(os.path.dirname(_NATIVE_DIR))

# loads a COPY of mmlspark_tpu/native (argv[1]) beside the real package,
# says "ready", waits for the starting gun on stdin, then reports
_BUILD_CHILD = """
import importlib.util, json, os, sys
import mmlspark_tpu
spec = importlib.util.spec_from_file_location(
    "mmlspark_tpu._native_copy", os.path.join(sys.argv[1], "__init__.py"))
mod = importlib.util.module_from_spec(spec)
sys.modules[spec.name] = mod
spec.loader.exec_module(mod)
print("ready", flush=True)
sys.stdin.readline()
if len(sys.argv) > 2:       # N threads of this process ask at once
    import threading
    libs = []
    ts = [threading.Thread(target=lambda: libs.append(mod.get_lib()))
          for _ in range(int(sys.argv[2]))]
    [t.start() for t in ts]
    [t.join() for t in ts]
    assert all(lib is not None for lib in libs), libs
ok = mod.available()
st = os.stat(mod._SO) if ok else None
print(json.dumps({"available": ok, "reason": mod.unavailable_reason(),
                  "quiet": mod.unavailable_quietly(),
                  "so": [st.st_ino, st.st_mtime_ns] if st else None}),
      flush=True)
"""


def _child_env(**extra):
    env = {k: v for k, v in os.environ.items()
           if k != "MMLSPARK_TPU_NO_NATIVE"}
    # the Makefile's own variable: these builds are for their exit code
    env.update(PYTHONPATH=_REPO, JAX_PLATFORMS="cpu",
               CXXFLAGS="-O0 -fPIC -std=c++17 -pthread", **extra)
    return env


def _build_at_once(copy: str, n: int, *args, **env) -> list[dict]:
    """n processes that reach get_lib() of the copy together."""
    procs = [subprocess.Popen([sys.executable, "-c", _BUILD_CHILD, copy,
                               *args],
                              stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                              stderr=subprocess.DEVNULL, text=True,
                              env=_child_env(**env))
             for _ in range(n)]
    try:
        for p in procs:
            assert p.stdout.readline().strip() == "ready"
        for p in procs:
            p.stdin.write("\n")
            p.stdin.flush()
        return [json.loads(p.communicate(timeout=300)[0]) for p in procs]
    finally:
        for p in procs:
            p.kill()


@pytest.fixture
def native_copy(tmp_path):
    dst = str(tmp_path / "native")
    shutil.copytree(_NATIVE_DIR, dst,
                    ignore=shutil.ignore_patterns("_build", "__pycache__"))
    return dst


class TestBuild:
    @pytest.mark.parametrize("start", ["cold_tree", "stale_source"])
    def test_six_processes_one_link(self, native_copy, start):
        """Six processes start on a tree with no (or an outdated) library:
        one builds under the lock, all six load, and they load one file."""
        build = os.path.join(native_copy, "_build")
        so = os.path.join(build, "libmmltpu.so")
        if start == "stale_source":
            [first] = _build_at_once(native_copy, 1)
            assert first["available"], first["reason"]
            now = time.time()
            for f in os.listdir(build):
                os.utime(os.path.join(build, f), (now - 100, now - 100))
            os.utime(os.path.join(native_copy, "csrc", "resize.cc"),
                     (now - 50, now - 50))
            before = os.stat(so).st_mtime_ns
        else:
            assert not os.path.exists(so)
            before = 0
        got = _build_at_once(native_copy, 6)
        assert [g["available"] for g in got] == [True] * 6, \
            [g["reason"] for g in got]
        st = os.stat(so)
        assert st.st_mtime_ns > before
        # a second link would have left an earlier loader another file
        assert {tuple(g["so"]) for g in got} == {(st.st_ino, st.st_mtime_ns)}

    def test_failed_build_is_loud(self, native_copy):
        """A toolchain is there and the build fails: None for callers as
        ever, but a fault with its cause, and nothing a test may skip on."""
        with open(os.path.join(native_copy, "csrc", "resize.cc"), "w") as f:
            f.write("this is not C++\n")
        [got] = _build_at_once(native_copy, 1)
        assert got["available"] is False
        assert got["reason"].startswith("build failed"), got["reason"]
        assert "resize.cc" in got["reason"]
        assert got["quiet"] is False
        assert not os.path.exists(
            os.path.join(native_copy, "_build", "libmmltpu.so"))

    def test_unloadable_library_is_loud(self, native_copy):
        """A library newer than its sources that will not load, with a
        toolchain there: the other fault, named with the file at fault."""
        so = os.path.join(native_copy, "_build", "libmmltpu.so")
        os.makedirs(os.path.dirname(so))
        with open(so, "wb") as f:
            f.write(b"not an ELF file")
        [got] = _build_at_once(native_copy, 1)
        assert got["available"] is False
        assert got["reason"].startswith("load failed"), got["reason"]
        assert so in got["reason"]
        assert got["quiet"] is False

    def test_threads_of_one_process_wait_for_the_builder(self, native_copy):
        """Eight threads ask a cold process at once: none is told "no
        library" while the first is still building it."""
        [got] = _build_at_once(native_copy, 1, "8")
        assert got["available"], got["reason"]

    def test_no_toolchain_is_quiet(self, native_copy, tmp_path):
        """Nothing to build with and nothing built: the other quiet cause."""
        [got] = _build_at_once(native_copy, 1, PATH=str(tmp_path))
        assert got["available"] is False
        assert got["reason"] == "no make or C++ compiler on the path"
        assert got["quiet"] is True


_FALLBACK_CHILD = """
import sys
import numpy as np
from mmlspark_tpu import native
from mmlspark_tpu.io import read_csv_matrix
from mmlspark_tpu.io.image import decode_image
from mmlspark_tpu.core.schema import image_to_array
from mmlspark_tpu.models.gbdt import engine
d = sys.argv[1]
inp = np.load(d + "/in.npz")
np.savez(d + "/out.npz",
         reason=native.unavailable_reason(),
         quiet=native.unavailable_quietly(),
         read_csv_matrix=read_csv_matrix(d + "/m.csv"),
         decode_image=image_to_array(
             decode_image("a.png", inp["png"].tobytes())),
         bin_data_native=engine.bin_data(inp["x"], inp["edges"], None, 256))
"""


@pytest.fixture(scope="module")
def fallback_answers(tmp_path_factory):
    """Inputs, and what a process under MMLSPARK_TPU_NO_NATIVE=1 makes of
    them through the three call sites that fall back."""
    import cv2
    d = tmp_path_factory.mktemp("fallbacks")
    rng = np.random.default_rng(7)
    mat = rng.normal(size=(300, 5)).astype(np.float32)
    mat[::17, 2] = np.nan
    np.savetxt(d / "m.csv", mat, delimiter=",", header="a,b,c,d,e",
               comments="")
    _, png = cv2.imencode(
        ".png", rng.integers(0, 256, (37, 53, 3), dtype=np.uint8))
    x = rng.normal(size=(125_000, 8)).astype(np.float32) * 3   # 1M cells
    edges = np.sort(rng.normal(size=(8, 254)).astype(np.float32) * 3, axis=1)
    x[::13, 1] = np.nan
    x[::5, 2] = edges[2, 100]
    np.savez(d / "in.npz", png=png, x=x, edges=edges)
    subprocess.run([sys.executable, "-c", _FALLBACK_CHILD, str(d)],
                   check=True, timeout=300,
                   env=_child_env(MMLSPARK_TPU_NO_NATIVE="1"))
    natives = {
        "read_csv_matrix": read_csv_matrix(str(d / "m.csv")),
        "decode_image": native.decode_image(png.tobytes()),
        "bin_data_native": native.bin_data_native(x, edges, None, 256)}
    return np.load(d / "out.npz"), natives


@pytest.mark.parametrize("entry", ["read_csv_matrix", "decode_image",
                                   "bin_data_native"])
def test_disabled_is_quiet_and_fallback_gives_the_native_answer(
        fallback_answers, entry):
    out, natives = fallback_answers
    assert str(out["reason"]) == "disabled by MMLSPARK_TPU_NO_NATIVE"
    assert bool(out["quiet"])
    ours = natives[entry]
    assert ours is not None and ours.dtype == out[entry].dtype
    np.testing.assert_array_equal(ours, out[entry])
