"""What the package does before any work runs: where the compile cache is
placed, that importing holds no device, which backend choices are legal, and
that chip_smoke.py refuses a machine without a TPU. Each check needs a fresh
interpreter, so they run as subprocesses."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# records every jax.config.update the package makes while importing
_PROBE = r"""
import json, os, sys
import jax
calls = []
_update = jax.config.update
def spy(name, value):
    calls.append(name)
    return _update(name, value)
jax.config.update = spy
import mmlspark_tpu
import mmlspark_tpu.io.http.worker
import mmlspark_tpu.io.serving
from jax._src import xla_bridge
print(json.dumps({
    "dir": jax.config.jax_compilation_cache_dir,
    "updates": calls,
    "backends_initialized": xla_bridge.backends_are_initialized()}))
"""


def _probe(cwd, cache_env=None):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    if cache_env is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_env
    r = subprocess.run([sys.executable, "-c", _PROBE], capture_output=True,
                       text=True, env=env, cwd=cwd, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_cache_dir_from_environment_is_left_alone(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: JAX reads it, the package sets no
    directory of its own."""
    want = str(tmp_path / "placed_from_outside")
    got = _probe(str(tmp_path), cache_env=want)
    assert got["dir"] == want
    assert "jax_compilation_cache_dir" not in got["updates"]


def test_cache_dir_default_is_fixed_inside_the_checkout(tmp_path):
    """Unset: one directory derived from the package's location — the same
    from any working directory (the path is part of the cache key)."""
    elsewhere = tmp_path / "elsewhere"
    elsewhere.mkdir()
    a = _probe(REPO)
    b = _probe(str(elsewhere))
    assert a["dir"] == b["dir"] == os.path.join(REPO, ".jax_cache")
    assert a["updates"].count("jax_compilation_cache_dir") == 1


def test_import_initializes_no_backend(tmp_path):
    """One process per chip: a parent that only imports the package (a fleet
    supervisor, a tuner that spawns trial processes) must not take the
    device from the children that need it."""
    assert _probe(str(tmp_path))["backends_initialized"] is False


def test_unknown_backend_is_an_error(monkeypatch):
    """The cpu paths are taken because the backend IS cpu, not because it
    is not tpu."""
    import jax
    from mmlspark_tpu.core import env
    from mmlspark_tpu.ops import pallas_kernels
    assert env.on_tpu() is False and pallas_kernels._interpret() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert env.on_tpu() is True and pallas_kernels._interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="unsupported JAX backend"):
        env.on_tpu()
    with pytest.raises(RuntimeError, match="unsupported JAX backend"):
        pallas_kernels._interpret()


def test_unknown_accelerator_kind_has_no_invented_peak(monkeypatch):
    import jax
    from mmlspark_tpu.telemetry import profiler

    class _Dev:
        platform = "tpu"
        device_kind = "TPU v99"

    assert profiler.peak_flops() is None          # cpu: no peak claimed
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev()])
    with pytest.raises(RuntimeError, match="TPU v99"):
        profiler.peak_flops()
    _Dev.device_kind = "TPU v5 lite"              # what libtpu 0.0.34 says
    monkeypatch.setattr(jax, "device_count", lambda: 4)
    assert profiler.peak_flops() == 4 * 197e12


def test_tpu_without_memory_limit_is_an_error(monkeypatch):
    import jax
    from mmlspark_tpu.models import trainer

    class _Dev:
        device_kind = "TPU v5 lite"

        def memory_stats(self):
            return None

    monkeypatch.setattr(trainer, "_device_data_cap_cache", None)
    monkeypatch.setattr(jax, "local_devices", lambda: [_Dev()])
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="bytes_limit"):
        trainer._device_data_cap()


def test_bundle_for_another_backend_is_refused(tmp_path):
    import jax
    import numpy as np
    from mmlspark_tpu.io.serving import (BucketPolicy, FusedServingStep,
                                         load_bundle, save_bundle)
    from mmlspark_tpu.models.modules import build_model
    cfg = {"type": "mlp", "hidden": [4], "num_classes": 2}
    params = build_model(cfg).init(jax.random.PRNGKey(0),
                                   np.zeros((1, 3), np.float32))
    step = FusedServingStep(cfg, params, row_shape=(3,), in_dtype=np.float32,
                            policy=BucketPolicy(max_batch=8, min_bucket=8))
    save_bundle(str(tmp_path), step, extra_meta={"backend": "tpu"})
    with pytest.raises(RuntimeError, match="built for backend 'tpu'"):
        load_bundle(str(tmp_path))


def test_chip_smoke_refuses_a_machine_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, env=env,
                       cwd=str(tmp_path), timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "not 'tpu'" in r.stderr


def test_flash_runs_per_batch_shard_on_a_multi_device_mesh():
    """GSPMD cannot partition a Mosaic call, so a transformer built for a
    multi-device mesh runs its flash kernel per batch shard (found on the
    four-chip host, PR 21). Here: the 8-device CPU mesh, the kernel pinned
    (``attn_impl='flash'``) and therefore in interpret mode."""
    import jax
    import numpy as np
    from mmlspark_tpu import DataFrame
    from mmlspark_tpu.models import TpuLearner, build_model
    from mmlspark_tpu.parallel import mesh as meshlib
    cfg = {"type": "transformer", "vocab_size": 50, "d_model": 16,
           "heads": 2, "layers": 1, "num_classes": 3, "max_len": 16,
           "causal": True, "attn_impl": "flash"}
    mesh = meshlib.create_mesh()
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 50, size=(32, 16)).astype(np.int32)
    meshed, plain = build_model(cfg, mesh=mesh), build_model(cfg)
    # the 2-row eager init is a one-device program: no shard_map, same tree
    params = meshed.init(jax.random.PRNGKey(0), tokens[:2])
    placed = (meshlib.put_replicated(params, mesh),
              meshlib.shard_batch(tokens[:16], mesh))
    fn = jax.jit(meshed.apply)
    assert "sdy.manual_computation" in fn.lower(*placed).as_text()
    assert "sdy.manual_computation" not in jax.jit(plain.apply).lower(
        params, tokens[:16]).as_text()
    np.testing.assert_allclose(        # the module computes in bfloat16
        np.asarray(fn(*placed)),
        np.asarray(jax.jit(plain.apply)(params, tokens[:16])), atol=2e-2)
    # and through the entry points, which hand the module their mesh
    df = DataFrame({"features": tokens, "label": tokens[:, 0] % 3})
    model = TpuLearner().setModelConfig(cfg).setBatchSize(16).setEpochs(1) \
        .fit(df)
    assert np.isfinite(model._final_loss)
    scores = np.stack(list(model.transform(df).col("scores")))
    assert scores.shape == (32, 3) and np.isfinite(scores).all()
