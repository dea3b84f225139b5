"""Environment / process / file utilities (reference: src/core/env —
EnvironmentUtils.scala:41-50 counts GPUs by shelling out to ``nvidia-smi -L``;
FileUtilities, StreamUtilities.using, ProcessUtils; NativeLoader lives in
mmlspark_tpu.native)."""

from __future__ import annotations

import contextlib
import os
import subprocess
from typing import Iterator, Optional, Sequence


def telemetry_enabled() -> bool:
    """The MMLSPARK_TPU_TELEMETRY=1 global switch: when truthy, the
    telemetry package enables its process-global metrics registry and span
    tracer at import (mmlspark_tpu.telemetry). Default off — a disabled
    registry costs one attribute lookup per call site."""
    return os.environ.get("MMLSPARK_TPU_TELEMETRY", "").strip().lower() \
        in ("1", "true", "yes", "on")


def telemetry_trace_path() -> Optional[str]:
    """MMLSPARK_TPU_TRACE=/path/file.jsonl: export the span buffer as
    Chrome-trace JSON-lines at interpreter exit (telemetry must also be
    enabled for spans to record). A literal ``{pid}`` in the path is
    replaced with the process id — spawned fleet workers inherit the env,
    and per-process files are what ``telemetry.merge_traces`` joins."""
    return os.environ.get("MMLSPARK_TPU_TRACE") or None


def flight_path() -> Optional[str]:
    """MMLSPARK_TPU_FLIGHT: arm the crash flight recorder
    (telemetry.flight) at import. ``=1`` (or any truthy switch) dumps
    bundles to the working directory; ``=/path/dir`` dumps there.
    Returns None (disarmed), "" (armed, default dir) or the directory."""
    v = os.environ.get("MMLSPARK_TPU_FLIGHT", "").strip()
    if not v or v.lower() in ("0", "false", "no", "off"):
        return None
    if v.lower() in ("1", "true", "yes", "on"):
        return ""
    return v


def timeseries_interval() -> Optional[float]:
    """MMLSPARK_TPU_TIMESERIES: arm the time-series sampler
    (telemetry.timeseries) at import. ``=1``/``true`` samples every
    second; a float value (``=0.25``) is the tick interval in seconds.
    Returns None (disarmed) or the interval. Arming also enables
    telemetry."""
    v = os.environ.get("MMLSPARK_TPU_TIMESERIES", "").strip()
    if not v or v.lower() in ("0", "false", "no", "off"):
        return None
    if v.lower() in ("1", "true", "yes", "on"):
        return 1.0
    try:
        iv = float(v)
    except ValueError:
        return 1.0
    return iv if iv > 0 else None


def fault_spec() -> Optional[str]:
    """MMLSPARK_TPU_FAULTS="site:kind:rate[:arg];...": arm the seeded
    fault-injection registry (mmlspark_tpu.resilience.faults) at import.
    Default unset — injection sites are a module-bool check, nothing
    more."""
    return os.environ.get("MMLSPARK_TPU_FAULTS") or None


def sanitize_mode() -> Optional[str]:
    """MMLSPARK_TPU_SANITIZE=donation: arm the donation sanitizer
    (mmlspark_tpu.analysis.sanitize) — donating dispatches poison their
    host-aliased donated inputs after dispatch and trap re-reads.
    MMLSPARK_TPU_SANITIZE=races: arm the race sanitizer
    (mmlspark_tpu.analysis.sanitize_races) — instrumented classes
    record (thread, held-lock set) per shared-field access and trap
    conflicting unlocked cross-thread pairs. Test/chaos-tier knob;
    unset (the default) costs nothing."""
    v = os.environ.get("MMLSPARK_TPU_SANITIZE", "").strip().lower()
    return v or None


def fault_seed() -> int:
    """MMLSPARK_TPU_FAULTS_SEED=<int>: the base seed every fault site's
    RNG derives from (seed ^ crc32(site)) — reruns replay identically."""
    try:
        return int(os.environ.get("MMLSPARK_TPU_FAULTS_SEED", "0"))
    except ValueError:
        return 0


def on_tpu() -> bool:
    """True on the tpu backend, False on the cpu backend the tests run on.
    Any other backend raises: every platform-dependent choice (compiled
    vs interpreted Pallas, flash vs blockwise attention, the GBDT
    histogram and predict kernels) is made for one of those two, and a
    third must not be quietly served by the CPU reference paths."""
    import jax
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"unsupported JAX backend {backend!r}: mmlspark_tpu runs on "
            f"'tpu' (and on 'cpu' for its tests)")
    return backend == "tpu"


def accelerator_count() -> int:
    """Attached accelerator chips (the GPUCount analog — no nvidia-smi
    subprocess: the JAX runtime already knows)."""
    import jax
    return sum(1 for d in jax.devices() if d.platform != "cpu")


def device_summary() -> dict:
    """Platform/topology snapshot for logs and config records."""
    import jax
    devs = jax.devices()
    return {
        "backend": jax.default_backend(),
        "device_count": len(devs),
        "local_device_count": jax.local_device_count(),
        "process_count": jax.process_count(),
        "device_kinds": sorted({d.device_kind for d in devs}),
    }


@contextlib.contextmanager
def using(*resources) -> Iterator[tuple]:
    """Close every resource on exit, first-error wins (reference
    StreamUtilities.using): an exception from the with-body outranks any
    close()-time error; with a clean body the first close() error raises."""
    try:
        yield resources
    except BaseException:
        for r in resources:
            try:
                r.close()
            except Exception:  # body error is the first error; keep it
                pass
        raise
    else:
        err = None
        for r in resources:
            try:
                r.close()
            except Exception as e:  # noqa: BLE001 - collect, raise once
                err = err or e
        if err is not None:
            raise err


def run_process(cmd: Sequence[str], timeout: float = 600.0,
                check: bool = True) -> tuple[int, str, str]:
    """Run a subprocess, capture (returncode, stdout, stderr) (reference
    ProcessUtils; the reference shells out for ssh/scp/mpirun — here process
    launch is only for tooling, never the compute path)."""
    r = subprocess.run(list(cmd), capture_output=True, text=True,
                       timeout=timeout)
    if check and r.returncode != 0:
        raise RuntimeError(f"{cmd[0]} failed ({r.returncode}): "
                           f"{r.stderr[-500:]}")
    return r.returncode, r.stdout, r.stderr
