"""Time the three flash attention kernels on the chip, a kernel at a time.

    python3 tools/sweep_flash_blocks.py                  # the blocks as shipped
    python3 tools/sweep_flash_blocks.py --shape cell \
        --fwd 512x1024x256,512x2048x512 --dq 512x512x256 --dkv 512x512x256

Each line is one kernel of one traced program: milliseconds a call from the
device trace's `XLA Ops` line (read with the benchmark's own reduction and
told apart by the benchmark's own patterns) and the share of
`benchmark/flops/attention.py`'s least time, so a reading here and
`flash_fwd_roofline` / `flash_bwd_roofline` of the cgpt cell are the same
quantity (the backward's share there is dq and dkv together, as `bwd` here).
Beside them `program_ms`, the whole traced program's busy time a call
(`fwd_program_ms` of the forward-only program, `grad_program_ms` of the
gradient program, which runs all three kernels): less the kernels it is what
`flash_attention` puts around its calls. The programs take q, k, v as the
models hand them over, (B, T, H*D) arrays viewed as (B, T, H, D), and return
results of that form, so no re-tiling of an entry parameter is counted.
A shape with two widths (the latent layers': q and k at `Dqk`, v at `Dv`) is
timed as built and, on the next line (`"call": "padded"`), as the one-width
call it replaces, v zero-padded to `Dqk` before the call and the result cut
back after it; its shares are of the work at the widths built and, as
`*_asked_share_pct`, at the widths the model asks for (192-wide q and k).
`--fwd` / `--dq` / `--dkv` take `block_q x block_k x sub` candidates and put
them in `_default_blocks`' place for that kernel, the others as shipped.
No cell runs this; it fails where JAX finds no TPU.
"""

import argparse
import glob
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import trace_reduce
from benchmark.flops import attention
from benchmark.layer_metrics import flash_bwd_roofline, flash_fwd_roofline
from mmlspark_tpu.ops import pallas_kernels as pk

ASKED = (192, 128)              # a latent head's q, k and v before padding
SHAPES = {                      # B, T, H, D or (Dqk, Dv), causal
    "cell": (8, 2048, 16, 128, True),       # cgpt1p3b_train_stream's
    "latent": (8, 2048, 8, (256, 128), True),       # kimilinear's
    "latent4k": (8, 4096, 8, (256, 128), True),     # joyai's
    "latent192": (8, 2048, 8, ASKED, True),   # q, k not padded: not shipped
    "latent4k192": (8, 4096, 8, ASKED, True),
    "long": (8, 4096, 4, 128, True),        # chip_smoke stage D's family
    "long_nc": (8, 4096, 4, 128, False),
    "d64": (8, 4096, 8, 64, True),
}
KERNELS = {"flash_fwd": flash_fwd_roofline.KERNEL,
           "flash_dq": flash_bwd_roofline.KERNEL_DQ,
           "flash_dkv": flash_bwd_roofline.KERNEL_DKV}
CALLS = 6


def kernel_ms(fn, args):
    """{kernel: milliseconds a call} of one traced run of `fn`, and the whole
    program's busy milliseconds a call under "program"."""
    jax.block_until_ready(fn(*args))                  # compile, warm
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        for _ in range(CALLS):
            jax.block_until_ready(fn(*args))
        jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(d, "plugins/profile/*/*.xplane.pb"))
        trace = trace_reduce.summarise(trace_reduce.load_events(path))
    out = {"program": sum(trace["step_busy_ms"]) / trace["steps"]}
    for name, pattern in KERNELS.items():
        seconds, calls = trace_reduce.kernel_time(trace, pattern)
        if calls:
            out[name] = 1e3 * seconds / calls
    return out


def widths(D):
    return D if isinstance(D, tuple) else (D, D)


def measure(shape, override=None, padded=False):
    """{kernel: blocks used}, {kernel: ms a call}: the forward from a
    forward-only program, dq and dkv from a gradient program; under
    `override` ({kernel: (block_q, block_k, sub)}) only that kernel's.
    `padded`: v enters the call zero-padded to q's width."""
    B, T, H, D, causal = SHAPES[shape]
    Dqk, Dv = widths(D)
    D = max(Dqk, Dv)
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(B, T, H * w)), jnp.bfloat16)
               for w in (Dqk, Dqk, Dv))

    def attend(q, k, v):
        q, k, v = (a.reshape(B, T, H, -1) for a in (q, k, v))
        if padded:
            v = jnp.pad(v, ((0, 0),) * 3 + ((0, Dqk - Dv),))
        out = pk.flash_attention(q, k, v, causal)[..., :Dv]
        return out.reshape(B, T, H * Dv)
    shipped = pk._default_blocks

    def blocks(D, causal, Tq, Tk, block_q=None, block_k=None,
               kernel="flash_fwd"):
        if override and kernel in override:
            return override[kernel]
        return shipped(D, causal, Tq, Tk, block_q, block_k, kernel)

    pk._default_blocks = blocks
    try:
        used = {n: blocks(D, causal, T, T, kernel=n) for n in KERNELS}
        ms = {}
        if not override or "flash_fwd" in override:
            read = kernel_ms(jax.jit(attend), (q, k, v))
            ms["fwd_program"] = read.pop("program")
            ms.update(read)
        if not override or "flash_fwd" not in override:
            grad = jax.jit(jax.grad(
                lambda q, k, v: jnp.sum(attend(q, k, v).astype(jnp.float32)),
                argnums=(0, 1, 2)))
            read = kernel_ms(grad, (q, k, v))
            ms["grad_program"] = read.pop("program")
            ms.update({n: t for n, t in read.items() if n != "flash_fwd"})
    finally:
        pk._default_blocks = shipped
    return used, ms


def least_ms(B, T, H, Dqk, Dv, causal, backward, peaks):
    """The least milliseconds of a call at two widths, product by product
    (`benchmark/flops/attention.py` counts one width: equal at Dqk == Dv).
    Forward QK^T and PV; backward S, dP, dq, dk, dv. Bytes: q, k, v, o once
    forward; those, dO, dq, dk, dv once backward."""
    lanes = 3 * Dqk + 2 * Dv if backward else Dqk + Dv
    ops = 2.0 * B * H * T * T * lanes * (0.5 if causal else 1.0)
    nbytes = 2.0 * B * H * T * 2 * (Dqk + Dv) * (2 if backward else 1)
    return 1e3 * attention.least_seconds(ops, nbytes, peaks)[0]


def report(shape, used, ms, peaks, padded=False):
    B, T, H, D, causal = SHAPES[shape]
    built = (max(widths(D)),) * 2 if padded else widths(D)
    line = {"shape": shape, "call": "padded" if padded else "as built",
            "widths": pk.widths_label(*built),
            "blocks": {n: "x".join(map(str, used[n])) for n in ms
                       if n in used}}
    line.update({n + "_ms": t for n, t in ms.items()})
    if "flash_dq" in ms and "flash_dkv" in ms:
        line["bwd_ms"] = ms["flash_dq"] + ms["flash_dkv"]
    for key, kernel, backward in (("fwd", "flash_fwd", False),
                                  ("bwd", "bwd", True)):
        took = line.get(kernel + "_ms")
        if took is None:
            continue
        line[key + "_share_pct"] = 100 * least_ms(
            B, T, H, *built, causal, backward, peaks) / took
        if isinstance(D, tuple):
            line[key + "_asked_share_pct"] = 100 * least_ms(
                B, T, H, *ASKED, causal, backward, peaks) / took
    print(json.dumps(line), flush=True)


def parse(text):
    return [tuple(int(x) for x in c.split("x")) for c in text.split(",") if c]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default=",".join(SHAPES))
    for kernel in KERNELS:
        ap.add_argument("--" + kernel[len("flash_"):], default="", type=parse)
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"needs a TPU, found {dev.platform}")
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)[dev.device_kind]
    print(json.dumps({"device": dev.device_kind, "calls": CALLS}), flush=True)
    for shape in args.shape.split(","):
        report(shape, *measure(shape), peaks)
        if len(set(widths(SHAPES[shape][3]))) == 2:
            report(shape, *measure(shape, padded=True), peaks, padded=True)
        for kernel in KERNELS:
            for cand in getattr(args, kernel[len("flash_"):]):
                try:
                    report(shape, *measure(shape, {kernel: cand}), peaks)
                except Exception as e:      # a candidate Mosaic refuses
                    print(json.dumps({"shape": shape, "kernel": kernel,
                                      "blocks": cand, "error":
                                      f"{type(e).__name__}: {str(e)[:200]}"}),
                          flush=True)


if __name__ == "__main__":
    main()
