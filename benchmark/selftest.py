"""The benchmark's own arithmetic, checked against hand counts on the CPU.

    python3 -m benchmark.selftest

- flops: ResNet-50's forward at 224x224 is 4.09 G multiply-adds (He et al.
  2015 quote 3.8 G for the stride-on-1x1 layout; torchvision's layout, which
  the program follows, 4.09 G); one cgpt_1p3b block holds 12 d^2 weights.
- trace_reduce on `fixtures/synthetic_trace.json`, small enough to do by
  hand (times in ns). Steps start at 100, 200, 320, 400; the first is taken
  as cut by the start of the trace and the last by its end, so the window is
  [200, 400) and holds 2 steps. Busy: [200,290) = 90 (the custom call from
  230 to 280 and fusion.3 from 280 join), [320,400) = 80: 170 of 200, idle
  15%. The kernel `_flash_kernel`: 50 + 40 = 90 in 2 calls. Step intervals
  120, 80; gaps between a step program's end and the next one's start 30, 0.
  `union_ns` alone is checked on overlapping intervals too.
- trace_reduce on the fixtures cut from real traces of the two cells (first
  traced runs of PR 25, TPU v5 lite): the numbers an independent
  reduction gave when they were cut, frozen here.
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmark import check_manifest, trace_reduce          # noqa: E402
from benchmark.flops import attention, resnet50, transformer  # noqa: E402


def close(a, b, rel=1e-9):
    return abs(a - b) <= rel * max(abs(a), abs(b), 1e-300)


def load_config(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def check_flops():
    macs = resnet50.forward_macs(load_config("resnet50_imagenet"))
    assert abs(macs / 4.09e9 - 1) < 0.02, macs
    assert resnet50.train_flops_per_row(load_config("resnet50_imagenet")) \
        == 6 * macs
    cfg = load_config("cgpt_1p3b")
    d = cfg["d_model"]
    assert abs(transformer.block_params(cfg) / (12 * d * d) - 1) < 1e-3
    # per token: 24 d^2 in the block's products and 2 T d in attention under
    # the causal mask, forward; three times that to train
    T, L = cfg["input"]["seq_len"], cfg["layers"]
    per_token = transformer.train_flops_per_row(cfg) / T
    by_hand = 3 * (L * (24 * d * d + 2 * T * d) + 2 * d * 2 / T)
    assert close(per_token, by_hand, 1e-6), (per_token, by_hand)
    ops, nbytes = attention.flash_fwd(8, 16, 2048, 128, True)
    assert ops == 4 * 8 * 16 * 2048 * 2048 * 128 / 2
    assert nbytes == 4 * 8 * 16 * 2048 * 128 * 2
    assert attention.flash_bwd(8, 16, 2048, 128, True) == (2.5 * ops,
                                                           2 * nbytes)


def check_synthetic():
    ev = trace_reduce.read_fixture(
        os.path.join(HERE, "fixtures", "synthetic_trace.json"))
    t = trace_reduce.summarise(ev)
    assert t["step_program"] == "jit_step" and t["steps"] == 2
    assert close(t["window_s"], 200e-9) and close(t["busy_s"], 170e-9)
    assert sorted(t["intervals_ms"]) == [80e-6, 120e-6]
    assert sorted(t["step_gaps_ms"]) == [0.0, 30e-6]
    assert t["step_busy_ms"] == [90e-6, 80e-6]
    seconds, calls = trace_reduce.kernel_time(t, "_flash_kernel")
    assert close(seconds, 90e-9) and calls == 2
    total, gaps = trace_reduce.union_ns([(100, 130), (130, 170), (165, 180),
                                         (200, 230)])
    assert total == 110 and gaps == [(180, 20)]
    assert trace_reduce.kernel_time(t, "no_such_kernel") == (0, 0)
    assert close(trace_reduce.quantile([1, 2, 3, 4, 5], 0.95), 4.8)
    gaps = [g for _, g in t["breakdown"]["idle_gaps"]]
    assert close(gaps[0], 30e-9) and len(gaps) == 1
    from benchmark.layer_metrics import device_idle_share
    assert close(device_idle_share.read(t, {}, {}), 15.0)


def check_recorded():
    frozen = os.path.join(HERE, "fixtures", "recorded.json")
    with open(frozen) as f:
        for name, want in json.load(f).items():
            ev = trace_reduce.read_fixture(
                os.path.join(HERE, "fixtures", name))
            t = trace_reduce.summarise(ev)
            assert t["steps"] == want["steps"], (name, t["steps"])
            assert close(t["window_s"], want["window_s"], 1e-9), name
            assert close(t["busy_s"], want["busy_s"], 1e-6), name
            for pattern, (sec, calls) in want.get("kernels", {}).items():
                got = trace_reduce.kernel_time(t, pattern)
                assert close(got[0], sec, 1e-6) and got[1] == calls, \
                    (name, pattern, got)


def check_manifest_rules():
    root = os.path.dirname(HERE)
    path = os.path.join(root, "BENCHMARK.json")
    assert check_manifest.check(path) == []
    with open(path) as f:
        m = json.load(f)
    m["per_layer"][0]["layer"] = "start up"
    bad = os.path.join(root, ".bench_trace_manifest_probe.json")
    try:
        with open(bad, "w") as f:
            json.dump(m, f)
        assert any("layer" in x for x in check_manifest.check(bad))
    finally:
        os.remove(bad)


def main():
    check_flops()
    check_synthetic()
    check_recorded()
    check_manifest_rules()
    print("selftest ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
