"""At-scale GBDT wall-clock measurements (the 10M-row scale rows).

Default: 10M x 28 level-wise (cold + warm 10-iter fits, synced) plus a
3-iter leaf-wise probe. LEAFWISE_1M=1 measures the 1M-row leaf-wise
per-iteration cost instead (the leaf-wise row)."""

import os
import sys
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import time

import numpy as np


def _data(n, d=28):
    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, d)).astype(np.float32)
    logit = x[:, 0] * 2 + x[:, 1] - x[:, 2] * 0.5 + rng.normal(0, 0.5, n)
    return x, (logit > 0).astype(np.float32)


def _timed_fit(x, y, p, tag):
    from mmlspark_tpu.models.gbdt.engine import fit_gbdt
    t0 = time.perf_counter()
    ens = fit_gbdt(x, y, p)
    np.asarray(ens.leaf).sum()          # sync on the fitted trees
    dt = time.perf_counter() - t0
    print(f"{tag}: {dt:.1f}s total, {dt/p.num_iterations:.2f} s/iter "
          f"(incl fixed binning/upload cost)", flush=True)
    return dt


def main():
    from mmlspark_tpu.models.gbdt.engine import GBDTParams

    if os.environ.get("LEAFWISE_1M") == "1":
        x, y = _data(1_000_000)
        print("data built", flush=True)
        p = GBDTParams(num_iterations=10, num_leaves=31, max_depth=0,
                       objective="binary")
        _timed_fit(x, y, p, "leaf-wise 31L 1M cold")
        _timed_fit(x, y, p, "leaf-wise 31L 1M warm")
        return

    x, y = _data(10_000_000)
    print("data built", flush=True)
    p = GBDTParams(num_iterations=10, max_depth=5, objective="binary")
    _timed_fit(x, y, p, "level-wise 10M cold")
    _timed_fit(x, y, p, "level-wise 10M warm")
    p2 = GBDTParams(num_iterations=3, num_leaves=31, max_depth=0,
                    objective="binary")
    _timed_fit(x, y, p2, "leaf-wise 10M cold")


if __name__ == "__main__":
    main()
