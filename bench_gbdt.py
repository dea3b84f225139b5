"""Secondary benchmark: LightGBM-class 1M-row GBDT fit wall-clock (the
second north-star metric in BASELINE.json). Prints one JSON line with cold (includes XLA compile)
and warm fit times on the attached chip."""

import json
import time

import numpy as np


def main():
    from mmlspark_tpu.models.gbdt.engine import GBDTParams, fit_gbdt

    rng = np.random.default_rng(0)
    n, d = 1_000_000, 28
    x = rng.normal(size=(n, d)).astype(np.float32)
    logit = x[:, 0] * 2 + x[:, 1] - x[:, 2] * 0.5 + rng.normal(0, 0.5, n)
    y = (logit > 0).astype(np.float32)

    p = GBDTParams(num_iterations=100, max_depth=5, objective="binary")

    def timed_fit():
        # sync on the fitted trees: async dispatch otherwise reports
        # enqueue time, not compute
        t0 = time.perf_counter()
        ens = fit_gbdt(x, y, p)
        np.asarray(ens.leaf).sum()
        return time.perf_counter() - t0

    cold = timed_fit()
    warm = [timed_fit() for _ in range(2)]
    print(json.dumps({
        "metric": "gbdt_1m_row_fit_seconds",
        "value": round(min(warm), 2),
        "unit": "s (warm; cold incl. compile: " + f"{cold:.1f})",
        "vs_baseline": None,
    }))


if __name__ == "__main__":
    main()
