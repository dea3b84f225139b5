"""EFB wide-sparse GBDT benchmark: fit wall-clock at
the reference's featurization width — hashed-text-style sparse rows,
2^16 columns — through the LightGBMClassifier stage's EFB path
(plan bundles -> categorical composite codes -> leaf-wise category-set
splits; the reference's Featurize defaults hash to 2^18 dims,
Featurize.scala:15-18, and native LightGBM survives them via EFB).

Prints one JSON line (synced timing: async dispatch would otherwise
report enqueue time)."""

import json
import time

import numpy as np
import scipy.sparse as sp


def main():
    from mmlspark_tpu.core.dataframe import DataFrame
    from mmlspark_tpu.core.utils import object_column
    from mmlspark_tpu.models.gbdt.stages import LightGBMClassifier

    rng = np.random.default_rng(0)
    n, d = 200_000, 1 << 16
    nnz_per_row = 24                      # hashed-text density ballpark
    # zipf-ish column popularity (token frequencies) + one signal token
    # per row drawn from 8 ids; the label is which half of the signal
    # vocabulary the row's token belongs to
    rows = np.repeat(np.arange(n), nnz_per_row)
    cols = (np.minimum(d - 1, rng.zipf(1.3, size=n * nnz_per_row) - 1)
            .astype(np.int64))
    sig_ids = np.array([5000, 9000, 14000, 20000, 27000, 35000, 44000,
                        54000])
    sig_pick = rng.integers(0, len(sig_ids), n)
    rows = np.concatenate([rows, np.arange(n)])
    cols = np.concatenate([cols, sig_ids[sig_pick]])
    vals = np.ones(len(rows), np.float32)
    x = sp.csr_matrix((vals, (rows, cols)), shape=(n, d))
    y = (sig_pick % 2).astype(np.float64)

    df = DataFrame({"features": object_column(list(x)),
                    "label": y})
    clf = (LightGBMClassifier().setLabelCol("label")
           .setNumIterations(20).setMaxDenseFeatures(512))

    t0 = time.perf_counter()
    model = clf.fit(df)
    # sync on the fitted trees
    np.asarray(model._ensemble().leaf).sum()
    fit_s = time.perf_counter() - t0

    out = model.transform(df)
    acc = float((np.asarray(out.toPandas()["prediction"],
                            dtype=np.float64) == y).mean())
    print(json.dumps({
        "metric": "gbdt_efb_widesparse_fit_seconds",
        "value": round(fit_s, 2),
        "unit": f"s (200k x 2^16 sparse, 20 iters, train-set acc "
                f"{acc:.3f})",
        "vs_baseline": None,
    }))


if __name__ == "__main__":
    main()
