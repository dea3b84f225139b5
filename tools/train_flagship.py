"""Flagship ACCURACY run: train ResNet-20 from scratch on the richest
real 32x32 corpus available offline and report held-out accuracy.

The benchmark (`benchmark/run.py`) measures SPEED on seeded noise; this
script proves the flagship path LEARNS — the reference's closest analog is notebook
401's CIFAR ConvNet demonstration. The corpus is all 10 classes of
sklearn's UCI handwritten-digit scans (the only real image data a
zero-egress image ships), split train/test at the ORIGINAL-scan level
and augmented to ~50k rows with label-preserving transforms
(testing.datagen.digits_rgb32_augmented); the held-out set is untouched
original scans.

Reproduce (runs on the attached TPU; CPU works but is slow):

    python tools/train_flagship.py              # ~50k rows, 12 epochs
    python tools/train_flagship.py --total 20000 --epochs 8   # quicker
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--total", type=int, default=50_000,
                    help="augmented training rows")
    ap.add_argument("--epochs", type=int, default=12)
    ap.add_argument("--batch", type=int, default=512)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from mmlspark_tpu.testing.datagen import digits_rgb32_augmented
    t0 = time.perf_counter()
    xt, yt, xe, ye = digits_rgb32_augmented(total=args.total,
                                            seed=args.seed)
    t_corpus = time.perf_counter() - t0
    print(f"corpus: {len(xt)} augmented train rows from "
          f"{len(np.unique(yt))}-class real scans, {len(xe)} held-out "
          f"ORIGINAL scans ({t_corpus:.1f}s to build)")

    sys.path.insert(0, os.path.join(REPO, "tools"))
    from build_zoo import train_and_eval
    t0 = time.perf_counter()
    _, acc = train_and_eval({"type": "resnet", "num_classes": 10},
                            xt, yt, xe, ye, epochs=args.epochs,
                            batch=args.batch, lr=args.lr, seed=args.seed)
    t_train = time.perf_counter() - t0
    print(json.dumps({
        "metric": "resnet20_real_digits10_heldout_accuracy",
        "value": round(acc, 4),
        "unit": f"accuracy on {len(xe)} untouched original scans "
                f"(train {t_train:.0f}s, {len(xt)} rows x "
                f"{args.epochs} epochs)",
        "vs_baseline": None,
    }))
    return 0 if acc > 0.97 else 1


if __name__ == "__main__":
    sys.exit(main())
