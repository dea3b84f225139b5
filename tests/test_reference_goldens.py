"""The reference's LITERAL benchmark goldens, keyed by its dataset names.

SURVEY §6's correctness bar: match the committed metric floors in
`/root/reference/src/lightgbm/src/test/scala/classificationBenchmarkMetrics
.csv` (train-set AUC of a numLeaves=5 x numIterations=10
LightGBMClassifier) and the train-classifier grid
(`VerifyTrainClassifier.scala` benchmarkMetrics.csv: train-set
areaUnderROC — probability scores for LR/DT/RF, scored LABELS for GBT/NB).

The real UCI CSVs are downloaded by the reference's build at test time and
are NOT in its repo; this environment has zero egress, so the datasets are
schema-faithful SYNTHESES (mmlspark_tpu/testing/reference_datasets.py:
exact column names, row counts, class balance, published marginal stats,
difficulty calibrated against the reference's own committed metrics).
Floors assert "our engine on this schema/difficulty clears what the
reference committed"; exact values live in the golden CSV as the
regression gate.

Stated plainly (the honesty bar for any parity claim built on these):
the floors accept `auc >= floor - 0.05`, matching the reference CSV's
own one-decimal rounding — e.g. banknote passes at 0.96 against the
reference's committed 1.0 — and the datasets are documented syntheses,
not the real UCI downloads. So the claim these tests support is
"meets the reference's committed metric AFTER its own rounding, on
schema-faithful synthetic stand-ins", not a raw-number tie on the
original corpora.

The two rows that moved twice (PR 30): the PimaIndian MLP trainAUC and
the BreastTissue LR trainAccuracy were first recorded as 0.9970 and
0.6981, re-recorded as 0.9619 and 0.6132 on a runtime since retired, and
read 0.9970 and 0.6981 again on the installed one (jax 0.9.0): the same
to four places in every run, with and without the native CSV parser, and
byte for byte the file first recorded. The arithmetic of both fits is
what it was then; what differs between runtimes is the order of float32
reductions. It shows in the LR because the fit has not settled where
the test reads it: 80 full-batch Adam steps at 0.05 on raw impedance
columns of magnitude 2,000-6,000, and the train accuracy swings between
0.53 and 0.74 from one step to the next around step 80 (a float64
reference reads 0.6792 there; the installed runtime without FMA 0.6887).
The MLP memorises 768 rows over 720 steps and reads 0.993-1.000 at every
epoch count from 100 to 130 here (0.9990 on one device, 1.0000 without
FMA): a long trajectory that a different rounding ends elsewhere.
So these two rows pin the runtime's numerics as well as the engine: on a
machine whose XLA build contracts or vectorises differently they may
need recording again, which is a reading to take, not a tolerance to
widen. The reference-floor asserts remain the correctness bar.
"""

import os

import numpy as np
import pytest
from sklearn.metrics import roc_auc_score

from mmlspark_tpu.automl import TrainClassifier
from mmlspark_tpu.models import (DecisionTreeClassifier, GBTClassifier,
                                 LogisticRegression,
                                 MultilayerPerceptronClassifier, NaiveBayes,
                                 RandomForestClassifier)
from mmlspark_tpu.models.gbdt import LightGBMClassifier
from mmlspark_tpu.testing import assert_golden
from mmlspark_tpu.testing.reference_datasets import (
    LIGHTGBM_REFERENCE_AUC, LIGHTGBM_REFERENCE_RMSE, MULTICLASS_DATASETS,
    REFERENCE_DATASETS, REGRESSION_DATASETS,
    TRAIN_CLASSIFIER_MULTICLASS_ACC, TRAIN_CLASSIFIER_REFERENCE_AUC)

GOLDENS = os.path.join(os.path.dirname(__file__), "goldens",
                       "reference_dataset_metrics.csv")


def _binary_y(df, label):
    """Label column -> {0,1} by SORTED level order (ValueIndexer's
    contract), so probability[:, 1] and scored labels stay aligned for
    string ('g'/'h') and non-contiguous (2/4) codings too."""
    vals = np.asarray(df.col(label))
    uniq = sorted(set(vals.tolist()))
    assert len(uniq) == 2, uniq
    return (vals == uniq[1]).astype(np.int64), uniq


def _train_auc_from_scores(out, label_col, y):
    prob = np.stack(list(out.col("probability")))[:, 1]
    return roc_auc_score(y, prob)


def _train_auc_from_labels(out, y, uniq):
    pred = (np.asarray(out.col("scored_labels")) == uniq[1]).astype(float)
    return roc_auc_score(y, pred)


@pytest.mark.parametrize("dataset", sorted(LIGHTGBM_REFERENCE_AUC))
def test_lightgbm_reference_floor(dataset):
    """VerifyLightGBMClassifier.scala:40-56 config exactly: numLeaves=5,
    numIterations=10, featurize-all-columns, TRAIN-set AUC; floor = the
    reference's committed value (classificationBenchmarkMetrics.csv)."""
    gen, label = REFERENCE_DATASETS[dataset]
    df = gen()
    y, _ = _binary_y(df, label)
    model = (TrainClassifier().setLabelCol(label)
             .setModel(LightGBMClassifier().setNumLeaves(5)
                       .setNumIterations(10))
             .fit(df))
    out = model.transform(df)
    auc = _train_auc_from_scores(out, label, y)
    floor = LIGHTGBM_REFERENCE_AUC[dataset]
    # the reference rounds to the decimals in its CSV; >= floor - half-ulp
    assert auc >= floor - 0.05, (
        f"{dataset}: train AUC {auc:.4f} below the reference's committed "
        f"{floor} (rounded to 1 decimal)")
    assert_golden(GOLDENS, dataset, "LightGBMClassifier", "trainAUC",
                  float(auc), tolerance=0.03)


_GRID_ALGOS = {
    "LogisticRegression": (
        lambda: LogisticRegression().setMaxIter(80), "scores"),
    "DecisionTreeClassification": (
        lambda: DecisionTreeClassifier().setMaxBin(63), "scores"),
    "RandomForestClassification": (
        lambda: RandomForestClassifier().setNumIterations(20)
        .setMaxBin(63), "scores"),
    "GradientBoostedTreesClassification": (
        lambda: GBTClassifier().setNumIterations(20).setMaxBin(63),
        "labels"),
    "NaiveBayesClassifier": (lambda: NaiveBayes(), "labels"),
    "MultilayerPerceptronClassifier": (
        lambda: MultilayerPerceptronClassifier().setMaxIter(120), "labels"),
}

#: datasets added in the round-3 widening run in the extended tier (the
#: telescope synthesis alone is 19k rows x 5 algorithms). Derived, not
#: hand-listed: exactly the binary datasets WITHOUT a LightGBM floor row
#: (the original three are the default-tier fixtures)
_WIDENED = set(REFERENCE_DATASETS) - set(LIGHTGBM_REFERENCE_AUC)


def test_banknote_has_no_nb_row_because_features_go_negative():
    """The reference grid omits NaiveBayes for banknote (Spark ML
    multinomial NB rejects the negative wavelet features — ours raises the
    same); keep the omission deliberate, not accidental."""
    assert ("data_banknote_authentication.csv",
            "NaiveBayesClassifier") not in TRAIN_CLASSIFIER_REFERENCE_AUC
    gen, label = REFERENCE_DATASETS["data_banknote_authentication.csv"]
    with pytest.raises(ValueError, match="nonnegative"):
        TrainClassifier().setLabelCol(label).setModel(NaiveBayes()).fit(gen())


@pytest.mark.parametrize("dataset,algo", [
    pytest.param(d, a, marks=([pytest.mark.extended] if d in _WIDENED
                              else []))
    for d, a in sorted(TRAIN_CLASSIFIER_REFERENCE_AUC)])
def test_train_classifier_reference_grid(dataset, algo):
    """The reference's benchmarkMetrics.csv rows for these datasets: our
    engine must meet or beat each committed train-set AUC (scored labels
    for GBT/NB, per VerifyTrainClassifier.scala:218-255 — label-AUC is why
    the reference's own GBT/NB numbers look low)."""
    gen, label = REFERENCE_DATASETS[dataset]
    make, mode = _GRID_ALGOS[algo]
    df = gen()
    y, uniq = _binary_y(df, label)
    model = TrainClassifier().setLabelCol(label).setModel(make()).fit(df)
    out = model.transform(df)
    auc = (_train_auc_from_scores(out, label, y) if mode == "scores"
           else _train_auc_from_labels(out, y, uniq))
    ref = TRAIN_CLASSIFIER_REFERENCE_AUC[(dataset, algo)]
    assert auc >= ref - 0.02, (
        f"{dataset}/{algo}: train AUC {auc:.4f} vs reference {ref}")
    assert_golden(GOLDENS, dataset, algo, "trainAUC", float(auc),
                  tolerance=0.03)


@pytest.mark.parametrize("dataset", sorted(REGRESSION_DATASETS))
def test_lightgbm_regression_reference_ceiling(dataset):
    """VerifyLightGBMRegressor.scala:32-66 config exactly: numLeaves=5,
    numIterations=10, TRAIN-set RMSE; ceiling = the reference's committed
    value + half of its rounding window (it rounds to `decimals`:
    energyefficiency 0, airfoil 1, Buzz -3, machine -2, Concrete 0)."""
    from mmlspark_tpu.automl import TrainRegressor
    from mmlspark_tpu.models.gbdt import LightGBMRegressor

    gen, label = REGRESSION_DATASETS[dataset]
    df = gen()
    y = np.asarray(df.col(label), np.float64)
    model = (TrainRegressor().setLabelCol(label)
             .setModel(LightGBMRegressor().setNumLeaves(5)
                       .setNumIterations(10))
             .fit(df))
    pred = np.asarray(model.transform(df).col("prediction"), np.float64)
    rmse = float(np.sqrt(np.mean((pred - y) ** 2)))
    ceiling, decimals = LIGHTGBM_REFERENCE_RMSE[dataset]
    tol = 0.5 * 10 ** (-decimals)
    assert rmse <= ceiling + tol, (
        f"{dataset}: train RMSE {rmse:.2f} above the reference's "
        f"committed {ceiling} (+{tol} rounding window)")
    # RMSE scales vary 4 orders of magnitude across these datasets —
    # the golden tolerance must be RELATIVE (1%), and stay inside the
    # ceiling's slack so the two assertions can't disagree
    assert_golden(GOLDENS, dataset, "LightGBMRegressor", "trainRMSE",
                  rmse, tolerance=max(0.01, 0.01 * rmse))


# the multiclass grid runs the SAME configs as the binary grid (minus
# GBT, which the reference rejects for multiclass) — derive, don't copy
_MC_ALGOS = {k: make for k, (make, _) in _GRID_ALGOS.items()
             if k != "GradientBoostedTreesClassification"}


@pytest.mark.parametrize("dataset,algo", sorted(
    TRAIN_CLASSIFIER_MULTICLASS_ACC))
def test_train_classifier_multiclass_reference_grid(dataset, algo):
    """The reference grid's multiclass rows (train-set accuracy via
    MulticlassMetrics, VerifyTrainClassifier.scala:404-424): abalone's
    ~28 near-continuous ring classes keep every number low; BreastTissue
    is 6 overlapping impedance classes; CarEvaluation is a deterministic
    expert rule with 70/22/4/4 skew."""
    gen, label = MULTICLASS_DATASETS[dataset]
    df = gen()
    model = (TrainClassifier().setLabelCol(label)
             .setModel(_MC_ALGOS[algo]()).fit(df))
    pred = model.transform(df).col("scored_labels")
    truth = df.col(label)
    acc = float(np.mean([str(a) == str(b) for a, b in zip(pred, truth)]))
    ref = TRAIN_CLASSIFIER_MULTICLASS_ACC[(dataset, algo)]
    assert acc >= ref - 0.02, (
        f"{dataset}/{algo}: train accuracy {acc:.3f} vs reference {ref}")
    assert_golden(GOLDENS, dataset, algo, "trainAccuracy", acc,
                  tolerance=0.03)
