"""Cross-stage XLA pipeline fusion (core/capture.py): fused-vs-staged
numerical parity for representative zoo-style pipelines, maximal-segment
planning around uncapturable stages (prefix/middle/suffix), the ONE-
compiled-program acceptance assertion via profiler counters, segment
telemetry (dispatches / transfer bytes), and bundle round-trip of a
pipeline serving composite including torn-shard graded fallback."""

import base64

import numpy as np
import pytest

from mmlspark_tpu import DataFrame, Pipeline, telemetry
from mmlspark_tpu.core import capture as capturelib
from mmlspark_tpu.core.capture import StageCapture
from mmlspark_tpu.core.pipeline import PipelineModel, Transformer
from mmlspark_tpu.core.utils import object_column
from mmlspark_tpu.io.serving import (BucketPolicy, FusedServingStep,
                                     load_bundle, save_bundle,
                                     serve_continuous)
from mmlspark_tpu.models.classical import (LinearRegression,
                                           LogisticRegression, NaiveBayes)
from mmlspark_tpu.models.gbdt.stages import (LightGBMClassifier,
                                             LightGBMRegressor)
from mmlspark_tpu.models.trainer import TpuLearner
from mmlspark_tpu.resilience.ckpt import CorruptCheckpoint
from mmlspark_tpu.stages.basic import (DropColumns, FastVectorAssembler,
                                       RenameColumn, SelectColumns,
                                       UDFTransformer)
from mmlspark_tpu.stages.data_stages import CleanMissingData


@pytest.fixture
def tel():
    telemetry.enable()
    telemetry.registry.reset()
    yield telemetry
    telemetry.disable()


def _counter_total(name):
    snap = telemetry.snapshot()
    return sum(s["value"] for s in snap.get(name, {}).get("series", []))


def _frame(n=200, d=4, seed=0, nans=True):
    rng = np.random.default_rng(seed)
    cols = {f"f{i}": rng.normal(size=n) for i in range(d)}
    if nans:
        cols["f1"][::7] = np.nan
    y = (np.nan_to_num(cols["f0"]) + np.nan_to_num(cols["f1"]) > 0)
    return DataFrame({**cols, "label": y.astype(np.int64)}), \
        [f"f{i}" for i in range(d)]


def _fit_lr_pipeline(df, feats):
    return Pipeline().setStages((
        CleanMissingData().setInputCols(feats),
        FastVectorAssembler().setInputCols(feats).setOutputCol("features"),
        LogisticRegression().setMaxIter(25),
    )).fit(df)


def _col_matrix(df, name):
    col = df.col(name)
    if col.dtype.kind == "O":
        return np.stack([np.asarray(v) for v in col])
    return col


def _assert_parity(staged, fused, cols, atol=1e-5):
    assert staged.columns == fused.columns
    for c in cols:
        np.testing.assert_allclose(
            _col_matrix(staged, c).astype(np.float64),
            _col_matrix(fused, c).astype(np.float64),
            rtol=1e-4, atol=atol, err_msg=c)


# ------------------------------------------------------------------- parity

class TestParity:
    def test_impute_assemble_lr_pipeline(self):
        df, feats = _frame()
        pm = _fit_lr_pipeline(df, feats)
        staged = pm.transform(df)
        fused = pm.setFusePipeline(True).transform(df)
        _assert_parity(staged, fused, ["features", "probability",
                                       "prediction"])
        # dtypes survive: prediction stays the staged float64
        assert fused.col("prediction").dtype == np.float64
        # score-column metadata tags survive the fused rebuild
        assert fused.metadata("probability") == staged.metadata("probability")
        assert fused.metadata("prediction") == staged.metadata("prediction")

    def test_gbdt_classifier_pipeline(self):
        df, feats = _frame(n=400, nans=False)
        pm = Pipeline().setStages((
            FastVectorAssembler().setInputCols(feats).setOutputCol("features"),
            LightGBMClassifier().setNumIterations(10).setMaxDepth(3),
        )).fit(df)
        staged = pm.transform(df)
        fused = pm.setFusePipeline(True).transform(df)
        _assert_parity(staged, fused,
                       ["rawPrediction", "probability", "prediction"],
                       atol=1e-4)

    def test_gbdt_regressor_pipeline(self):
        df, feats = _frame(n=400, nans=False)
        df = df.withColumn("target",
                           np.asarray(df.col("f0")) * 2.0 + 1.0)
        pm = Pipeline().setStages((
            FastVectorAssembler().setInputCols(feats).setOutputCol("features"),
            LightGBMRegressor().setLabelCol("target")
            .setNumIterations(10).setMaxDepth(3),
        )).fit(df)
        staged = pm.transform(df)
        fused = pm.setFusePipeline(True).transform(df)
        _assert_parity(staged, fused, ["prediction"], atol=1e-4)

    def test_tpu_learner_model_pipeline(self):
        """Featurize -> trained-net predict: the zoo shape (a TpuLearner
        fit hands back a TpuModel, whose capture is the same
        module.apply body the staged jitted transform dispatches)."""
        df, feats = _frame(n=256, nans=True)
        pm = Pipeline().setStages((
            CleanMissingData().setInputCols(feats),
            FastVectorAssembler().setInputCols(feats).setOutputCol("features"),
            TpuLearner().setModelConfig({"type": "mlp", "hidden": [16],
                                         "num_classes": 2})
            .setEpochs(2).setBatchSize(64),
        )).fit(df)
        staged = pm.transform(df)
        fused = pm.setFusePipeline(True).transform(df)
        _assert_parity(staged, fused, ["scores"], atol=1e-3)

    def test_naive_bayes_pipeline(self):
        df, feats = _frame(n=300, nans=False)
        pm = Pipeline().setStages((
            FastVectorAssembler().setInputCols(feats).setOutputCol("features"),
            NaiveBayes().setModelType("gaussian"),
        )).fit(df)
        staged = pm.transform(df)
        fused = pm.setFusePipeline(True).transform(df)
        _assert_parity(staged, fused, ["probability", "prediction"],
                       atol=1e-4)

    def test_linear_regression_with_plumbing_stages(self):
        """Select/Drop/Rename fold into the segment as pure column
        plumbing — no extra dispatches, no host hop."""
        df, feats = _frame(nans=False)
        pm = Pipeline().setStages((
            FastVectorAssembler().setInputCols(feats).setOutputCol("features"),
            SelectColumns().setCols(["features", "label"]),
            LinearRegression().setLabelCol("label").setMaxIter(25),
            RenameColumn().setInputCol("prediction").setOutputCol("yhat"),
            DropColumns().setCols(["label"]),
        )).fit(df)
        staged = pm.transform(df)
        fused = pm.setFusePipeline(True).transform(df)
        assert staged.columns == fused.columns == ["features", "yhat"]
        _assert_parity(staged, fused, ["yhat"])

    def test_default_is_staged(self):
        df, feats = _frame()
        pm = _fit_lr_pipeline(df, feats)
        assert pm.getFusePipeline() is False
        pm.transform(df)
        assert not getattr(pm, "_seg_cache", None)


# ------------------------------------------------- one-program acceptance

class TestOneProgram:
    def test_three_stage_pipeline_is_one_compiled_program(self, tel):
        """The acceptance criterion: a 3-stage capturable pipeline
        executes as exactly ONE compiled program — one segment, one
        XLA compile, one dispatch per transform — and the second
        transform reuses the executable (zero new compiles)."""
        df, feats = _frame()
        pm = _fit_lr_pipeline(df, feats).setFusePipeline(True)
        d0 = _counter_total("mmlspark_pipeline_fused_dispatches_total")
        pm.transform(df)
        (entry,) = pm._seg_cache.values()
        pf = entry["pf"]
        assert pf.compiles == 1          # ONE program for all 3 stages
        assert pf.calls == 1             # ONE device dispatch
        assert _counter_total(
            "mmlspark_pipeline_fused_dispatches_total") - d0 == 1
        snap = telemetry.snapshot()
        assert snap["mmlspark_pipeline_segments"]["series"][0]["value"] == 1
        pm.transform(df)
        assert pf.compiles == 1          # warm: no recompile
        assert pf.calls == 2

    def test_transfer_bytes_counted_at_boundaries_only(self, tel):
        df, feats = _frame()
        pm = _fit_lr_pipeline(df, feats).setFusePipeline(True)
        pm.transform(df)
        snap = telemetry.snapshot()
        series = {s["labels"]["direction"]: s["value"] for s in
                  snap["mmlspark_pipeline_transfer_bytes_total"]["series"]}
        n = len(df)
        # in: the four f64 feature columns, shipped ONCE for the whole
        # segment; out: the four imputed f32 columns (visible in the
        # result frame, like the staged path) + features (n,4) f32 +
        # probability (n,2) f32 + prediction (n,) f32. The staged chain
        # would additionally round-trip every intermediate between
        # stages; inside the segment that traffic is zero.
        assert series["in"] == n * 4 * 8
        assert series["out"] == (n * 4 * 4) + (n * 4 * 4) \
            + (n * 2 * 4) + (n * 4)

    def test_shape_polymorphic_retrace_is_counted(self, tel):
        df, feats = _frame(n=200)
        df2, _ = _frame(n=77)
        pm = _fit_lr_pipeline(df, feats).setFusePipeline(True)
        pm.transform(df)
        pm.transform(df2)                # new batch shape -> retrace
        (entry,) = pm._seg_cache.values()
        assert entry["pf"].compiles == 2
        assert entry["pf"].causes.get("shape_change") == 1


# ---------------------------------------------------- segment splitting

def _udf_stage(in_col="f0", out_col="g0"):
    return (UDFTransformer().setInputCol(in_col).setOutputCol(out_col)
            .setUdf(lambda v: float(v) * 2.0).setVectorized(False))


class TestSegmentSplitting:
    def _pipeline(self, df, feats, where):
        """Five capturable stages with one UDF stage spliced at
        ``where`` (prefix | middle | suffix | none)."""
        stages = [
            CleanMissingData().setInputCols(feats),
            FastVectorAssembler().setInputCols(feats).setOutputCol("features"),
            LogisticRegression().setMaxIter(15),
        ]
        udf = _udf_stage()
        if where == "prefix":
            stages = [udf] + stages
        elif where == "middle":
            stages = stages[:1] + [udf] + stages[1:]
        elif where == "suffix":
            stages = stages + [udf]
        return Pipeline().setStages(tuple(stages)).fit(df)

    @pytest.mark.parametrize("where,segments", [
        ("none", 1),      # [C A L]        -> one 3-stage segment
        ("prefix", 1),    # [U | C A L]    -> staged U, one segment
        ("suffix", 1),    # [C A L | U]    -> one segment, staged U
        ("middle", 1),    # [C | U | A L]  -> staged C+U, A+L fuse
    ])
    def test_split_positions_keep_parity(self, tel, where, segments):
        df, feats = _frame()
        pm = self._pipeline(df, feats, where)
        staged = pm.transform(df)
        fused = pm.setFusePipeline(True).transform(df)
        _assert_parity(staged, fused, ["features", "probability",
                                       "prediction"]
                       + (["g0"] if where != "none" else []))
        snap = telemetry.snapshot()
        assert snap["mmlspark_pipeline_segments"]["series"][0]["value"] \
            == segments

    def test_middle_split_counts_staged_stages(self, tel):
        df, feats = _frame()
        pm = self._pipeline(df, feats, "middle").setFusePipeline(True)
        pm.transform(df)
        # CleanMissingData's model lands in a 1-stage "segment" (runs
        # staged) + the UDF stage itself
        assert _counter_total(
            "mmlspark_pipeline_staged_stage_transforms_total") == 2
        assert _counter_total(
            "mmlspark_pipeline_fused_dispatches_total") == 1

    def test_ragged_rows_fall_back_staged(self, tel):
        """A ragged object column passes the cheap planner predicate but
        fails at encode — the segment falls back to the staged chain,
        counted, with identical results."""
        rows = [np.ones(3, np.float32), np.ones(4, np.float32)] * 10
        df = DataFrame({"features": object_column(rows),
                        "flat": np.arange(20).astype(np.float64)})
        pmodel = PipelineModel().setStages((
            _RowSum(),
            RenameColumn().setInputCol("s").setOutputCol("rowsum"),
        )).setFusePipeline(True)
        out = pmodel.transform(df)
        assert _counter_total(
            "mmlspark_pipeline_fusion_fallbacks_total") == 1
        assert _counter_total(
            "mmlspark_pipeline_fused_dispatches_total") == 0
        np.testing.assert_allclose(out.col("rowsum"),
                                   [float(np.asarray(r).sum())
                                    for r in rows])


class _RowSum(Transformer):
    """Test stage: per-row sum of the features column. Capturable on
    paper — the fallback test feeds it RAGGED rows the encoder rejects."""

    def transform(self, df):
        out = np.array([float(np.asarray(v).sum())
                        for v in df.col("features")])
        return df.withColumn("s", out)

    def capture(self, columns):
        if "features" not in columns:
            return None
        return StageCapture(lambda p, xs: (xs[0].sum(axis=1),),
                            inputs=("features",), outputs=("s",),
                            host_cast={"s": np.float64})


# --------------------------------------------------- serving composites

_D = 6


def _fit_serving_pipeline(seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(240, _D)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.int64)
    df = DataFrame({"features": object_column(list(x)), "label": y})
    pm = Pipeline().setStages((
        FastVectorAssembler().setInputCols(["features"])
        .setOutputCol("assembled"),
        LogisticRegression().setFeaturesCol("assembled").setMaxIter(20),
    )).fit(df)
    return pm, x


def _mk_pipeline_step(pm, output="argmax", max_batch=32):
    return FusedServingStep.from_pipeline(
        pm, input_col="features", row_shape=(_D,), in_dtype=np.float32,
        policy=BucketPolicy(max_batch=max_batch, min_bucket=8),
        output=output)


def _payloads(x):
    return [base64.b64encode(np.ascontiguousarray(r).tobytes()).decode()
            for r in x]


class TestPipelineServingComposite:
    def test_step_matches_staged_pipeline(self):
        pm, x = _fit_serving_pipeline()
        step = _mk_pipeline_step(pm)
        replies = step(_payloads(x[:9]))
        staged = pm.transform(DataFrame(
            {"features": object_column(list(x[:9]))}))
        want = staged.col("prediction").astype(int)
        got = [int(r.split(":")[1].rstrip("}")) for r in replies]
        assert got == list(want)

    def test_uncapturable_stage_raises(self):
        pm, _ = _fit_serving_pipeline()
        bad = PipelineModel().setStages(
            tuple(pm.getStages()) + (_udf_stage("prediction", "z"),))
        with pytest.raises(ValueError, match="not capturable"):
            _mk_pipeline_step(bad)

    def test_bundle_round_trip_zero_compiles(self, tel, tmp_path):
        """A serving worker loads a featurize->predict PIPELINE — not a
        bare model — warm: the reloaded composite answers its first
        request with ZERO compiles."""
        pm, x = _fit_serving_pipeline()
        step = _mk_pipeline_step(pm)
        step.compile_buckets()
        want = step(_payloads(x[:5]))
        save_bundle(str(tmp_path), step)

        def loads():
            series = telemetry.snapshot().get(
                "mmlspark_serving_bundle_loads_total", {"series": []})
            return {s["labels"]["result"]: s["value"]
                    for s in series["series"]}
        before = loads()
        loaded = load_bundle(str(tmp_path))
        assert loaded.warm_buckets() == step.policy.buckets
        assert loaded.compiles() == 0
        assert loaded(_payloads(x[:5])) == want
        assert loaded.compiles() == 0            # first request was warm
        # the loads this test made, whatever series the worker's earlier
        # test files left in the registry
        after = loads()
        assert {k: v - before.get(k, 0) for k, v in after.items()
                if v != before.get(k, 0)} == {"warm": 1}

    def test_torn_exec_shard_degrades_to_cold_compile(self, tel, tmp_path):
        pm, x = _fit_serving_pipeline()
        step = _mk_pipeline_step(pm)
        save_bundle(str(tmp_path), step)
        shard = tmp_path / "bundle_exec_b16.bin"
        shard.write_bytes(shard.read_bytes()[:-5])
        loaded = load_bundle(str(tmp_path))
        assert loaded.warm_buckets() == [8, 32]
        assert _counter_total(
            "mmlspark_serving_bundle_exec_failures_total") == 1
        # the torn bucket still serves — one counted cold compile
        out = loaded.score_rows(np.zeros((12, _D), np.float32), 16)
        assert out.shape == (12,)
        assert loaded.compiles() == 1

    def test_torn_pipeline_shard_is_fatal(self, tel, tmp_path):
        pm, _ = _fit_serving_pipeline()
        step = _mk_pipeline_step(pm)
        save_bundle(str(tmp_path), step)
        blob = (tmp_path / "bundle_pipeline.bin").read_bytes()
        (tmp_path / "bundle_pipeline.bin").write_bytes(blob[:-3])
        with pytest.raises(CorruptCheckpoint):
            load_bundle(str(tmp_path))

    def test_continuous_engine_serves_pipeline_step(self, tel):
        """FusedServingStep.from_pipeline drops into serve_continuous
        unchanged — the continuous-batching engine's step body IS the
        pipeline composite."""
        import urllib.request
        pm, x = _fit_serving_pipeline()
        step = _mk_pipeline_step(pm)
        source, loop = serve_continuous(step, max_wait=0.005)
        try:
            req = urllib.request.Request(
                source.url, data=_payloads(x[:1])[0].encode())
            with urllib.request.urlopen(req, timeout=30) as r:
                assert r.status == 200
                body = r.read().decode()
            staged = pm.transform(DataFrame(
                {"features": object_column(list(x[:1]))}))
            assert body == '{"label": %d}' % int(staged.col("prediction")[0])
        finally:
            loop.stop()
            source.close()
