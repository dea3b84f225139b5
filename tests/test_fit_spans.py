"""The host side of a training step, as both step loops record it.

`_fit_stream_core` (fitStream) and `_run_epochs` (fit on the host-feed path)
emit the same spans per step (`fit/step` > `fit/feed_wait`, `fit/dispatch`
on the loop's thread, `fit/prefetch` on the producer's), joined by the number
of the step in the fit; none of them waits for the device; with telemetry off
nothing is recorded and nothing is kept. Tiny shapes on the CPU backend:
counts and structure, never a time.
"""

import numpy as np
import pytest

from mmlspark_tpu import telemetry
from mmlspark_tpu.core.dataframe import DataFrame
from mmlspark_tpu.core.utils import object_column
from mmlspark_tpu.models import trainer as tr
from mmlspark_tpu.models.trainer import TpuLearner
from mmlspark_tpu.telemetry.tracer import ANCHOR_EVENT

ROWS, BATCH, EPOCHS = 96, 32, 2
STEPS = ROWS // BATCH * EPOCHS
STEP_SPANS = {"fit/init", "fit/step", "fit/feed_wait", "fit/dispatch",
              "fit/prefetch"}
PATHS = ("stream", "feed")


@pytest.fixture
def tel():
    """Enabled telemetry with clean state; restores disabled default."""
    telemetry.registry.reset()
    telemetry.trace.clear()
    telemetry.enable()
    yield telemetry
    telemetry.disable()
    telemetry.registry.reset()
    telemetry.trace.clear()


@pytest.fixture
def quiet():
    """Telemetry off (the default), with a clean ring."""
    telemetry.disable()
    telemetry.trace.clear()
    yield telemetry
    telemetry.trace.clear()


def _data():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(ROWS, 8)).astype(np.float32)
    return x, (x[:, 0] > 0).astype(np.int64)


def _learner():
    return (TpuLearner()
            .setModelConfig({"type": "mlp", "hidden": [8], "num_classes": 2})
            .setEpochs(EPOCHS).setBatchSize(BATCH).setSeed(0)
            .setLearningRate(0.1).setPrefetchDepth(2))


def fit(path):
    """STEPS optimizer steps through the stream loop or the feed loop."""
    x, y = _data()
    if path == "stream":
        def batches():
            for lo in range(0, ROWS, BATCH):
                yield x[lo:lo + BATCH], y[lo:lo + BATCH]
        return _learner().fitStream(batches)
    df = DataFrame({"features": object_column(list(x)), "label": y})
    return _learner().setDeviceDataCap(1).fit(df)   # force the host feed


def spans(name):
    return [e for e in telemetry.trace.events()
            if e["name"] == name and e["ph"] == "X"]


def recorded_names():
    """The ring's events in order, less its `clock/anchor`s."""
    return [e["name"] for e in telemetry.trace.events()
            if e["name"] != ANCHOR_EVENT]


def attr(events, key):
    return [e["args"][key] for e in events]


@pytest.fixture
def flights(monkeypatch):
    """Every `_StepsInFlight` a fit builds."""
    built = []

    class Recorded(tr._StepsInFlight):
        def __init__(self):
            super().__init__()
            built.append(self)

    monkeypatch.setattr(tr, "_StepsInFlight", Recorded)
    return built


@pytest.mark.parametrize("path", PATHS)
def test_both_loops_emit_the_same_span_set(tel, path):
    fit(path)
    names = {e["name"] for e in telemetry.trace.events()}
    assert STEP_SPANS <= names
    assert {n for n in names if n.startswith("fit/")} == STEP_SPANS
    assert attr(spans("fit/dispatch"), "step") == list(range(STEPS))


@pytest.mark.parametrize("path", PATHS)
def test_a_steps_spans_nest_and_share_its_number(tel, path):
    fit(path)
    by_step = {e["args"]["step"]: e for e in spans("fit/step")}
    for inner in spans("fit/feed_wait") + spans("fit/dispatch"):
        outer = by_step[inner["args"]["step"]]
        assert outer["tid"] == inner["tid"]
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    loop_tid = {e["tid"] for e in spans("fit/step")}
    assert len(loop_tid) == 1
    assert loop_tid.isdisjoint({e["tid"] for e in spans("fit/prefetch")})


@pytest.mark.parametrize("path", PATHS)
def test_prefetch_item_joins_dispatch_step_one_to_one(tel, path):
    """Item k is step k over the whole fit, in both loops (fitStream builds
    a prefetcher an epoch, each numbering on from the fit's step count);
    the `next()` that finds a feed exhausted is no item and no step."""
    fit(path)
    steps = attr(spans("fit/dispatch"), "step")
    assert steps == list(range(STEPS))
    assert sorted(attr(spans("fit/prefetch"), "item")) == steps
    assert attr(spans("fit/feed_wait"), "step") == steps
    assert attr(spans("fit/step"), "step") == steps
    assert set(attr(spans("fit/prefetch"), "source")) == {f"fit-{path}"}


@pytest.mark.parametrize("path", PATHS)
def test_in_flight_is_a_count_and_nothing_is_held_after_the_fit(
        tel, flights, path):
    fit(path)
    counts = attr(spans("fit/dispatch"), "in_flight")
    assert len(counts) == STEPS
    assert all(type(c) is int and c >= 0 for c in counts)
    assert counts[0] == 0
    assert all(c <= k for k, c in enumerate(counts))
    assert len(flights) == 1 and len(flights[0].losses) == 0


@pytest.mark.parametrize("path", PATHS)
def test_no_span_waits_for_the_device(tel, monkeypatch, path):
    """Telemetry on must not change the schedule: no `block_until_ready` a
    step from any span (the feed loop's `fit/step` used to sync)."""
    import jax
    calls = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda v: calls.append(1) or real(v))
    telemetry.disable()
    fit(path)
    off = len(calls)
    telemetry.enable()
    fit(path)
    assert len(calls) - off == off < STEPS
    assert len(spans("fit/dispatch")) == STEPS


@pytest.mark.parametrize("path", PATHS)
def test_telemetry_off_records_nothing_and_keeps_no_loss(
        quiet, flights, path):
    model = fit(path)
    assert np.isfinite(model._final_loss)
    assert telemetry.trace.events() == []
    assert flights == []


@pytest.mark.parametrize("path", PATHS)
def test_telemetry_off_reaches_no_record_and_takes_no_anchor(
        quiet, monkeypatch, path):
    """Off, every span is the one shared no-op, `_record` is never reached
    and so no `clock/anchor` is taken: nothing new executes."""
    from mmlspark_tpu.telemetry import tracer
    reached, opened = [], []
    monkeypatch.setattr(tracer.Tracer, "_record",
                        lambda self, ev: reached.append(ev))
    real = tracer.Tracer.span

    def span(self, name, **kw):
        opened.append(real(self, name, **kw))
        return opened[-1]

    monkeypatch.setattr(tracer.Tracer, "span", span)
    fit(path)
    assert reached == [] and telemetry.trace.anchors() == []
    assert len(opened) >= 3 * STEPS
    assert all(sp is tracer._NOOP_SPAN for sp in opened)


EXPERTS = {"type": "kimi_linear", "vocab_size": 64, "hidden_size": 32,
           "num_hidden_layers": 2, "first_k_dense_replace": 1,
           "num_attention_heads": 2,
           "linear_attn_config": {"kda_layers": [1], "full_attn_layers": [2],
                                  "head_dim": 8, "num_heads": 2,
                                  "short_conv_kernel_size": 4},
           "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
           "v_head_dim": 8, "intermediate_size": 48,
           "moe_intermediate_size": 16, "num_experts": 4, "router_width": 16,
           "first_expert_held": 0, "num_experts_per_token": 4,
           "num_shared_experts": 1, "moe_renormalize": True,
           "routed_scaling_factor": 2.446, "rms_norm_eps": 1e-5,
           "kda_chunk_size": 8, "num_classes": 2, "pool": "mean"}


def test_step_stats_spans_the_read_of_a_finished_steps_counts(tel):
    """An expert model's per-step counts come off the device in the loop's
    thread, between `fit/feed_wait` and `fit/dispatch`: `fit/step_stats` has
    that read's duration, the step's number and the counts as before, and
    lies outside every `fit/dispatch`."""
    rng = np.random.default_rng(2)
    batches = [(rng.integers(0, 64, (8, 16)).astype(np.int32),
                rng.integers(0, 2, (8,)).astype(np.int32)) for _ in range(4)]
    (TpuLearner().setModelConfig(EXPERTS).setBatchSize(8).setEpochs(1)
     .setLearningRate(1e-3).setLoss("cross_entropy").setSeed(3)
     .fitStream(lambda: iter(batches)))
    stats = spans("fit/step_stats")
    assert attr(stats, "step") == [0, 1, 2, 3]
    assert all(e["dur"] > 0 for e in stats)
    for e in stats:
        assert {"moe_expert_tokens_max", "moe_tokens_routed",
                "moe_tokens_dropped", "moe_tiles_needed",
                "moe_tiles_walked"} <= set(e["args"])
        assert all(type(v) is int for v in e["args"].values())
    (loop_tid,) = {e["tid"] for e in spans("fit/dispatch")}
    assert {e["tid"] for e in stats} == {loop_tid}
    ends = lambda e: (e["ts"], e["ts"] + e["dur"])
    for lo, hi in map(ends, stats):
        assert not any(a < hi and lo < b
                       for a, b in map(ends, spans("fit/dispatch")))


class FakeAnnotation:
    log = []

    def __init__(self, name, **attrs):
        self.name, self.attrs = name, attrs

    def __enter__(self):
        self.log.append(("enter", self.name, self.attrs))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name, self.attrs))
        return False


@pytest.fixture
def annotations(monkeypatch):
    import jax.profiler
    monkeypatch.setattr(FakeAnnotation, "log", [])
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", FakeAnnotation)
    return FakeAnnotation.log


def test_a_span_opens_and_closes_an_annotation_of_its_name(tel, annotations):
    with telemetry.trace.span("fit/outer", step=3):
        with telemetry.trace.span("fit/inner"):
            pass
    assert annotations == [("enter", "fit/outer", {"step": 3}),
                           ("enter", "fit/inner", {}),
                           ("exit", "fit/inner", {}),
                           ("exit", "fit/outer", {"step": 3})]
    assert recorded_names() == ["fit/inner", "fit/outer"]


def test_an_annotation_closes_when_the_body_raises(tel, annotations):
    with pytest.raises(KeyError):
        with telemetry.trace.span("fit/fails"):
            raise KeyError("x")
    assert [a[:2] for a in annotations] == [("enter", "fit/fails"),
                                            ("exit", "fit/fails")]


def test_a_discarded_span_records_no_event(tel, annotations):
    with telemetry.trace.span("fit/kept"):
        with telemetry.trace.span("fit/nothing_to_do") as sp:
            sp.discard()
    assert recorded_names() == ["fit/kept"]
    assert [a[:2] for a in annotations][1:3] == [
        ("enter", "fit/nothing_to_do"), ("exit", "fit/nothing_to_do")]


def test_a_disabled_span_makes_no_annotation(quiet, annotations):
    with telemetry.trace.span("fit/outer", step=3) as sp:
        sp.discard()
    telemetry.trace.instant("fit/mark")
    assert annotations == [] and sp.seconds == 0.0
    assert telemetry.trace.events() == []


def test_instant_and_complete_stay_ring_only(tel, annotations):
    import time
    telemetry.trace.instant("fit/mark")
    telemetry.trace.complete("fit/late", time.perf_counter_ns())
    assert annotations == []
    assert recorded_names() == ["fit/mark", "fit/late"]


@pytest.mark.parametrize("path", PATHS)
def test_step_histogram_is_fed_from_the_dispatch_spans_clock(tel, path):
    fit(path)
    series = telemetry.snapshot()["mmlspark_trainer_step_seconds"][
        "series"][0]
    dispatched = spans("fit/dispatch")
    assert series["count"] == len(dispatched) == STEPS
    ring_s = sum(e["dur"] for e in dispatched) / 1e6   # whole microseconds
    assert ring_s <= series["sum"] < ring_s + STEPS * 1e-6


@pytest.mark.parametrize("path,entry", [("stream", "stream"),
                                        ("feed", "fit")])
def test_fit_init_ends_where_the_step_loop_begins(tel, path, entry):
    fit(path)
    (init,) = spans("fit/init")
    assert init["args"] == {"path": entry}
    first = min(spans("fit/step"), key=lambda e: e["ts"])
    assert first["args"]["step"] == 0
    assert init["ts"] + init["dur"] <= first["ts"]
