"""The ring's clock anchors, and the benchmark's timeline that reads them.

`clock/anchor` ties the tracer's `perf_counter_ns` to the Unix clock, which a
profiler capture's `profile_start_time` is on; `benchmark/host_timeline.py`
moves each idle gap of the device onto the ring's clock and cuts it by the
span the loop thread was inside. Checked against
`benchmark/fixtures/host_timeline.json`, worked out by hand (ms of the
capture; the ring's clock is the capture's + 4,500 ms through the anchor at
`perf_ns` 5 s, `unix_ns` start + 0.5 s):

    steps start at 1, 11, 21, 32, 41: the window is [11, 41), 3 whole steps
    idle gap     on the ring           the loop thread was in        cut
    [14.0,14.5)  [4514.0,4514.5)       fit/step_stats 4513.9-4514.7  loop 0.5
    [19,21)      [4519,4521)           nothing until fit/dispatch    loop 0.8
                                       4519.8-4522.0                 runtime 1.2
    [30,32)      [4530,4532)           fit/dispatch 4529-4533        runtime 2.0
    [40,41)      [4540,4541)           fit/feed_wait 4539.5-4541.2   feed 1.0

    idle 5.5 ms = window 30 - busy 24.5; a step: feed 1/3, runtime 3.2/3,
    loop 1.3/3. The producer thread's `fit/prefetch` over [4513.95,4514.6)
    is another thread's and cuts nothing. Of the other two anchors one is
    1.5 s before the capture and 40 us off, one inside it, 300 us off and
    unsound (`slack_ns` 150,000): neither may be used.
    `fit/dispatch` of the window's steps 2-5 ends at 4522, 4533, 4543, 4556
    and the capture stopped at 50 ms, 4550 on the ring: step 5 ended in its
    wake and is left out. Intervals 11, 10 ms; median 10.5, p95 10 + 0.95,
    max 11.

`benchmark/fixtures/host_timeline_lfm2moe.json.gz` is recorded: the traced run
of `lfm2moe_train_stream` on the chip (PR 36), its capture reduced to the
plane's steps and idle gaps, its whole ring, and what its result line printed.
"""

import copy
import gzip
import importlib
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import host_timeline, scope_ops, span_reduce   # noqa: E402
from mmlspark_tpu import telemetry                             # noqa: E402
from mmlspark_tpu.telemetry import tracer                      # noqa: E402

with open(os.path.join(ROOT, "benchmark", "fixtures",
                       "host_timeline.json")) as f:
    RECORDED = json.load(f)
with gzip.open(os.path.join(ROOT, "benchmark", "fixtures",
                            "host_timeline_lfm2moe.json.gz"), "rt") as f:
    ON_CHIP = json.load(f)
START, RING = RECORDED["profile_start_time"], RECORDED["ring"]
STOP = RECORDED["profile_stop_time"]
COUNTERS = {"window_steps": RECORDED["window_steps"]}
BY_HAND = {"idle_in_feed_wait_ms": 1.0 / 3, "idle_in_dispatch_ms": 3.2 / 3,
           "idle_in_loop_ms": 1.3 / 3, "step_host_interval_ms_median": 10.5,
           "step_host_interval_ms_p95": 10.95,
           "step_host_interval_ms_max": 11.0}
INTERVALS = sorted(m for m in BY_HAND if m.startswith("step_host_interval"))
UNIX0 = 1_700_000_000_000_000_000


class FakeTime:
    """The tracer's two clocks, by hand: every `perf_counter_ns()` read
    costs 100 ns, the Unix clock is `offset` ahead."""

    def __init__(self):
        self.perf, self.offset = 5_000_000_000, UNIX0

    def perf_counter_ns(self):
        self.perf += 100
        return self.perf

    def time_ns(self):
        return self.perf + self.offset

    def monotonic(self):
        return self.perf / 1e9


@pytest.fixture
def clock(monkeypatch):
    """Telemetry off and a clean ring on a clock that moves by hand."""
    telemetry.disable()
    telemetry.trace.clear()
    fake = FakeTime()
    monkeypatch.setattr(tracer, "time", fake)
    yield fake
    telemetry.disable()
    telemetry.trace.clear()


def test_an_anchor_is_taken_at_enable_and_at_most_once_a_second(clock):
    trace = telemetry.trace
    telemetry.enable()
    assert len(trace.anchors()) == 1
    telemetry.enable()                      # already on: no second one
    for ahead_s, want in ((0.2, 1), (0.7, 1), (0.2, 2), (0.9, 2), (0.2, 3),
                          (5.0, 4), (0.0, 4)):
        clock.perf += int(ahead_s * 1e9)
        trace.instant("fit/mark")
        with trace.span("fit/some"):
            pass
        assert len(trace.anchors()) == want
    perf = [a["perf_ns"] for a in trace.anchors()]
    assert all(b - a > 1_000_000_000 for a, b in zip(perf, perf[1:]))
    # the event comes before the one that brought it, with whole numbers
    names = [e["name"] for e in trace.events()]
    assert names[:3] == ["clock/anchor", "fit/mark", "fit/some"]
    assert names.count("clock/anchor") == 4


def test_an_anchor_keeps_three_whole_numbers(clock, tmp_path):
    telemetry.enable()
    (a,) = telemetry.trace.anchors()
    # reads at 5 s + 100 and + 200 ns around the Unix clock's
    assert a == {"perf_ns": 5_000_000_150, "unix_ns": UNIX0 + 5_000_000_100,
                 "slack_ns": 50}
    assert all(type(v) is int for v in a.values())
    path = str(tmp_path / "ring.jsonl")
    telemetry.trace.export_chrome_trace(path)
    (ev,) = telemetry.merge_traces([path])
    assert ev["name"] == "clock/anchor" and ev["args"] == a
    assert ev["ts"] == a["perf_ns"] // 1000


def test_telemetry_off_takes_no_anchor(clock):
    telemetry.trace.anchor()
    telemetry.trace.instant("fit/mark")
    with telemetry.trace.span("fit/some"):
        pass
    assert telemetry.trace.anchors() == [] == telemetry.trace.events()
    assert telemetry.trace.to_unix_ns(5_000_000) is None


def test_to_unix_ns_goes_through_the_nearest_anchor(clock):
    trace = telemetry.trace
    telemetry.enable()                 # perf 5,000,000,150 <-> UNIX0 + ...100
    clock.perf += 2_000_000_000
    clock.offset += 7_000              # the Unix clock was stepped by 7 us
    trace.instant("fit/mark")          # brings the second anchor
    first, second = trace.anchors()
    assert second["unix_ns"] - second["perf_ns"] \
        == first["unix_ns"] - first["perf_ns"] + 7_000
    at = lambda us: trace.to_unix_ns(us) - us * 1000
    assert at(5_000_000) == at(5_900_000) == UNIX0 - 50
    assert at(6_100_000) == at(9_000_000) == UNIX0 - 50 + 7_000


# ------------------------------------------------ benchmark/host_timeline.py

def planes(chips=1):
    device = dict(RECORDED["device"])
    for k in range(1, chips):
        device[f"/device:TPU:{k}"] = device["/device:TPU:0"]
    return host_timeline.device_planes(device)


def without(ring, *names):
    return [e for e in ring if e["name"] not in names]


def test_the_fixture_is_what_the_docstring_says():
    (plane,) = planes()
    assert plane["steps"] == 3 and plane["window_ns"] == 30e6
    assert plane["gaps"] == [(14e6, 0.5e6), (19e6, 2e6), (30e6, 2e6),
                             (40e6, 1e6)]
    assert plane["window_ns"] - plane["busy_ns"] == 5.5e6


@pytest.mark.parametrize("gap,cut,inside,name", [
    ((14e6, 0.5e6), {"loop": 0.5e6}, "fit/step_stats", "loop"),
    ((19e6, 2e6), {"loop": 0.8e6, "runtime": 1.2e6}, "fit/dispatch",
     "runtime"),
    ((30e6, 2e6), {"runtime": 2e6}, "fit/dispatch", "runtime"),
    ((40e6, 1e6), {"feed": 1e6}, "fit/feed_wait", "feed"),
])
def test_a_gap_is_cut_by_what_the_loop_thread_was_inside(gap, cut, inside,
                                                         name):
    line = host_timeline.timeline(START, RING)
    want = dict.fromkeys(host_timeline.CUTS, 0.0) | cut
    assert line.cut(gap) == pytest.approx(want, abs=1e-6)
    assert line.covering(gap)["name"] == inside
    assert host_timeline.name_gap(gap, line) == name


@pytest.mark.parametrize("chips", [1, 2])
def test_idle_time_a_step_in_three_cuts_adds_up_to_the_traces(chips):
    idle = host_timeline.idle_ms_a_step(
        planes(chips), host_timeline.timeline(START, RING))
    assert idle == pytest.approx({"feed": 1.0 / 3, "runtime": 3.2 / 3,
                                  "loop": 1.3 / 3}, rel=1e-12)
    assert sum(idle.values()) == pytest.approx((30 - 24.5) / 3, rel=1e-12)


def only_anchor(perf_ns, unix_ns, slack_ns):
    ring = without(RING, "clock/anchor")
    return ring + [{"name": "clock/anchor", "ph": "i", "ts": perf_ns // 1000,
                    "args": {"perf_ns": perf_ns, "unix_ns": unix_ns,
                             "slack_ns": slack_ns}}]


CANNOT_TIE = {
    "no anchor": (START, without(RING, "clock/anchor")),
    "an unsound anchor only": (START, only_anchor(
        5_000_000_000, START + 500_000_000, 100_001)),
    "an anchor 2.1 s before the capture": (START, only_anchor(
        2_400_000_000, START - 2_100_000_000, 180)),
    "no profile_start_time": (None, RING),
    "no fit/dispatch": (START, without(RING, "fit/dispatch")),
}


@pytest.mark.parametrize("why", sorted(CANNOT_TIE))
def test_clocks_that_cannot_be_tied_give_none(why):
    start, ring = CANNOT_TIE[why]
    line = host_timeline.timeline(start, ring)
    assert host_timeline.idle_ms_a_step(planes(), line) is None
    assert host_timeline.name_gap((14e6, 0.5e6), line) == "unattributed"


def test_an_anchor_just_inside_two_seconds_ties_them():
    ring = only_anchor(2_600_000_000, START - 1_900_000_000, 100_000)
    idle = host_timeline.idle_ms_a_step(
        planes(), host_timeline.timeline(START, ring))
    assert idle["feed"] == pytest.approx(1.0 / 3)


def test_no_whole_step_in_the_trace_gives_none():
    device = copy.deepcopy(RECORDED["device"])
    lines = device["/device:TPU:0"]
    lines["XLA Modules"] = lines["XLA Modules"][:1]
    assert host_timeline.idle_ms_a_step(
        host_timeline.device_planes(device),
        host_timeline.timeline(START, RING)) is None


def read(metric, ring, monkeypatch, stop=STOP, recorded=None):
    """What the reader of `metric` gives in a traced run whose process holds
    `ring` and whose capture is the hand-made one (or `recorded`'s)."""
    device = planes() if recorded is None else recorded["planes"]
    start = (recorded or RECORDED)["profile_start_time"]
    monkeypatch.setattr(span_reduce, "ring", lambda: ring)
    monkeypatch.setattr(scope_ops, "traced_run_file", lambda: "a.xplane.pb")
    monkeypatch.setattr(
        host_timeline, "load",
        lambda path, events: (device, host_timeline.timeline(start, events,
                                                             stop)))
    host_timeline._load_with_ring.cache_clear()
    reader = importlib.import_module(f"benchmark.layer_metrics.{metric}")
    try:
        return reader.read(None, {"window_steps": (recorded or RECORDED)[
            "window_steps"]}, None)
    finally:
        host_timeline._load_with_ring.cache_clear()


@pytest.mark.parametrize("metric", sorted(BY_HAND))
def test_a_reader_gives_the_value_worked_out_by_hand(metric, monkeypatch):
    value = read(metric, RING, monkeypatch)
    assert isinstance(value, float)
    assert value == pytest.approx(BY_HAND[metric], rel=1e-12)


@pytest.mark.parametrize("metric", sorted(BY_HAND))
def test_a_reader_gives_none_on_a_ring_without_anchors(metric, monkeypatch):
    """The parent of the PR that brought the anchors: its spans are the
    same, and every new metric has to be absent from its line."""
    assert read(metric, without(RING, "clock/anchor"), monkeypatch) is None
    assert read(metric, [], monkeypatch) is None


@pytest.mark.parametrize("metric", sorted(BY_HAND))
def test_a_capture_without_its_stop_time_reads_the_cuts_alone(metric,
                                                              monkeypatch):
    """The host's intervals end where the capture stopped: not known, no
    reading; the idle gaps need the start alone."""
    value = read(metric, RING, monkeypatch, stop=None)
    if metric in INTERVALS:
        assert value is None
    else:
        assert value == pytest.approx(BY_HAND[metric], rel=1e-12)


def test_the_wake_of_the_capture_is_left_out_of_the_host_intervals(
        monkeypatch):
    """The same ring with the capture stopping after the window's last
    dispatch end: all three intervals, 11, 10, 13 ms."""
    late = START + 60_000_000
    assert read("step_host_interval_ms_max", RING, monkeypatch,
                stop=late) == pytest.approx(13.0)
    assert read("step_host_interval_ms_median", RING, monkeypatch,
                stop=late) == pytest.approx(11.0)
    line = host_timeline.timeline(START, RING, STOP)
    assert line.stopped_ring_ns == 4_550_000_000
    assert host_timeline.timeline(START, RING).stopped_ring_ns is None


@pytest.mark.parametrize("metric", sorted(BY_HAND))
def test_a_reader_gives_what_the_chip_run_printed(metric, monkeypatch):
    """The recorded run: the three cuts as its result line printed them
    (the runtime's holds the 14 ms before every step), adding up to the
    trace's idle time a step; the host's intervals over the 9 window steps
    dispatched before the capture stopped, of 33, at the device's pace
    (`step_interval_ms_p95` 656.2), where the 24 after it ran 657-712."""
    printed = ON_CHIP["printed"]
    value = read(metric, ON_CHIP["ring"], monkeypatch,
                 stop=ON_CHIP["profile_stop_time"], recorded=ON_CHIP)
    if metric in INTERVALS:
        want = {"median": 655.1575, "p95": 656.4748, "max": 656.696}
        assert value == pytest.approx(want[metric.rsplit("_", 1)[1]],
                                      rel=1e-9)
        assert abs(value / printed["step_interval_ms_p95"] - 1) < 0.002
    else:
        assert value == pytest.approx(printed[metric], rel=1e-9)


def test_the_recorded_cuts_add_up_to_the_traces_idle_time():
    line = host_timeline.timeline(ON_CHIP["profile_start_time"],
                                  ON_CHIP["ring"])
    idle = host_timeline.idle_ms_a_step(ON_CHIP["planes"], line)
    printed = ON_CHIP["printed"]
    (plane,) = ON_CHIP["planes"]
    assert sum(idle.values()) == pytest.approx(
        (printed["window_s"] - printed["busy_s"]) * 1e3 / plane["steps"],
        rel=1e-9)
    # the gap before each of the plane's steps: wholly the runtime's, inside
    # the dispatch call of the step that follows it
    for gap in (g for g in plane["gaps"] if g[1] > 1e6):
        assert host_timeline.name_gap(gap, line) == "runtime"
        assert line.cut(gap)["runtime"] == pytest.approx(gap[1], rel=1e-6)
        assert line.covering(gap)["name"] == "fit/dispatch"


def test_the_manifest_lists_the_six_readers():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = {m["name"]: m for m in json.load(f)["per_layer"]}
    for metric in BY_HAND:
        entry = listed[metric]
        assert "workloads" not in entry
        assert (entry["source"], entry["moves"], entry["unit"]) == (
            "program_span", "train_rows_per_s", "ms")


def test_the_command_prints_each_gap_and_the_totals(tmp_path, monkeypatch,
                                                    capsys):
    ring = tmp_path / "ring.jsonl"
    ring.write_text("".join(json.dumps(e) + "\n" for e in RING))
    monkeypatch.setattr(host_timeline.trace_reduce, "load_events",
                        lambda path: RECORDED["device"])
    monkeypatch.setattr(host_timeline, "profile_times_ns",
                        lambda path: (START, STOP))
    assert host_timeline.main(["", "a.xplane.pb", str(ring)]) == 0
    out = capsys.readouterr().out.splitlines()
    assert len(out) == 5
    assert "fit/step_stats" in out[0] and "loop" in out[0]
    assert "fit/dispatch" in out[1] and "step 2 in_flight 1" in out[1]
    assert "fit/feed_wait" in out[3] and "feed" in out[3]
    totals = json.loads(out[-1])
    assert totals["steps"] == 3
    assert totals["idle_ms_a_step"]["runtime"] == pytest.approx(3.2 / 3)
