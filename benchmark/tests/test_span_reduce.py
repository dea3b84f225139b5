"""The cut of the program's ring to the window, and the five readers, against
a small recorded ring with values worked out by hand.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_span_reduce.py -q

`fixtures/ring_cgpt_rehearsal.json`: steps 0-2 are checked (their waits on
the driver's host reads last 2,468, 467,764 and 318,659 us), 3-5 warmed, 6-11
the window (`window_steps` 6). The window opens when batch 6 is placed, at
12,688,380 + 150,919 = 12,839,299 us: item 6's production and the loop's wait
for it (from 12,688,342) straddle the driver's drain and its mark, and are
left out; item 7 (from 12,839,387) was made before the loop had woken to take
item 6 (12,840,129) and is in. What is left, in us:

    fit/feed_wait  steps 7-11   44, 19, 1964, 29, 3858      mean 1182.8
    fit/dispatch   steps 6-11   8217, 478, 424, 447, 355, 372
                                                median (424 + 447) / 2 = 435.5
    in_flight      steps 6-11   0, 1, 2, 3, 4, 5            median 2.5
    fit/prefetch   items 7-11   643, 885, 840, 718, 717     median 718
    fit/init                    7,016,234 us
"""

import copy
import importlib
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import span_reduce                      # noqa: E402

with open(os.path.join(ROOT, "benchmark", "fixtures",
                       "ring_cgpt_rehearsal.json")) as f:
    RECORDED = json.load(f)
COUNTERS = {"window_steps": RECORDED["window_steps"]}
BY_HAND = {"feed_wait_ms": 1.1828, "dispatch_host_ms": 0.4355,
           "steps_in_flight": 2.5, "feed_produce_ms": 0.718,
           "setup_init_s": 7.016234}


def read(metric, events, counters=COUNTERS, monkeypatch=None):
    monkeypatch.setattr(span_reduce, "ring", lambda: events)
    reader = importlib.import_module(f"benchmark.layer_metrics.{metric}")
    return reader.read(None, counters, None)


def numbers(cut, name, key):
    return sorted(e["args"][key] for e in cut[name])


def test_the_window_is_the_last_steps_dispatched_after_it_opened():
    cut = span_reduce.window(RECORDED["events"], 6)
    assert numbers(cut, "fit/dispatch", "step") == [6, 7, 8, 9, 10, 11]
    assert numbers(cut, "fit/feed_wait", "step") == [7, 8, 9, 10, 11]
    assert numbers(cut, "fit/prefetch", "item") == [7, 8, 9, 10, 11]
    assert numbers(cut, "fit/step", "step") == [7, 8, 9, 10, 11]


@pytest.mark.parametrize("metric", sorted(BY_HAND))
def test_a_reader_gives_the_value_worked_out_by_hand(metric, monkeypatch):
    value = read(metric, RECORDED["events"], monkeypatch=monkeypatch)
    assert isinstance(value, float)
    assert value == pytest.approx(BY_HAND[metric], rel=1e-12)


def test_set_ups_waits_do_not_reach_feed_wait_ms(monkeypatch):
    """The ring holds the hand-fed steps' waits of 0.3-0.5 s and the drain's
    0.15 s; a cut that let one in would read tens of milliseconds."""
    waits = [e["dur"] for e in RECORDED["events"]
             if e["name"] == "fit/feed_wait"]
    assert sorted(waits)[-3:] == [151787, 318659, 467764]
    assert read("feed_wait_ms", RECORDED["events"],
                monkeypatch=monkeypatch) < 2.0


@pytest.mark.parametrize("metric", sorted(BY_HAND))
def test_a_program_without_the_spans_reads_none(metric, monkeypatch):
    """The parent's ring: `fit/prefetch` with no `item`, `fit/step` from the
    feed loop only, no `fit/dispatch`, no `fit/init`."""
    parent = [{"name": "fit/prefetch", "ph": "X", "ts": 10 * k, "dur": 5,
               "tid": 2, "args": {"source": "fit-stream"}}
              for k in range(12)]
    assert read(metric, parent, monkeypatch=monkeypatch) is None
    assert read(metric, [], monkeypatch=monkeypatch) is None


@pytest.mark.parametrize("counters", [{}, {"window_steps": 0}])
def test_no_window_reads_none(counters, monkeypatch):
    assert read("dispatch_host_ms", RECORDED["events"], counters,
                monkeypatch) is None
    assert read("setup_init_s", RECORDED["events"], counters,
                monkeypatch) == pytest.approx(7.016234)


def test_only_the_rings_last_fit_is_read(monkeypatch):
    """A process that fits twice (the control tool) numbers its steps from 0
    both times; what follows the last `fit/init` is the run's fit."""
    earlier = copy.deepcopy(RECORDED["events"])
    for e in earlier:
        e["dur"] *= 100
    both = earlier + RECORDED["events"]
    for metric, value in BY_HAND.items():
        assert read(metric, both, monkeypatch=monkeypatch) == pytest.approx(
            value, rel=1e-12)


def test_a_window_longer_than_the_fit_keeps_every_step():
    cut = span_reduce.window(RECORDED["events"], 1000)
    assert numbers(cut, "fit/dispatch", "step") == list(range(12))
    # the loop's wait for the fit's first batch is then the opening
    assert numbers(cut, "fit/feed_wait", "step") == list(range(1, 12))
