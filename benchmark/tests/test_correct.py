"""What decides `correct`, tried at the cells' rehearsal sizes on the CPU.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q

- the plain reference and the program agree to rounding when the program
  computes in float32 (the witness that the reference is the same model);
- the control (the reference in float8, put in the program's place) and the
  planted faults come out not correct through the run's own `compare` and
  `judge`, at the limits the cell itself is held to (there is one set);
- a run whose timed path is broken underneath (a step that returns its state
  unchanged; half of the batch left out, the mean taken over the rest) comes
  out with `correct` false. The two other faults of the contract's list do not
  apply: one chip has no exchange, and training produces no token or answer.
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run as brun                      # noqa: E402
from benchmark.drivers import train_stream as ts       # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def small(workload):
    cell, config, traffic = brun.load_cell(MANIFEST, workload)
    assert "limits" not in traffic["rehearsal"]
    return (brun.merge(config, config["rehearsal"]),
            brun.merge(traffic, traffic["rehearsal"]))


def last_line(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def run_cell(workload, capsys, seed=11):
    brun.main(["--workload", workload, "--seed", str(seed), "--seconds",
               "0.3", "--trace", "0", "--rehearsal", "1"])
    return last_line(capsys)


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload, capsys):
    out = run_cell(workload, capsys)
    assert out["correct"] is True, out["compared"]
    assert out["rehearsal"] is True and out["metrics"] == {}


@pytest.mark.parametrize("workload", CELLS)
def test_program_in_float32_agrees_with_reference(workload, monkeypatch,
                                                  capsys):
    real = brun.load_cell

    def in_f32(manifest, name):
        cell, config, traffic = real(manifest, name)
        config = brun.merge(config, {"learner": {"precision": "f32"}})
        return cell, config, traffic
    monkeypatch.setattr(brun, "load_cell", in_f32)
    out = run_cell(workload, capsys)
    for name, c in out["compared"].items():
        assert c["value"] < 1e-4, (name, c)


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("workload", CELLS)
def test_control_and_faults_are_not_correct(workload, seed):
    config, traffic = small(workload)
    judged = ts.judge_stand_ins(config, traffic, seed)
    assert set(judged) == set(ts.MUST_FAIL)
    for name, j in judged.items():
        assert j["correct"] is False, (name, j["compared"])


def broken_step_body(fault):
    """A stand-in for the program's step body with one fault planted."""
    from mmlspark_tpu.models import trainer
    real = trainer._make_step_body

    def make(*args, **kw):
        body = real(*args, **kw)

        def step_body(params, opt_state, xb, yb, wb):
            if fault == "half_batch":
                half = wb.shape[0] // 2
                wb = wb.at[half:].set(0.0)
            new_p, new_o, loss = body(params, opt_state, xb, yb, wb)
            if fault == "state_unchanged":
                return params, opt_state, loss
            return new_p, new_o, loss
        return step_body
    return make


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
@pytest.mark.parametrize("workload", CELLS)
def test_broken_timed_path_is_not_correct(workload, fault, monkeypatch,
                                          capsys):
    from mmlspark_tpu.models import trainer
    monkeypatch.setattr(trainer, "_make_step_body", broken_step_body(fault))
    out = run_cell(workload, capsys)
    assert out["correct"] is False, out["compared"]
