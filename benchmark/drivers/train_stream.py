"""Driver `train_stream`: one `TpuLearner.fitStream` call fed by a generator.

The traffic file gives the batch, the pool of distinct host batches made from
the seed, and how many steps are checked and warmed before the window. The
generator hands batches to the learner; what it hands over after the mark and
the time until `fitStream` has returned (final sync and host copy of the model
included) give `train_rows_per_s`.

`fitStream` offers no way to see a step's state from outside, so the generator
reads it from the running call: the locals `params`, `opt_state`, `loss` and
`steps_run` of the frame named FIT_FRAME on the main thread. That is the one
place where the benchmark knows anything inside the program (PERF.md, Open
questions: a public per-step hook would replace it). A PR that renames those
locals or donates the state they hold has to come with a `benchmark` PR that
reads them another way; NEEDS_BENCHMARK_PR says so where the read fails.
"""

import functools
import importlib
import sys
import threading
import time

import numpy as np

FIT_FRAME = "_fit_stream_core"
FIT_LOCALS = ("params", "opt_state", "loss", "steps_run")
NEEDS_BENCHMARK_PR = (
    "the benchmark reads the first steps of the running fitStream call from "
    f"the locals {FIT_LOCALS} of the frame {FIT_FRAME!r}; a change that "
    "renames them or donates their buffers needs a companion `benchmark` PR "
    "that reads them another way (PERF.md, Open questions)")
ADAM_B1 = 0.9   # optax.adamw's default, which make_optimizer leaves alone


# ------------------------------------------------------------------ traffic

def make_pool(config, traffic, seed):
    """`pool_batches` distinct (features, labels) host batches from the seed."""
    rng = np.random.default_rng(seed)
    rows, inp = traffic["batch_rows"], config["input"]
    classes = config["num_classes"]
    pool = []
    for k in range(traffic["pool_batches"]):
        if inp["kind"] == "image_uint8":
            x = rng.integers(0, 256, size=(rows, inp["height"], inp["width"],
                                           inp["channels"]), dtype=np.uint8)
        elif inp["kind"] == "token_ids_int32":
            x = rng.integers(0, config["vocab_size"],
                             size=(rows, inp["seq_len"]), dtype=np.int32)
        else:
            raise ValueError(f"unknown input kind {inp['kind']!r}")
        if traffic["label_rule"] == "sum_mod_classes":
            y = x.reshape(rows, -1).sum(axis=1, dtype=np.int64) % classes
        else:
            raise ValueError(f"unknown label rule {traffic['label_rule']!r}")
        pool.append((x, y.astype(np.int32)))
    return pool


# ------------------------------------------------- the view into the call

def fit_locals():
    """A consistent snapshot of the running fitStream loop's locals."""
    frame = sys._current_frames().get(threading.main_thread().ident)
    while frame is not None and frame.f_code.co_name != FIT_FRAME:
        frame = frame.f_back
    if frame is None:
        raise RuntimeError(f"no frame named {FIT_FRAME} on the main thread: "
                           + NEEDS_BENCHMARK_PR)
    held = frame.f_locals
    snap = dict(held)
    if isinstance(held, dict):
        # before Python 3.13 the frame keeps this dict, and with it the
        # state of the step it was read at: 12 bytes a parameter on the
        # device for as long as nobody looks again
        held.clear()
    return snap


def wait_steps(n, timeout=1500.0):
    """Block until the loop has dispatched `n` steps; return its locals."""
    t_end = time.monotonic() + timeout
    while True:
        snap = fit_locals()
        if snap.get("steps_run", 0) >= n:
            if snap["steps_run"] != n:
                raise RuntimeError(f"loop ran {snap['steps_run']} steps when "
                                   f"{n} batches had been handed over")
            if not all(k in snap for k in FIT_LOCALS):
                raise RuntimeError(NEEDS_BENCHMARK_PR)
            return snap
        if time.monotonic() > t_end:
            raise RuntimeError(f"step {n} was not dispatched in {timeout} s; "
                               + NEEDS_BENCHMARK_PR)
        time.sleep(0.002)


def leaf_paths(tree):
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return ["/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path) for path, _ in flat]


def optimizer_first_input(opt_state):
    """The first gradient as the optimizer got it, on the host, from its
    state after one step: momentum's trace is g + wd*p, Adam's mu is
    (1-b1)*g."""
    import jax
    nodes = jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda s: hasattr(s, "trace") or hasattr(s, "mu"))
    for node in nodes:
        if hasattr(node, "trace"):
            return jax.device_get(node.trace)
        if hasattr(node, "mu"):
            return jax.tree_util.tree_map(
                lambda m: m / np.float32(1.0 - ADAM_B1),
                jax.device_get(node.mu))
    raise RuntimeError("no momentum trace or Adam mu in the optimizer state: "
                       + NEEDS_BENCHMARK_PR)


# ------------------------------------------------------------------ the run

class Feed:
    """The generator fitStream pulls, and what it saw on the way."""

    def __init__(self, ctx, pool):
        self.ctx, self.pool = ctx, pool
        t = ctx["traffic"]
        self.check_steps, self.warm = t["check_steps"], t["warmup_steps"]
        self.rows = t["batch_rows"]
        self.losses, self.seen = [], {}
        self.t_mark = self.counted = None
        self.error = None

    def __call__(self):
        try:
            yield from self._batches()
        except BaseException as e:   # surfaces on the main thread, in run()
            self.error = e
            raise

    def _observe(self, k):
        import jax
        snap = wait_steps(k)
        self.losses.append(float(snap["loss"]))
        if k == 1:
            self.seen["first_input"] = optimizer_first_input(
                snap["opt_state"])
        if k == self.check_steps:
            self.seen["params_after"] = jax.device_get(snap["params"])

    def _batches(self):
        import jax
        handed = 0
        for k in range(self.check_steps):
            if k == 1:
                # first pull from the prefetch thread: the loop exists and
                # has not finished a step, so `params` is the seeded init
                snap = fit_locals()
                if "steps_run" not in snap or "params" not in snap:
                    raise RuntimeError(NEEDS_BENCHMARK_PR)
                if snap["steps_run"] != 0:
                    raise RuntimeError("the first step ended before the "
                                       "initial parameters could be read")
                self.seen["params_init"] = jax.device_get(snap["params"])
                del snap    # or the initial state stays on the device
            if k >= 1:
                self._observe(k)
            yield self.pool[k % len(self.pool)]
            handed += 1
        self._observe(self.check_steps)
        for _ in range(self.warm):
            yield self.pool[handed % len(self.pool)]
            handed += 1
        # every batch handed over so far has been dispatched; its loss on the
        # host means the device has run them all and is idle
        float(wait_steps(handed)["loss"])
        self.ctx["clock"].close_setup()
        seconds, tracer = self.ctx["seconds"], self.ctx["tracer"]
        self.counted = 0
        self.t_mark = time.perf_counter()
        while time.perf_counter() - self.t_mark < seconds:
            tracer.tick(time.perf_counter() - self.t_mark, self.counted)
            yield self.pool[handed % len(self.pool)]
            handed += 1
            self.counted += 1


def build_learner(config, traffic, seed):
    from mmlspark_tpu.models import TpuLearner
    meta = ("source", "input", "learner", "published", "reduced",
            "departures", "assumed", "rehearsal")
    model_cfg = {k: v for k, v in config.items() if k not in meta}
    lp = config["learner"]
    learner = (TpuLearner().setModelConfig(model_cfg)
               .setBatchSize(traffic["batch_rows"]).setEpochs(1)
               .setOptimizer(lp["optimizer"])
               .setLearningRate(lp["learningRate"])
               .setWeightDecay(lp.get("weightDecay", 0.0))
               .setPrecision(lp["precision"]).setLoss(lp["loss"])
               .setPrefetchDepth(traffic["prefetch_depth"])
               .setSeed(seed % (2 ** 31)))
    if "momentum" in lp:
        learner = learner.setMomentum(lp["momentum"])
    return learner


def initial_params(config, traffic, pool, seed):
    """The seeded initial parameters as fitStream makes them, for the control
    and the tests (a run reads them from the call itself)."""
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.models import build_model
    learner = build_learner(config, traffic, seed)
    module = build_model(dict(learner.getModelConfig()))
    return jax.device_get(module.init(
        jax.random.PRNGKey(learner.getSeed()), jnp.asarray(pool[0][0][:1])))


def run(ctx):
    """Drive the cell. Returns what run.py reports and a `check` to call once
    the window has closed, the peak is read and the program's state is gone."""
    config, traffic, seed = ctx["config"], ctx["traffic"], ctx["seed"]
    pool = make_pool(config, traffic, seed)
    learner = build_learner(config, traffic, seed)
    feed = Feed(ctx, pool)
    try:
        model = learner.fitStream(feed)
    except BaseException:
        if feed.error is not None:
            raise feed.error
        raise
    t_end = time.perf_counter()
    final_loss = float(model._final_loss)
    del model, learner
    window_s = t_end - feed.t_mark
    rows = feed.counted * feed.rows

    return {
        "metrics": {"train_rows_per_s": rows / window_s},
        "attempted": feed.counted, "failed": 0,
        "counters": {"window_rows": rows, "window_s": window_s,
                     "window_steps": feed.counted,
                     "rows_handed": (feed.check_steps + feed.warm
                                     + feed.counted) * feed.rows,
                     "batch_rows": feed.rows, "final_loss": final_loss},
        "check": functools.partial(check_outputs, config, traffic, pool, feed,
                                   final_loss),
    }


# ------------------------------------------------------------------ correct

def compare(seen, ref, params_init):
    """The numbers compared: `seen` (the program, or the reference in a lower
    precision or with a fault, put in its place) against the plain reference
    `ref`. Both hold the checked steps' `losses`, the optimizer's
    `first_input` and the `params_after` the last of them.

    loss_gap         the largest relative gap of a step's loss.
    grad_diff        worst leaf: the norm of the difference of the two first
                     inputs, over the reference's `block_rms` of that leaf or
                     of the median leaf, whichever is larger. The difference
                     and not the gap of the two norms: rounding that is
                     nought on average moves a norm only by its square, so a
                     gap of norms cannot tell float8 from bfloat16 (PERF.md
                     has its readings). `block_rms` and not the norm: where
                     the rows' gradients nearly cancel in the batch's,
                     rounding does not cancel with them.
    change_norm_gap  worst leaf: the gap between the two norms of the
                     parameters' change over the checked steps, over the
                     reference's norm of that leaf or of the median leaf.
                     Leaves whose reference gradient is under a thousandth of
                     the median leaf's are left out (they move by round-off
                     alone)."""
    from benchmark.reference.common import diff_norms
    loss_gaps = [abs(a - b) / abs(b)
                 for a, b in zip(seen["losses"], ref["losses"])]
    rms = ref["block_rms"]
    g_diffs = (diff_norms(seen["first_input"], ref["first_input"])
               / np.maximum(rms, np.median(rms)))
    c_ref = diff_norms(ref["params_after"], params_init)
    c_seen = diff_norms(seen["params_after"], params_init)
    moved = ref["grad_norms"] >= 1e-3 * np.median(ref["grad_norms"])
    c_gaps = (np.abs(c_seen - c_ref)
              / np.maximum(c_ref, np.median(c_ref[moved])))[moved]
    paths = leaf_paths(params_init)
    moved_paths = [p for p, m in zip(paths, moved) if m]
    return {
        "loss_gap": float(max(loss_gaps)),
        "grad_diff": float(g_diffs.max()),
        "change_norm_gap": float(c_gaps.max()),
    }, {
        "worst_leaf": {"grad_diff": paths[int(g_diffs.argmax())],
                       "change_norm_gap": moved_paths[int(c_gaps.argmax())]},
        "leaves": len(paths), "leaves_left_out": int((~moved).sum()),
        "losses": [float(x) for x in seen["losses"]],
        "reference_losses": [float(x) for x in ref["losses"]],
    }


def judge(numbers, limits):
    """`correct`, and each number beside its limit."""
    if set(limits) != set(numbers):
        raise ValueError(f"the traffic file's limits name {sorted(limits)}; "
                         f"what is compared is {sorted(numbers)}")
    compared = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in compared.values())
    return bool(ok), compared


def reference_readings(config, traffic, pool, params_init, precision="f32",
                       fault=None):
    """The plain reference over the checked steps, from the same initial
    parameters and batches."""
    family = importlib.import_module(
        f"benchmark.reference.{config['type']}")
    return family.train_steps(
        config, params_init, [pool[k % len(pool)]
                              for k in range(traffic["check_steps"])],
        block_rows=traffic["reference_block_rows"], precision=precision,
        fault=fault)


def check_outputs(config, traffic, pool, feed, final_loss):
    p0 = feed.seen.pop("params_init")
    seen = dict(feed.seen, losses=feed.losses)
    feed.seen.clear()
    ref = reference_readings(config, traffic, pool, p0)
    numbers, notes = compare(seen, ref, p0)
    ok, compared = judge(numbers, traffic["limits"])
    notes["final_loss"] = final_loss
    return ok and bool(np.isfinite(final_loss)), compared, notes


# ------------------------------------------------- the control and the faults

BELOW = {"bf16": "fp8", "f32": "bf16"}   # the nearest precision below


MUST_FAIL = ("control", "half_batch", "state_unchanged")


def stand_ins(config):
    """What is put in the program's place: the reference in the nearest
    precision below the one the configuration states (the control), the
    reference with a fault planted, each of which has to come out not
    correct, and the reference in the stated precision (a witness of what
    sound rounding alone reads, which has to pass)."""
    stated = config["learner"]["precision"]
    return {"control": {"precision": BELOW[stated]},
            "half_batch": {"fault": "half_batch"},
            "state_unchanged": {"fault": "state_unchanged"},
            "witness": {"precision": stated}}


def judge_stand_ins(config, traffic, seed, which=MUST_FAIL):
    """For one seed: the reference follows the checked steps, then each
    stand-in named does in its place, and is judged exactly as a run judges
    the program: {name: {"correct", "compared", "losses"}}."""
    pool = make_pool(config, traffic, seed)
    p0 = initial_params(config, traffic, pool, seed)
    ref = reference_readings(config, traffic, pool, p0)
    todo = stand_ins(config)
    out = {}
    for name in which:
        seen = reference_readings(config, traffic, pool, p0, **todo[name])
        numbers, _ = compare(seen, ref, p0)
        ok, compared = judge(numbers, traffic["limits"])
        out[name] = {"correct": ok, "compared": compared,
                     "losses": [float(x) for x in seen["losses"]]}
    return out
