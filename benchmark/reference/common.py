"""What the plain references share: the precision switch, the loss, the two
optimizers as their papers give them, and the loop over the checked steps.

Plain `jax.numpy` in float32 under `default_matmul_precision("highest")`.
Nothing here imports the program. `precision` is "f32" (the reference),
"bf16" (the operands and the result of every product rounded to bfloat16:
what the configurations state) or "fp8" (rounded to float8_e4m3 under a
per-tensor scale: the control, the nearest precision below bfloat16).
Rounding the operands alone is not enough of a control: in sums over
thousands of terms it averages out and reads no more than bfloat16
activations do (PERF.md, PR 25).
"""

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
PRECISIONS = ("f32", "bf16", "fp8")
FAULTS = (None, "half_batch", "state_unchanged")


def _round(a, precision):
    if precision == "bf16":
        # not a pair of casts: XLA may drop those ("excess precision")
        return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
    if precision == "fp8":
        top = float(jnp.finfo(jnp.float8_e4m3fn).max)
        s = top / jnp.maximum(jnp.max(jnp.abs(a)), 1e-30)
        return (a * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s
    raise ValueError(f"precision must be one of {PRECISIONS}")


def lowp(a, precision):
    """Round an operand or a result as a product in `precision` would hold
    it. The rounding is straight-through: the backward products see the
    rounded operands and an unrounded cotangent (rounding the cotangent to
    float8 as well gave no number at the cell's size on the chip: NaN)."""
    if precision == "f32":
        return a
    return a + jax.lax.stop_gradient(_round(a, precision) - a)


def matmul(a, b, precision):
    """A product as `precision` computes and stores it: both operands and the
    result rounded (a program "in bfloat16" keeps every activation so)."""
    return lowp(jnp.matmul(lowp(a, precision), lowp(b, precision),
                           precision=HIGHEST), precision)


def cross_entropy_sum(logits, labels):
    """Sum over rows of -log softmax(logits)[label]."""
    logp = logits - jax.scipy.special.logsumexp(logits, axis=-1,
                                                keepdims=True)
    return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def tree_map(f, *trees):
    return jax.tree_util.tree_map(f, *trees)


def momentum_update(p, g, state, step, lr, momentum, wd):
    """SGD with momentum, the decay added to the gradient first
    (Sutskever et al. 2013 as optax.sgd has it, after add_decayed_weights)."""
    u = tree_map(lambda g_, p_: g_ + wd * p_, g, p)
    trace = u if state is None else tree_map(
        lambda u_, t_: u_ + momentum * t_, u, state)
    return tree_map(lambda p_, t_: p_ - lr * t_, p, trace), trace, u


def adamw_update(p, g, state, step, lr, wd, b1=0.9, b2=0.999, eps=1e-8):
    """AdamW (Loshchilov & Hutter 2019): decoupled decay, bias-corrected."""
    m, v = state if state is not None else (
        tree_map(jnp.zeros_like, g), tree_map(jnp.zeros_like, g))
    m = tree_map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
    v = tree_map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
    c1, c2 = 1 - b1 ** step, 1 - b2 ** step
    new = tree_map(
        lambda p_, m_, v_: p_ - lr * ((m_ / c1) / (jnp.sqrt(v_ / c2) + eps)
                                      + wd * p_), p, m, v)
    return new, (m, v), g


@jax.jit
def _norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(a)))
                      for a in jax.tree_util.tree_leaves(tree)])


def norms(tree):
    """The norm of every leaf, in the order of `tree_leaves`."""
    return np.asarray(_norms(tree), dtype=np.float64)


@jax.jit
def _diff_norms(a, b):
    return _norms(tree_map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b))


def diff_norms(a, b):
    """The norm of every leaf of a - b."""
    return np.asarray(_diff_norms(a, b), dtype=np.float64)


def train_steps(forward, config, params_init, batches, block_rows,
                precision="f32", fault=None):
    """Follow the checked steps from `params_init` over `batches`.

    The gradient of the batch's mean loss is summed over blocks of
    `block_rows` rows so that it fits. Returns each step's loss; of the first
    step, what the optimizer got (`first_input`, on the host), the norms by
    leaf of the gradient, and by leaf the root mean square over the blocks of
    the norm of a block's mean gradient (`block_rms`: what the batch's
    gradient would measure if the blocks' did not cancel); and the parameters
    after the last step (`params_after`, on the host)."""
    if fault not in FAULTS:
        raise ValueError(f"fault must be one of {FAULTS}")
    lp = config["learner"]

    def block_loss(p, x, y):
        return cross_entropy_sum(forward(config, p, x, precision), y)

    grad_block = jax.jit(jax.value_and_grad(block_loss))
    add = jax.jit(lambda a, b: tree_map(jnp.add, a, b), donate_argnums=0)
    scale = jax.jit(lambda a, c: tree_map(lambda v: v * c, a),
                    donate_argnums=0)
    # a state left unchanged keeps its parameters: they are not donated then
    donated = (1, 2) if fault == "state_unchanged" else (0, 1, 2)
    if lp["optimizer"] == "momentum":
        update = jax.jit(lambda p, g, s, t: momentum_update(
            p, g, s, t, lp["learningRate"], lp["momentum"],
            lp.get("weightDecay", 0.0)), donate_argnums=donated)
    elif lp["optimizer"] == "adamw":
        update = jax.jit(lambda p, g, s, t: adamw_update(
            p, g, s, t, lp["learningRate"], lp.get("weightDecay", 0.0)),
            donate_argnums=donated)
    else:
        raise ValueError(f"no reference for optimizer {lp['optimizer']!r}")

    def to_device(tree):
        return tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)

    with jax.default_matmul_precision("highest"):
        # the parameters and the optimizer's state are donated step by step,
        # so `params_init` stays on the host until the change is taken
        p, state, losses = to_device(params_init), None, []
        out = {}
        for t, (x, y) in enumerate(batches, start=1):
            if fault == "half_batch":
                x, y = x[:len(x) // 2], y[:len(y) // 2]
            n, total, g, squares = len(x), 0.0, None, []
            for i in range(0, n, block_rows):
                xb, yb = x[i:i + block_rows], y[i:i + block_rows]
                lb, gb = grad_block(p, jnp.asarray(xb), jnp.asarray(yb))
                total += float(lb)
                if t == 1:
                    squares.append((norms(gb) / len(xb)) ** 2)
                g = gb if g is None else add(g, gb)
            g = scale(g, 1.0 / n)
            losses.append(total / n)
            if t == 1:
                out["grad_norms"] = norms(g)
                out["block_rms"] = np.sqrt(np.mean(squares, axis=0))
            new_p, state, first_input = update(p, g, state, t)
            if t == 1:
                out["first_input"] = jax.device_get(first_input)
            del first_input, g
            if fault != "state_unchanged":
                p = new_p
        del state
        out["losses"] = losses
        out["params_after"] = jax.device_get(p)
    return out
