"""Long-context benchmark: causal transformer train step throughput vs
sequence length on one chip (flash attention + rematerialization — the
long-context stack SURVEY.md §5 notes the reference lacks entirely; its
only sequence model is a pre-trained BiLSTM evaluated via CNTKModel).

Prints one JSON line per length; tokens/sec counts every token in the
batch per optimizer step (fwd+bwd+update).
"""

import json
import time

import numpy as np


def main():
    import jax
    import jax.numpy as jnp
    import optax

    from mmlspark_tpu.models import build_model
    from mmlspark_tpu.models.trainer import make_loss

    rng = np.random.default_rng(0)
    loss_fn = make_loss("cross_entropy")

    for T, batch in ((4096, 8), (16384, 2), (32768, 1)):
        # sequence classifier head (num_classes=8): the metric is the
        # ATTENTION-STACK train throughput (embed + L causal flash blocks,
        # fwd+bwd+adam), not causal-LM training — a per-token 32k-vocab LM
        # head would add ~2*d*V FLOPs/token on top of these numbers
        # head_dim 128 (512/4): fills the MXU's 128-deep contraction
        cfg = {"type": "transformer", "vocab_size": 32000, "d_model": 512,
               "heads": 4, "layers": 4, "num_classes": 8,
               "max_len": T, "causal": True, "remat": True,
               "attn_impl": "flash"}
        module = build_model(cfg)
        x = jnp.asarray(rng.integers(0, 32000, size=(batch, T), dtype=np.int32))
        y = jnp.asarray(rng.integers(0, 8, size=batch, dtype=np.int32))
        params = module.init(jax.random.PRNGKey(0), x[:1])
        tx = optax.adam(1e-3)
        opt_state = tx.init(params)

        @jax.jit
        def step(params, opt_state, xb, yb):
            def compute(p):
                return loss_fn(module.apply(p, xb), yb)
            loss, grads = jax.value_and_grad(compute)(params)
            upd, opt2 = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, upd), opt2, loss

        params, opt_state, loss = step(params, opt_state, x, y)
        float(loss)  # hard sync: a host fetch of the value
        n_steps = 5
        t0 = time.perf_counter()
        for _ in range(n_steps):
            params, opt_state, loss = step(params, opt_state, x, y)
        float(loss)
        dt = (time.perf_counter() - t0) / n_steps
        print(json.dumps({
            "metric": "longcontext_attention_stack_train",
            "seq_len": T,
            "batch": batch,
            "tokens_per_sec": round(batch * T / dt, 0),
            "step_ms": round(dt * 1e3, 1),
            "config": "d512 h4 L4 (head_dim 128), flash+remat, bf16-in-f32-out blocks",
        }))


if __name__ == "__main__":
    main()
