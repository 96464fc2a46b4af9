"""Quickstart: the paper's algorithm in 40 lines of public API.

    PYTHONPATH=src python examples/quickstart.py

Builds a reduced GPT-2, trains 50 steps with RMNP (Algorithm 2: momentum
EMA + row-wise l2 normalization instead of Muon's Newton-Schulz), prints
the loss curve and the preconditioner diagonal-dominance ratios that
motivate the substitution.
"""
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import cosine_with_warmup, global_dominance, make_optimizer
from repro.data.pipeline import make_stream
from repro.models import init_params
from repro.train.step import make_train_step

STEPS = 50

cfg = get_config("gpt2-small").reduced()
opt = make_optimizer("rmnp",
                     lr_matrix=cosine_with_warmup(2e-2, STEPS),
                     lr_adamw=cosine_with_warmup(3e-3, STEPS))

params = init_params(cfg, jax.random.PRNGKey(0))
opt_state = opt.init(params)
step_fn = jax.jit(make_train_step(cfg, opt, remat="none"),
                  donate_argnums=(0, 1))

stream = make_stream(cfg, seq_len=64, global_batch=8)
for step in range(STEPS):
    batch = {k: jnp.asarray(v) for k, v in next(stream).items()}
    params, opt_state, metrics = step_fn(params, opt_state, batch,
                                         jnp.int32(step))
    if step % 10 == 0 or step == STEPS - 1:
        print(f"step {step:3d}  loss {float(metrics['loss']):.4f}  "
              f"grad-norm {float(metrics['grad_norm']):.3f}")

dom = global_dominance(opt_state.momentum)
print(f"\npreconditioner dominance: r_avg={float(dom['r_avg']):.2f} "
      f"r_min={float(dom['r_min']):.2f} r_max={float(dom['r_max']):.2f}  "
      f"(paper Sec 3.2: > 1 justifies row normalization)")
