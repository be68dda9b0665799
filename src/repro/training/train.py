"""Training loop: cross-entropy LM loss (+ MoE aux losses) + AdamW.

``make_train_step`` returns the pure step function the launcher lowers for
the train_4k dry-run shape; ``Trainer`` is the host-side loop used by the
end-to-end example (reduced model, a few hundred steps on CPU).
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.coopt import CoOptConfig, COOPT
from repro.models import get_model
from repro.training.optimizer import AdamWState, adamw_init, adamw_update


def loss_fn(model, params, batch, coopt: CoOptConfig,
            moe_lb_weight: float = 0.01, moe_z_weight: float = 1e-3):
    logits, aux = model.forward(params, batch, coopt)
    labels = batch["labels"]
    lf = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(lf, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    loss = jnp.mean(nll)
    metrics = {"nll": loss}
    if aux and "load_balance" in aux:
        loss = loss + moe_lb_weight * aux["load_balance"] \
                    + moe_z_weight * aux["router_z"]
        metrics.update(load_balance=aux["load_balance"],
                       router_z=aux["router_z"])
    metrics["loss"] = loss
    return loss, metrics


def make_train_step(cfg: ModelConfig, coopt: CoOptConfig = COOPT, *,
                    lr: float = 3e-4, weight_decay: float = 0.1,
                    grad_clip: float = 1.0,
                    num_microbatches: int = 1) -> Callable:
    """(params, opt_state, batch) -> (params, opt_state, metrics).

    ``num_microbatches > 1`` = gradient accumulation: the global batch is
    split on its leading axis and scanned, so per-step activation memory
    scales by 1/n while the optimizer math is unchanged (grads averaged in
    f32). EXPERIMENTS.md §Perf P0 — this is what makes the train_4k shapes
    fit v5e HBM.
    """
    model = get_model(cfg)

    def grads_of(params, batch):
        return jax.value_and_grad(
            lambda p: loss_fn(model, p, batch, coopt), has_aux=True)(params)

    def train_step(params, opt_state: AdamWState, batch):
        if num_microbatches == 1:
            (loss, metrics), grads = grads_of(params, batch)
        else:
            n = num_microbatches
            micro = {k: v.reshape(n, v.shape[0] // n, *v.shape[1:])
                     for k, v in batch.items()}

            def body(acc, mb):
                (loss, metrics), g = grads_of(params, mb)
                acc = jax.tree.map(
                    lambda a, x: a + x.astype(jnp.float32) / n, acc, g)
                return acc, (loss, metrics)

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            grads, (losses, metricses) = jax.lax.scan(body, zeros, micro)
            metrics = jax.tree.map(lambda x: jnp.mean(x, 0), metricses)
        params, opt_state, gnorm = adamw_update(
            params, grads, opt_state, lr=lr, weight_decay=weight_decay,
            grad_clip=grad_clip)
        metrics["grad_norm"] = gnorm
        return params, opt_state, metrics

    return train_step


@dataclass
class Trainer:
    cfg: ModelConfig
    coopt: CoOptConfig = COOPT
    lr: float = 3e-4
    seed: int = 0

    def __post_init__(self):
        self.model = get_model(self.cfg)
        self.params = self.model.init(jax.random.PRNGKey(self.seed))
        self.opt_state = adamw_init(self.params)
        self._step = jax.jit(make_train_step(self.cfg, self.coopt,
                                             lr=self.lr))
        self.history = []

    def step(self, batch: Dict[str, Any]) -> Dict[str, float]:
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        self.params, self.opt_state, metrics = self._step(
            self.params, self.opt_state, batch)
        out = {k: float(v) for k, v in metrics.items()}
        self.history.append(out)
        return out

    def fit(self, batches, steps: int, log_every: int = 10,
            log: Optional[Callable[[str], None]] = print):
        it = iter(batches)
        t0 = time.perf_counter()
        for i in range(steps):
            m = self.step(next(it))
            if log and (i % log_every == 0 or i == steps - 1):
                log(f"step {i:4d}  loss {m['loss']:.4f}  "
                    f"nll {m['nll']:.4f}  gnorm {m['grad_norm']:.3f}  "
                    f"({time.perf_counter() - t0:.1f}s)")
        return self.history
