"""Tables 1-2 reproduction: inference accuracy before/after LLM-CoOpt.

Paper: ARC-C/ARC-E 4-choice accuracy is preserved (±<=1pt) under LLM-CoOpt.
ARC is not on the container, so the proxy (DESIGN.md §8.5) is:

  1. train a small model briefly on the synthetic bigram corpus,
  2. build 4-choice items: (context, true continuation, 3 distractors),
  3. score each choice by decode-path log-likelihood THROUGH THE SERVING
     STACK (prefill + per-token decode against the paged cache) under each
     mode — so the fp8 cache, SkipSet writes and block-wise softmax are all
     in the measurement loop, exactly the code the paper's claim is about,
  4. report accuracy per mode + the mean |delta logit| between Original and
     CoOpt paths (a tighter proxy than 4-way accuracy).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.coopt import MODES
from repro.data import TrainPipeline
from repro.models import get_model
from repro.training import Trainer

from benchmarks.common import write_csv


def _score_choices(model, params, coopt, contexts, choices):
    """log p(choice | context) via prefill + teacher-forced decode steps."""
    n_items, ctx_len = contexts.shape
    _, n_choice, cho_len = choices.shape
    scores = np.zeros((n_items, n_choice))
    for c in range(n_choice):
        cache = model.init_cache(n_items, ctx_len + cho_len + 4, coopt)
        logits, cache, _ = model.prefill(params, {"tokens": jnp.asarray(contexts)},
                                      cache, coopt)
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        tot = np.array(jnp.take_along_axis(
            lp, jnp.asarray(choices[:, c, :1]), axis=-1))[:, 0]
        for t in range(cho_len - 1):
            tok = jnp.asarray(choices[:, c, t:t + 1], jnp.int32)
            logits, cache, _ = model.decode_step(params, {"token": tok}, cache,
                                              coopt)
            lp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
            tot += np.asarray(jnp.take_along_axis(
                lp, jnp.asarray(choices[:, c, t + 1:t + 2]), axis=-1))[:, 0]
        scores[:, c] = tot
    return scores


def run(n_items: int = 24, train_steps: int = 60, quick: bool = False):
    if quick:
        n_items, train_steps = 8, 25
    cfg = get_config("llama13b-gptq-reduced").replace(vocab_size=256)
    pipe = TrainPipeline(cfg.vocab_size, batch=8, seq_len=64, seed=0)
    tr = Trainer(cfg, lr=2e-3)
    tr.fit(pipe, steps=train_steps, log=None)
    model, params = get_model(cfg), tr.params

    # 4-choice items from held-out pipeline samples
    rng = np.random.default_rng(123)
    ctx_len, cho_len = 24, 6
    rows = []
    while sum(len(r) for r in rows) < n_items:
        rows.append(pipe.next_batch()["tokens"])
    toks = np.concatenate(rows)[:n_items]
    contexts = toks[:, :ctx_len]
    true_cont = toks[:, ctx_len:ctx_len + cho_len]
    distract = rng.integers(0, cfg.vocab_size,
                            (n_items, 3, cho_len), dtype=np.int32)
    choices = np.concatenate([true_cont[:, None], distract], axis=1)
    answer = np.zeros(n_items, np.int64)

    rows, base_scores = [], None
    for mode, coopt in MODES.items():
        sc = _score_choices(model, params, coopt, contexts, choices)
        acc = float(np.mean(np.argmax(sc, -1) == answer))
        dl = (0.0 if base_scores is None
              else float(np.mean(np.abs(sc - base_scores))))
        if mode == "original":
            base_scores = sc
        rows.append([mode, round(100 * acc, 2), round(dl, 4)])
        print(f"table12 {mode:9s} accuracy={100*acc:6.2f}%  "
              f"mean|dlogprob| vs original={dl:.4f}", flush=True)
    path = write_csv("table12_accuracy.csv",
                     ["mode", "accuracy_pct", "mean_abs_dlogprob"], rows)
    return path, rows


if __name__ == "__main__":
    run()
