"""What decides ``correct``: the served tokens of a sample of the requests
that the window finished (and, where the cell asks, of those still in
flight at its close), held against the plain reference.

Each sample is drawn from the seed and always holds the longest request.
For each request the reference runs once over its prompt and its served
tokens (padded to the cell's ``max_len``; causality keeps the pad out),
and each served token reads how far its reference logit lies below the
reference's best at that position. Greedy decoding serves the top token,
so a sound program reads rounding there; a wrong token reads the gap of
whatever it put first. The mean gap over every served token of the sample
is compared with the cell's limit: random-weight logits are near-tied, so
the widest gap reads bf16 rounding about as high as a broken path.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np


def sample(finished: List[Tuple[np.ndarray, List[int]]], count: int,
           seed: int) -> List[int]:
    """Indices of ``count`` (prompt, tokens) pairs: the longest, then
    others in an order drawn from ``seed``."""
    if count <= 0:
        return []
    if not finished:
        return []
    longest = max(range(len(finished)),
                  key=lambda i: len(finished[i][0]) + len(finished[i][1]))
    rest = [i for i in range(len(finished)) if i != longest]
    order = np.random.default_rng(
        np.random.SeedSequence([int(seed), 7])).permutation(len(rest))
    return [longest] + [rest[j] for j in order[:max(count - 1, 0)]]


def readings(W, arch, pairs: List[Tuple[np.ndarray, List[int]]],
             length: int, control: bool = False) -> Dict[str, float]:
    """Run the reference over each (prompt, served tokens) pair padded to
    ``length``. Returns the mean and the widest gap of a served token
    (``mean_gap``, ``max_gap``), the share of served tokens that are not
    the reference's top one (``flipped``), how many were compared, and with
    ``control`` the same of the control's top tokens (``control_*``)."""
    import jax.numpy as jnp
    from benchlib import reference

    worst, worst_c, n = 0.0, 0.0, 0
    tot, tot_c, flip, flip_c = 0.0, 0.0, 0, 0
    for prompt, toks in pairs:
        seq = np.concatenate([prompt, np.asarray(toks, np.int32)])
        if len(seq) - 1 > length:
            raise ValueError(f"a request of {len(seq) - 1} positions does "
                             f"not fit the reference's {length}")
        inp = np.zeros(length, np.int32)
        inp[:len(seq) - 1] = seq[:-1]
        served = np.zeros(length, np.int32)
        lo, hi = len(prompt) - 1, len(seq) - 1
        served[lo:hi] = seq[len(prompt):]
        g, gc = reference.gaps(W, jnp.asarray(inp), jnp.asarray(served),
                               lo, hi, arch=arch, control=control)
        g, gc = np.asarray(g[lo:hi]), np.asarray(gc[lo:hi])
        worst = max(worst, float(g.max()))
        tot += float(g.sum())
        flip += int((g > 0).sum())
        if control:
            worst_c = max(worst_c, float(gc.max()))
            tot_c += float(gc.sum())
            flip_c += int((gc > 0).sum())
        n += hi - lo
    n_ = max(n, 1)
    out = {"mean_gap": tot / n_, "max_gap": worst, "flipped": flip / n_,
           "tokens_compared": n}
    if control:
        out.update(control_mean_gap=tot_c / n_, control_max_gap=worst_c,
                   control_flipped=flip_c / n_)
    return out
