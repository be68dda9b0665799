"""Long-context decode with the Opt-KV SkipSet as block sparsity
(DESIGN.md §5 long_500k policy): only {sink pages + sliding-window pages}
are gathered per step — the paper's Eq. 5/Eq. 9 machinery used as a
sparsity mechanism (streaming-LLM style).

Also runs the attention-free RWKV-6 path (O(1) state) for contrast.

  PYTHONPATH=src python examples/long_context_decode.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.coopt import COOPT, MODES
from repro.models import get_model


def dense_block_sparse():
    cfg = get_config("qwen3-4b-reduced")
    m = get_model(cfg)
    p = m.init(jax.random.PRNGKey(0))
    B, CTX = 1, 2048                       # stand-in for 500k on CPU
    coopt = COOPT
    cache = m.init_cache(B, CTX + 64, coopt)

    # fill a long context via chunked prefill (Sarathi-style continuation:
    # absolute positions + cross-chunk attention over the paged cache)
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, CTX), 0,
                              cfg.vocab_size)
    step = jax.jit(lambda p, b, c: m.prefill(p, b, c, coopt))
    for i in range(0, CTX, 512):
        pos = jnp.broadcast_to(jnp.arange(i, i + 512),
                               (B, 512)).astype(jnp.int32)
        logits, cache, _ = step(p, {"tokens": toks[:, i:i + 512],
                                 "positions": pos, "slot_idx": pos}, cache)
    print(f"prefilled {int(cache['length'][0])} tokens")

    dec_full = jax.jit(lambda p, b, c: m.decode_step(p, b, c, coopt))
    dec_win = jax.jit(lambda p, b, c: m.decode_step(p, b, c, coopt,
                                                    long_window=256))
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    for name, fn in [("full-attention decode", dec_full),
                     ("block-sparse decode (window 256 + sink)", dec_win)]:
        c = jax.tree.map(lambda x: x, cache)
        lg, c, _ = fn(p, {"token": tok}, c)    # compile
        t0 = time.perf_counter()
        for _ in range(8):
            lg, c, _ = fn(p, {"token": jnp.argmax(lg, -1)[:, None]
                           .astype(jnp.int32)}, c)
        lg.block_until_ready()
        dt = (time.perf_counter() - t0) / 8 * 1e3
        print(f"{name:42s} {dt:7.1f} ms/token")


def rwkv_constant_state():
    cfg = get_config("rwkv6-7b-reduced")
    m = get_model(cfg)
    p = m.init(jax.random.PRNGKey(0))
    B = 1
    cache = m.init_cache(B, 0, COOPT)      # O(1) state: no pages at all
    dec = jax.jit(lambda p, b, c: m.decode_step(p, b, c, COOPT))
    tok = jnp.zeros((B, 1), jnp.int32)
    lg, cache, _ = dec(p, {"token": tok}, cache)
    t0 = time.perf_counter()
    for _ in range(16):
        lg, cache, _ = dec(p, {"token": jnp.argmax(lg, -1)[:, None]
                            .astype(jnp.int32)}, cache)
    lg.block_until_ready()
    dt = (time.perf_counter() - t0) / 16 * 1e3
    bytes_state = sum(np.prod(v.shape) * v.dtype.itemsize
                      for v in jax.tree.leaves(cache))
    print(f"rwkv6 O(1)-state decode                    {dt:7.1f} ms/token "
          f"(state = {bytes_state/1024:.0f} KiB regardless of context)")


if __name__ == "__main__":
    dense_block_sparse()
    rwkv_constant_state()
