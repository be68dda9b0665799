"""The system under test, built from a configuration file: the program's
model config with every size taken from the file, its seeded weights made
on the device in one jitted call, and the serving engine's ``coopt`` mode
(fp8 pool, Opt-GQA, Opt-Pa, compiled Pallas kernels).
"""
from __future__ import annotations

import dataclasses

import numpy as np

# published config key -> the program's ModelConfig field
PROGRAM_KEYS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "vocab_size": "vocab_size",
    "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta",
    "attention_bias": "qkv_bias",
    "qk_norm": "qk_norm",
}

NORMS = ("ln1", "ln2", "final_norm", "q_norm", "k_norm")
BIASES = ("bq", "bk", "bv")
# the program initialises norm scales to one and biases to zero; the
# benchmark perturbs both so that a path which drops them shows
NORM_NOISE = 0.1
BIAS_STD = 0.5
EMBED_STD = 0.02


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for ``cfg``, every size from the file."""
    from repro.configs import get_config
    base = get_config(cfg["program_arch"])
    pc = base.replace(**{f: cfg[k] for k, f in PROGRAM_KEYS.items()})
    if pc.family != "dense":
        raise ValueError(f"{cfg['name']}: the harness serves dense GQA "
                         f"decoders, not family {pc.family!r}")
    return pc


def coopt_mode():
    """The program's ``coopt`` mode with its Pallas kernels: the flag is
    set where the program still has one, and the kernels are its only
    path where it has none."""
    from repro.core.coopt import MODES
    mode = MODES["coopt"]
    if "use_kernel" in {f.name for f in dataclasses.fields(mode)}:
        mode = mode.replace(use_kernel=True)
    return mode


def key_word(seed: int) -> int:
    """A 31-bit word from any whole-number seed (numpy takes big ints)."""
    return int(np.random.SeedSequence([int(seed), 5]).generate_state(1)[0]
               & 0x7FFFFFFF)


def _leaf_name(path) -> str:
    for k in reversed(path):
        if hasattr(k, "key"):
            return str(k.key)
    raise ValueError(f"leaf {path} has no name")


def seeded_params(model, seed: int, tied: bool):
    """The model's weights, made on the device from ``seed`` in one jitted
    call, in the dtypes the program serves them in. Shapes come from
    ``jax.eval_shape(model.init)``; each weight keeps the program's init
    scale (normal, std 1/sqrt(fan-in); embedding std 0.02), norm scales
    are 1 + N(0, 0.1^2) and biases N(0, 0.5^2). ``tied``: the output head
    is the embedding's transpose, as a tied model's is."""
    import jax
    import jax.numpy as jnp

    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [_leaf_name(p) for p, _ in flat]
    specs = [s for _, s in flat]

    def gen(key):
        out = {}
        for i, (name, s) in enumerate(zip(names, specs)):
            if name == "lm_head" and tied:
                continue
            z = jax.random.normal(jax.random.fold_in(key, i), s.shape,
                                  jnp.float32)
            if name == "embed":
                x = z * EMBED_STD
            elif name in NORMS:
                x = 1.0 + NORM_NOISE * z
            elif name in BIASES:
                x = BIAS_STD * z
            elif name.startswith("w") or name == "lm_head":
                x = z * (1.0 / np.sqrt(s.shape[-2]))
            else:
                raise ValueError(f"no init rule for weight {name!r}")
            out[name] = x.astype(s.dtype)
        if tied:
            out["lm_head"] = out["embed"].T
        return jax.tree_util.tree_unflatten(treedef,
                                            [out[n] for n in names])

    key = jax.random.key(key_word(seed), impl="rbg")
    return jax.jit(gen)(key)


def named_weights(params) -> dict:
    """``{leaf name: array}`` of a dense model's weights (names are
    unique there), the plain reference's view of them."""
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    out = {}
    for p, x in flat:
        n = _leaf_name(p)
        if n in out:
            raise ValueError(f"weight name {n!r} is not unique")
        out[n] = x
    return out
