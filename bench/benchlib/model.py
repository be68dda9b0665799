"""The system under test, built from a configuration file: the program's
model config with every size taken from the file, its seeded weights made
on the device in one jitted call, and the serving engine's ``coopt`` mode
(fp8 pool, Opt-GQA, Opt-Pa, compiled Pallas kernels).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from benchlib.spec import SpecError

# the program initialises norm scales to one and biases to zero; the
# benchmark perturbs both so that a path which drops them shows
NORM_NOISE = 0.1
BIAS_STD = 0.5
EMBED_STD = 0.02


def program_config(cfg: dict, block):
    """The program's ``ModelConfig`` for ``cfg``, every size from the file:
    each key of the block's ``PROGRAM_KEYS`` that the file has; a file
    without one of the block's ``REQUIRED`` keys is refused."""
    from repro.configs import get_config
    missing = [k for k in block.REQUIRED if k not in cfg]
    if missing:
        raise SpecError(f"{cfg['name']}: block {cfg['block']} needs "
                        f"{', '.join(missing)}")
    base = get_config(cfg["program_arch"])
    return base.replace(**{f: cfg[k] for k, f in block.PROGRAM_KEYS.items()
                           if k in cfg})


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


def seeded_params(model, seed: int, tied: bool, block):
    """The model's weights, made on the device from ``seed`` in one jitted
    call, in the dtypes the program serves them in. Shapes come from
    ``jax.eval_shape(model.init)``; leaf ``i`` draws one normal array from
    ``fold_in(key, i)``. Each weight keeps the program's init scale
    (normal, std 1/sqrt(fan-in); embedding std 0.02); the leaves the
    block names in ``NORMS`` are 1 + N(0, 0.1^2) and in ``BIASES``
    N(0, 0.5^2). ``tied``: the output head is the embedding's transpose,
    as a tied model's is."""
    import jax
    import jax.numpy as jnp

    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [_leaf_name(p) for p, _ in flat]
    specs = [s for _, s in flat]

    def gen(key):
        out = [None] * len(names)
        for i, (name, s) in enumerate(zip(names, specs)):
            if name == "lm_head" and tied:
                continue
            z = jax.random.normal(jax.random.fold_in(key, i), s.shape,
                                  jnp.float32)
            if name == "embed":
                x = z * EMBED_STD
            elif name in block.NORMS:
                x = 1.0 + NORM_NOISE * z
            elif name in block.BIASES:
                x = BIAS_STD * z
            elif name.startswith("w") or name == "lm_head":
                x = z * (1.0 / np.sqrt(s.shape[-2]))
            else:
                raise ValueError(f"no init rule for weight {name!r}")
            out[i] = x.astype(s.dtype)
        if tied:
            out[names.index("lm_head")] = out[names.index("embed")].T
        return jax.tree_util.tree_unflatten(treedef, out)

    key = jax.random.key(key_word(seed), impl="rbg")
    return jax.jit(gen)(key)


def weights_by_path(params) -> dict:
    """``{path: array}`` of every leaf of the model's weights, the path's
    keys joined by "/" (``embed``, ``segments/1/wq``): the plain
    reference's view of them."""
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    return {"/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in p): x for p, x in flat}
