"""The dense GQA block reads exactly what the harness read before the
blocks were split out of it (``bench/blocks/dense_gqa.py``): for both
dense configurations at the CPU test size of ``test_bench.small_cell``,
the seeded weights of two seeds, the reference's and the control's logit
gaps over one fixed sequence, and the work counts that ``step_mfu`` and
the kernel rooflines read on a fixed run are pinned bit for bit to values
recorded on the tree before the split.
"""
import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(os.path.dirname(HERE)), "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from benchlib import model, runner, spec, trace, work  # noqa: E402
from benchlib.driver import Record, StepSample  # noqa: E402
from benchlib.traffic import Req  # noqa: E402
from repro.models import get_model  # noqa: E402

SMALL = dict(hidden_size=256, intermediate_size=512, num_hidden_layers=2,
             num_attention_heads=4, num_key_value_heads=2, head_dim=64,
             vocab_size=4096)
SEEDS = (7, 2 ** 31 + 17)

# sha256 over every leaf's path, dtype, shape and bytes, in leaf order
WEIGHTS = {
    ("qwen3-4b", 7):
        "5e343f4bf1c0637762bb91d29a6f87e814b1b6beb49f41ba178b8b16a72fd8a8",
    ("qwen3-4b", 2 ** 31 + 17):
        "1c2b58e35f732b4d7c85662ec24167ed3a8a58a58200c0cfab9d3bd4a70b3aad",
    ("qwen2.5-14b", 7):
        "6f4ae1006609927820a20448a98cedb209511568279e1c5d89103ae364127596",
    ("qwen2.5-14b", 2 ** 31 + 17):
        "00bd61c1ff503c1ca4721790be854e3c7c70d8a24f46e12c033c43814b6e1b74",
}
# (sha256 of the float32 gaps, their sum): the reference's gaps of the
# served tokens and the control's, positions 40..254 of 256
GAPS = {
    "qwen3-4b": {
        "reference": ("688aa26f2fc221525acb995e1eefc1c5"
                      "5172885ead0483a34bc51bfeec60b457", 242.35816651582718),
        "control": ("075ee5a8967e07afb9ae5083310237d6"
                    "f1cde16fb9be3a4fa41bfbd36f20d9d3", 4.472922503948212)},
    "qwen2.5-14b": {
        "reference": ("2efa41100bf9b7c32a2d3f4bdec614c5"
                      "259c181b64e81eea9af29b1e50c38f00", 763.199248790741),
        "control": ("b13221f071c8d62fda567acc44c7ce6d"
                    "ba9328e71b9d18ecce181f69fee98d3e", 3.0427019596099854)},
}
# on the fixed run below; the decode cell shares qwen3-4b.chat's model
WORK = {
    "qwen3-4b.chat": {"window_flops": 19677926981632,
                      "step_mfu": 0.9988795422148223,
                      "decode_attn_roofline": 33.15782377258879,
                      "prefill_attn_roofline": 5.888529009563179},
    "qwen2.5-14b.chat": {"window_flops": 17471209881600,
                         "step_mfu": 0.8868634457664974,
                         "decode_attn_roofline": 11.073448139005722,
                         "prefill_attn_roofline": 2.442362884609417},
}
WORK["qwen3-4b.decode"] = WORK["qwen3-4b.chat"]


def small_config(name):
    cfg = spec.load_json(os.path.join(BENCH, "configs", name + ".json"))
    cfg.update(SMALL)
    return cfg


def weights_digest(params):
    h = hashlib.sha256()
    for path, x in jax.tree_util.tree_flatten_with_path(params)[0]:
        x = np.asarray(x)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(x.dtype).encode() + str(x.shape).encode())
        h.update(x.tobytes())
    return h.hexdigest()


def gaps_digest(x):
    x = np.asarray(x, np.float32)
    return (hashlib.sha256(x.tobytes()).hexdigest(),
            float(x.sum(dtype=np.float64)))


@pytest.mark.parametrize("name", ["qwen3-4b", "qwen2.5-14b"])
def test_dense_weights_and_gaps_equal_recorded(name):
    cfg = small_config(name)
    block = spec.load_block(cfg)
    mdl = get_model(model.program_config(cfg, block))
    tied = bool(cfg["tie_word_embeddings"])
    for seed in SEEDS:
        params = model.seeded_params(mdl, seed, tied, block)
        assert weights_digest(params) == WEIGHTS[name, seed], seed
    W = model.weights_by_path(params)
    arch = block.Arch.from_config(cfg)
    seq = np.random.default_rng(5).integers(0, 4096, 257, dtype=np.int32)
    inp, served = jnp.asarray(seq[:-1]), jnp.asarray(seq[1:])
    g, _ = block.gaps(W, inp, served, 40, 255, arch=arch, control=False)
    _, gc = block.gaps(W, inp, served, 40, 255, arch=arch, control=True)
    assert gaps_digest(g) == GAPS[name]["reference"]
    assert gaps_digest(gc) == GAPS[name]["control"]


def _records():
    recs = []
    for i, (plen, due, times, nc0, nc1) in enumerate([
            (300, 9.0, [9.5, 10.2, 10.4], 300, 300),
            (1200, 10.5, [13.0, 13.1, 13.2, 13.3], 0, 1200),
            (2048, 18.0, [], 512, 1536),
            (64, 11.0, [11.5] + [11.5 + 0.05 * k for k in range(1, 120)],
             0, 64),
            (3000, 5.0, [6.0 + 0.1 * k for k in range(200)], 3000, 3000)]):
        r = Record(req=Req(idx=i, prompt=np.zeros(plen, np.int32),
                           max_new=400), due=due)
        r.times = list(times)
        r.nc0, r.nc1 = nc0, nc1
        recs.append(r)
    return recs


STEPS = [StepSample(t=10.0 + 0.1 * k, kind="decode", chunks=[],
                    decode_ctx=[100 + 37 * k + 5 * j for j in range(12)])
         for k in range(20)] + \
        [StepSample(t=12.0 + 0.1 * k, kind="prefill",
                    chunks=[(512 * k, 512), (0, 77)],
                    decode_ctx=[900 + 3 * j for j in range(k + 1)])
         for k in range(4)]


@pytest.mark.parametrize("cell", sorted(WORK))
def test_dense_work_counts_equal_recorded(cell):
    """At each cell's own sizes, on a recorded chip trace and a hand-built
    run: the window's useful FLOPs and what ``step_mfu`` and both kernel
    rooflines read from them."""
    c = spec.load_cell(cell)
    rows = trace.load_rows(os.path.join(
        BENCH, "testdata", "qwen3-4b.chat.serve-trace.json.gz"))
    run = runner.RunData(cell=c, dims=c.block.Dims.from_config(c.config),
                         peaks={"bf16_flops": 197e12, "hbm_bw": 819e9},
                         t0=10.0, t1=20.0, setup_s=1.0, records=_records(),
                         steps=STEPS, trace_rows=rows)
    got = {"window_flops": work.window_flops(run),
           **{m: spec.metric_reader(m)(run) for m in
              ("step_mfu", "decode_attn_roofline", "prefill_attn_roofline")}}
    assert got == WORK[cell]
