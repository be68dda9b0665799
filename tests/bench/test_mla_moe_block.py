"""The DeepSeek-V2 block of the benchmark (``bench/blocks/mla_moe.py``):
its configuration maps onto the program, its plain reference agrees with
the program served through the latent pool, its control does not, and its
work counts and the readers of its three metrics read what they state.

The agreement runs at a reduced size on the CPU (every published key of
the configuration kept, widths cut), on the benchmark's own seeded
weights: a prompt prefilled in chunks through the fp8 latent pool, then
decoded greedily from it, with the program's Pallas kernels interpreted.
"""
import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from benchlib import model as bm  # noqa: E402
from benchlib import runner, spec  # noqa: E402
from benchlib.driver import StepSample  # noqa: E402
from repro.core.opt_kv import identity_slots  # noqa: E402
from repro.models import get_model  # noqa: E402
from repro.serving import steplog  # noqa: E402

CELL = "deepseek-v2-lite.decode"
# the configuration's widths cut for the CPU; every other key as published
SMALL = dict(name="dsv2-lite-small", hidden_size=256, intermediate_size=384,
             moe_intermediate_size=96, num_hidden_layers=3,
             num_attention_heads=4, num_key_value_heads=4,
             n_routed_experts=16, kv_lora_rank=128, qk_nope_head_dim=64,
             qk_rope_head_dim=64, v_head_dim=64, vocab_size=1024)
# a mean gap between the sound program's and the control's (read 0.009
# and 0.052 here): dropping YaRN reads 0.77, renormalising the top 6 0.046
LIMIT = 0.02


@pytest.fixture(scope="module")
def cell():
    return spec.load_cell(CELL)


def test_configuration_maps_onto_the_program(cell):
    pc = bm.program_config(cell.config, cell.block)
    assert (pc.family, pc.num_layers, pc.first_dense_layers) == ("mla", 7, 1)
    assert (pc.d_model, pc.d_ff, pc.moe_d_ff, pc.vocab_size) == \
        (2048, 10944, 1408, 102400)
    assert (pc.num_experts, pc.top_k, pc.num_shared_experts,
            pc.norm_topk_prob) == (64, 6, 2, False)
    assert (pc.kv_lora_rank, pc.qk_nope_head_dim, pc.qk_rope_head_dim,
            pc.v_head_dim, pc.norm_eps, pc.rope_theta) == \
        (512, 128, 64, 128, 1e-6, 10000)
    assert dict(pc.rope_scaling) == cell.config["rope_scaling"]
    arch = cell.block.Arch.from_config(cell.config)
    assert cell.block.softmax_scale(arch) == pytest.approx(0.1147214,
                                                           abs=1e-7)
    with pytest.raises(ValueError, match="sigmoid"):
        cell.block.Arch.from_config(dict(cell.config, scoring_func="sigmoid"))
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == "deepseek-v2-lite")
    assert entry["reduced"] == cell.config["reduced"] == ["num_hidden_layers"]


def _serve(mdl, params, prompt, steps, chunk=16):
    """Program logits: the prompt's last position after a chunked prefill
    through the pool, then each greedy decode step's."""
    coopt = bm.coopt_mode()
    cache = mdl.init_cache(1, 256, coopt)
    P = cache["kv"].shape[1]
    for i in range(0, len(prompt), chunk):
        n = min(chunk, len(prompt) - i)
        pos = jnp.arange(i, i + n, dtype=jnp.int32)[None]
        lg, cache, _ = mdl.prefill(params, {
            "tokens": jnp.asarray(prompt[None, i:i + n]), "positions": pos,
            "slot_idx": identity_slots(1, pos, P, coopt.page_size),
            "cache_len": jnp.full((1,), i + n, jnp.int32),
            "last_pos": jnp.full((1,), n - 1, jnp.int32)}, cache, coopt)
    out = [np.asarray(lg[0], np.float32)]
    toks = list(prompt) + [int(np.argmax(out[-1]))]
    for _ in range(steps - 1):
        p = len(toks) - 1
        pos = jnp.full((1, 1), p, jnp.int32)
        lg, cache, _ = mdl.decode_step(params, {
            "token": jnp.asarray([[toks[-1]]], jnp.int32), "positions": pos,
            "slot_idx": identity_slots(1, pos, P, coopt.page_size),
            "cache_len": jnp.full((1,), p + 1, jnp.int32)}, cache, coopt)
        out.append(np.asarray(lg[0], np.float32))
        toks.append(int(np.argmax(out[-1])))
    return np.stack(out), toks


def test_reference_agrees_with_the_program_and_the_control_does_not(cell):
    cfg = dict(cell.config, **SMALL)
    block = cell.block
    pc = bm.program_config(cfg, block)
    mdl = get_model(pc)
    params = bm.seeded_params(mdl, 2 ** 31 + 11, False, block)
    prompt = np.random.default_rng(0).integers(0, 1024, 40).astype(np.int32)
    steps = 12
    got, toks = _serve(mdl, params, prompt, steps)

    W = bm.weights_by_path(params)
    arch = block.Arch.from_config(cfg)
    L, lo, hi = 256, len(prompt) - 1, len(prompt) - 1 + steps
    inp = np.zeros(L, np.int32)
    inp[:len(toks) - 1] = toks[:-1]
    h = block.hidden(W, jnp.asarray(inp), arch, False)
    want = np.asarray(block.mm(h, W["lm_head"]))[lo:hi]
    # logits: bf16 weights and activations and the fp8 pool leave an rms
    # difference of 1.6% of the largest logit; renormalising the top 6
    # reads 3.0%, plain rope 16%
    rms = np.sqrt(np.mean((got - want) ** 2))
    assert rms < 0.025 * np.abs(want).max()

    served = np.zeros(L, np.int32)
    served[lo:hi] = toks[len(prompt):]
    g, gc = block.gaps(W, jnp.asarray(inp), jnp.asarray(served), lo, hi,
                       arch=arch, control=True)
    mean, mean_c = float(np.sum(g)) / steps, float(np.sum(gc)) / steps
    assert mean < LIMIT < mean_c, (mean, mean_c)


def test_work_counts_at_the_published_widths(cell):
    b = cell.block
    d = b.Dims.from_config(cell.config)
    assert b.latent_token_bytes(d) == 584
    assert b.expert_bytes(d) == 3 * 2048 * 1408 * 2
    # 32 tokens hit 61.3 of 64 experts in expectation
    assert b.experts_hit(d, 32) == pytest.approx(61.26, abs=0.01)
    assert b.moe_step_flops(d, 32) == 6 * 32 * 6 * 2 * 3 * 2048 * 1408
    assert b.decode_attn_bytes(d, 1000) == 584 * 1000 + 4 * 16 * 576 \
        + 4 * 16 * 512
    assert b.attn_flops(d, 1) == 2 * 16 * (576 + 512)
    assert b.decode_flops(d, 1) == b.prompt_flops(d, 0, 1) + b.head_flops(d)


def _rows(ops, t0=0.0, t1=1e9):
    """Synthetic trace rows: a benchmark span over the window and each
    (name, start ns, duration ns) as a device op."""
    rows = [("/host:CPU", "main", "bench.turn", t0, t1 - t0)]
    rows += [("/device:TPU:0", "XLA Ops", f"%{n} = bf16[1] custom-call()",
              s, dur) for n, s, dur in ops]
    return rows


def _run(cell, steps=(), rows=None):
    return runner.RunData(cell=cell,
                          dims=cell.block.Dims.from_config(cell.config),
                          peaks={"bf16_flops": 197e12, "hbm_bw": 819e9},
                          t0=10.0, t1=20.0, setup_s=1.0, records=[],
                          steps=list(steps), trace_rows=rows)


def test_kernel_roofline_readers_read_their_kernels(cell):
    b, d = cell.block, cell.block.Dims.from_config(cell.config)
    steps = [StepSample(t=0, kind="decode", chunks=[],
                        decode_ctx=[1000] * 32),
             StepSample(t=0, kind="prefill", chunks=[(0, 400)],
                        decode_ctx=[2000] * 8)]
    rows = _rows([("_moe_experts.7", 1e6, 4e6), ("_moe_experts.7", 6e6, 6e6),
                  ("_paged_latent_decode_single.1", 20e6, 2e6),
                  ("fusion.3", 30e6, 5e6)])
    run = _run(cell, steps, rows)
    peaks = run.peaks
    need = sum(max(b.moe_step_flops(d, n) / peaks["bf16_flops"],
                   b.moe_step_bytes(d, n) / peaks["hbm_bw"]) for n in (32, 408))
    assert spec.metric_reader("moe_ffn_roofline")(run) == \
        pytest.approx(100 * need / 10e-3)
    lat = 7 * max(32 * b.attn_flops(d, 1000) / peaks["bf16_flops"],
                  32 * b.decode_attn_bytes(d, 1000) / peaks["hbm_bw"])
    assert spec.metric_reader("latent_decode_roofline")(run) == \
        pytest.approx(100 * lat / 2e-3)
    # a program without the kernels, or no trace: nothing to read
    for name in ("moe_ffn_roofline", "latent_decode_roofline"):
        assert spec.metric_reader(name)(
            _run(cell, steps, _rows([("fusion.3", 1e6, 5e6)]))) is None
        assert spec.metric_reader(name)(_run(cell, steps)) is None


def test_expert_load_ratio_reads_the_step_log(cell, monkeypatch):
    ring = steplog.StepRing(maxlen=16)
    monkeypatch.setattr(steplog, "RECENT", ring)

    def step(t, real, most):
        return steplog.StepRecord(engine=0, seq=0, kind="decode", rows=32,
                                  bucket=1, real_tokens=real, chunks=(),
                                  decode_ctx=(), req_ids=(), t_dispatched=t,
                                  expert_tokens_max=most, experts_hit=60.0)

    read = spec.metric_reader("expert_load_ratio")
    ring.extend([step(9.0, 32, 30), step(11.0, 32, 6), step(12.0, 32, 9),
                 step(13.0, 64, 12), step(21.0, 32, 30)])
    # even load: 32 x 6 / 64 = 3 (64 tokens: 6); ratios 2, 3, 2
    assert read(_run(cell)) == pytest.approx(2.0)
    ring.clear()
    ring.append(step(11.0, 32, 0))      # a program that counts nothing
    assert read(_run(cell)) is None
