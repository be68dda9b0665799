"""A block of another shape goes through the harness with new files only.

A copy of the benchmark's tree gains a configuration of the program's
reduced DeepSeek-V2-Lite (latent attention, one dense layer, then routed
and shared experts), a cell, a traffic mix and a stand-in block module
(``latent_moe_stub.py``), and nothing else in it changes. The harness
builds the program's config from the file's sizes, makes a seeded weight
for every leaf, hands the block every leaf of both segments, and serves the
cell on the CPU through ``run_cell`` up to the block's gaps and work
counts.
"""
import filecmp
import json
import os
import shutil
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(os.path.dirname(HERE)), "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from benchlib import model, runner, spec  # noqa: E402
from benchlib.driver import Record  # noqa: E402
from benchlib.traffic import Req  # noqa: E402
from repro.models import get_model  # noqa: E402

CONFIG = {
    "name": "dsv2-lite-test",
    "source": "https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/"
              "main/config.json",
    "program_arch": "deepseek-v2-lite-16b-reduced",
    "block": "latent_moe_stub",
    "hidden_size": 256, "intermediate_size": 384,
    "moe_intermediate_size": 96, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "n_routed_experts": 6, "n_shared_experts": 1, "num_experts_per_tok": 2,
    "first_k_dense_replace": 1, "kv_lora_rank": 64, "q_lora_rank": None,
    "qk_nope_head_dim": 64, "qk_rope_head_dim": 32, "v_head_dim": 64,
    "vocab_size": 1024, "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
    "tie_word_embeddings": False, "kv_page_size": 64,
}
MIX = {"loop": "open",
       "prompt": {"dist": "lognormal", "median": 20, "log_std": 0.5,
                  "min": 4, "max": 60},
       "output": {"dist": "lognormal", "median": 6, "log_std": 0.3,
                  "min": 2, "max": 10},
       "warm_in_s": 1, "order_seed": 1}
CELL = {"config": "dsv2-lite-test", "traffic": "tiny", "lanes": 2,
        "max_len": 128, "pool_pages": 8, "rate_rps": 2.0,
        "check": {"requests": 3, "min_tokens": 4, "mean_logit_gap": 0.01}}
WORKLOAD = "dsv2-lite-test.tiny"


@pytest.fixture
def tree(tmp_path, monkeypatch):
    """The benchmark's tree copied, with the new cell's files added, as
    ``spec.BENCH_DIR``; yields the cell's ``BENCHMARK.json`` entries."""
    root = tmp_path / "bench"
    shutil.copytree(BENCH, root,
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    files = {"configs/dsv2-lite-test.json": json.dumps(CONFIG),
             "traffic/tiny.json": json.dumps(MIX),
             f"cells/{WORKLOAD}.json": json.dumps(CELL)}
    for rel, text in files.items():
        (root / rel).write_text(text)
    shutil.copy(os.path.join(HERE, "latent_moe_stub.py"),
                root / "blocks" / "latent_moe_stub.py")
    monkeypatch.setattr(spec, "BENCH_DIR", str(root))
    bm = {"workloads": [{"name": WORKLOAD, "config": CONFIG["name"],
                         "traffic": "tiny", "chips": 1}],
          "end_to_end": [{"name": "output_tok_s", "unit": "tokens/s"},
                         {"name": "setup_s", "unit": "s"},
                         {"name": "step_mfu", "unit": "%"}],
          "per_layer": [{"name": "step_mfu", "unit": "%"}]}
    yield bm
    # the harness's own files are as they were: the cell took new files
    cmp = filecmp.dircmp(BENCH, root, ignore=["__pycache__", "testdata"])
    stack = [cmp]
    while stack:
        c = stack.pop()
        assert not c.diff_files and not c.left_only, (c.left, c.diff_files)
        stack.extend(c.subdirs.values())


def test_program_config_takes_the_files_sizes(tree):
    cell = spec.load_cell(WORKLOAD, tree)
    pc = model.program_config(cell.config, cell.block)
    assert pc.family == "mla"
    assert (pc.num_layers, pc.d_model, pc.d_ff, pc.vocab_size) == \
        (3, 256, 384, 1024)
    assert (pc.num_experts, pc.num_shared_experts, pc.top_k, pc.moe_d_ff,
            pc.first_dense_layers) == (6, 1, 2, 96, 1)
    assert (pc.kv_lora_rank, pc.qk_nope_head_dim, pc.qk_rope_head_dim,
            pc.v_head_dim) == (64, 64, 32, 64)
    cfg = dict(cell.config)
    del cfg["n_routed_experts"]
    with pytest.raises(spec.SpecError, match="n_routed_experts"):
        model.program_config(cfg, cell.block)


def test_config_without_a_block_is_refused(tree):
    cfg = dict(CONFIG)
    del cfg["block"]
    path = os.path.join(spec.BENCH_DIR, "configs", "dsv2-lite-test.json")
    with open(path, "w") as f:
        json.dump(cfg, f)
    with pytest.raises(spec.SpecError, match="names no block"):
        spec.load_cell(WORKLOAD, tree)
    with open(path, "w") as f:
        json.dump(dict(CONFIG, block="no_such_block"), f)
    with pytest.raises(spec.SpecError, match="no block"):
        spec.load_cell(WORKLOAD, tree)
    with open(path, "w") as f:
        json.dump(CONFIG, f)


def test_every_leaf_is_seeded_and_handed_to_the_block(tree):
    cell = spec.load_cell(WORKLOAD, tree)
    mdl = get_model(model.program_config(cell.config, cell.block))
    params = model.seeded_params(mdl, 2 ** 31 + 3, False, cell.block)
    W = model.weights_by_path(params)
    dense, moe = params["segments"]
    assert set(dense) >= {"ln1", "wq", "w_dkv", "kv_norm", "w_uk", "w_uv",
                          "wo", "ln2", "wg", "wu", "wd"}
    assert set(moe) >= {"ln1", "wq", "kv_norm", "wr", "wg_e", "wu_e",
                        "wd_e", "wg_s", "wu_s", "wd_s"}
    assert set(W) == {"embed", "final_norm", "lm_head"} | \
        {f"segments/0/{k}" for k in dense} | \
        {f"segments/1/{k}" for k in moe}
    assert W["segments/1/wg_e"].shape == (2, 6, 256, 96)
    assert W["segments/0/wq"].shape[0] == 1
    for seg in ("0", "1"):
        kv = np.asarray(W[f"segments/{seg}/kv_norm"], np.float32)
        assert 0.5 < kv.mean() < 1.5 and kv.std() > 0.05   # 1 + N(0, 0.1^2)
    assert not np.array_equal(np.asarray(W["segments/0/wq"][0]),
                              np.asarray(W["segments/1/wq"][0]))


def test_run_cell_serves_the_block_on_cpu(tree):
    cell = spec.load_cell(WORKLOAD, tree)
    stub = cell.block
    stub.SEEN.clear()
    stub.COUNTED.clear()
    res = runner.run_cell(cell, 2 ** 31 + 5, 6.0, False, time.perf_counter(),
                          require_tpu=False)
    assert res["correct"], res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"output_tok_s", "setup_s", "step_mfu"}
    assert res["metrics"]["step_mfu"]["value"] > 0
    # one reference pass per sampled request, each over every leaf
    n = res["compared"]["served_tokens_compared"]["value"]
    assert n >= CELL["check"]["min_tokens"]
    assert 1 <= len(stub.SEEN) <= CELL["check"]["requests"]
    for seen in stub.SEEN:
        assert {"segments/0/kv_norm", "segments/1/wg_e",
                "segments/1/wd_s", "embed", "lm_head"} <= set(seen)
    assert {"head_flops", "decode_flops"} <= set(stub.COUNTED)


def test_step_mfu_reads_the_blocks_work_counts(tree):
    cell = spec.load_cell(WORKLOAD, tree)
    stub = cell.block
    stub.COUNTED.clear()
    r = Record(req=Req(idx=0, prompt=np.zeros(10, np.int32), max_new=4),
               due=1.0)
    r.times, r.nc0, r.nc1 = [1.5, 1.6, 1.7], 0, 10
    run = runner.RunData(cell=cell, dims=stub.Dims.from_config(cell.config),
                         peaks={"bf16_flops": 1e9, "hbm_bw": 1e9}, t0=1.0,
                         t1=2.0, setup_s=1.0, records=[r])
    # 10 prompt positions, a first token's head, two decoded tokens
    want = (2 * 10 * 3 * 256 * 256) + 2 * 256 * 1024 + \
        2 * (2 * 3 * 256 * 256 + 2 * 256 * 1024)
    assert spec.read_metrics(tree["per_layer"], run) == {
        "step_mfu": {"value": 100.0 * want / 1e9, "unit": "%"}}
    assert sorted(set(stub.COUNTED)) == ["decode_flops", "head_flops",
                                         "prompt_flops"]
