"""The Granite-4.0-H cell: its work counts tied to the published widths,
and a CPU rehearsal of `granite_4_0_h_small.rag_chat` at the program's
smoke sizes through the harness, from the cell's own files."""
import json
import os
import shutil
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402
from bench.models import granite_hybrid as G  # noqa: E402

CELL = "granite_4_0_h_small.rag_chat"


def config():
    with open(os.path.join(ROOT, "bench", "configs",
                           "granite_4_0_h_small.json")) as f:
        return json.load(f)


GH = config()


def test_config_holds_the_published_numbers_and_its_cut():
    assert GH["reduced"] == ["num_hidden_layers", "num_local_experts"]
    assert GH["published"] == {"num_hidden_layers": 40,
                               "num_local_experts": 72}
    assert GH["num_hidden_layers"] == 10 and GH["num_local_experts"] == 18
    assert len(GH["layer_types"]) == 40
    s = G.sizes(GH)
    # one whole period: 9 Mamba-2 mixers and the attention layer at 5
    assert s["kinds"].count("mamba") == 9 and s["kinds"][5] == "full"
    assert (s["E"], s["Eh"], s["k"], s["hd"], s["di"]) == (72, 18, 10,
                                                           128, 8192)


def test_parameters_bytes_and_state():
    s = G.sizes(GH)
    mixer = G._mixer_params(s)
    assert round(9 * mixer / 1e6, 1) == 920.1        # projections
    # with the conv (x||B||C, bias), gated-norm and input-norm weights and
    # the per-head dt bias, A and D: 920.6 M
    whole = mixer + 5 * 8448 + 8192 + 4096 + 3 * 128
    assert round(9 * whole / 1e6, 1) == 920.6
    assert G._attn_params(s) == 4096 * 128 * 80 == 41943040
    assert G._expert_params(s) == 3 * 4096 * 768 == 9437184
    per_layer_ffn = 18 * 9437184 + 3 * 4096 * 1536 + 4096 * 72
    assert round(10 * per_layer_ffn / 1e6) == 1890
    # every weight once, all 18 experts reached: 3.27 B parameters, the
    # float32 router at 4 bytes
    full = G.weight_bytes(GH, 10 ** 6)
    assert 6.5e9 < full < 6.6e9
    assert G.reached_experts(GH, 10 ** 6) == pytest.approx(18)
    # one decode row reaches 18 x 10/72 = 2.5 held experts
    assert G.reached_experts(GH, 1) == pytest.approx(2.5)
    assert G.held_pairs(GH, 64) == pytest.approx(160)
    # a slot: f32 SSM state 37.7 MB, conv windows over x||B||C, and one
    # attention layer's KV, 4,096 bytes a position (16.8 MB at 4,096)
    ssm = 9 * 128 * 64 * 128 * 4
    assert round(ssm / 1e6, 1) == 37.7
    assert G.state_bytes_per_row(GH) == ssm + 9 * 3 * 8448 * 2
    assert G.kv_bytes_per_token(GH) == 2 * 8 * 128 * 2 == 4096


def test_decode_prefill_and_expert_counts():
    f1, b1 = G.decode_step_work(GH, [0])
    f2, b2 = G.decode_step_work(GH, [0, 1000])
    # a second row: its 1,001 positions of KV, its state read and
    # written, and the extra held experts it reaches
    extra = (G.reached_experts(GH, 2) - G.reached_experts(GH, 1)) \
        * 10 * 2 * 9437184
    assert b2 - b1 == pytest.approx(1001 * 4096
                                    + 2 * G.state_bytes_per_row(GH)
                                    + extra)
    assert f2 > 2 * f1 * 0.99
    # prefill: about 2.77 GFLOP a token at 1.5k, the routed and shared
    # experts near a third of it
    f, b = G.prefill_work(GH, 1536)
    assert 2.6e9 < f / 1536 < 2.9e9
    fe, be = G.expert_gmm_work(GH, 1536)
    shared = 2.0 * 3 * 4096 * 1536 * 1536
    assert 0.25 < 10 * (fe + shared) / f < 0.36
    # the held experts' decode work at 64 rows is bound by their weights
    fe, be = G.expert_gmm_work(GH, 64)
    assert fe == pytest.approx(2 * 160 * 9437184)
    assert be == pytest.approx(G.reached_experts(GH, 64) * 2 * 9437184
                               + 160 * 4 * (4096 + 768))
    fk, bk = G.ssd_scan_work(GH, 0)
    assert fk == 0 and bk == 4 * 128 * 64 * 128


def test_hybrid_kernel_readers_count_their_own_layers():
    """On a hand-made slice (one prefill of 1,536 tokens, one decode
    segment of 8 steps over 64 rows): the `.hybrid` kernel shares count
    the one attention layer and the nine Mamba-2 layers, where the
    readers of every layer would count all ten."""
    import numpy as np
    from bench import trace as T
    from bench.peaks import least_seconds
    ms = 1e6
    ops = [T.Ev("%flash_attention.1 = f", 10 * ms, 4 * ms),
           T.Ev("%ssd_scan.2 = f", 20 * ms, 1 * ms),
           T.Ev("%ssd_scan.3 = f", 30 * ms, 1 * ms),
           T.Ev("%decode_attention_fused.4 = f", 120 * ms, 9 * ms)]
    mods = [T.Ev("jit_prefill(1)", 5 * ms, 90 * ms),
            T.Ev("jit_segment(2)", 110 * ms, 80 * ms)]
    tr = T.Trace(ops, mods, 0.0, 200 * ms, perf0=0.0)
    pos = [np.arange(1000, 1064) + t for t in range(8)]
    run = harness.Run(harness.load_cell(ROOT, CELL), 0.0, 0.2,
                      [harness.Launch(0.004, 0.1, length=1536)],
                      [harness.Launch(0.109, 0.195, positions=pos)],
                      {}, tr, "TPU v5 lite")
    peaks = run.peaks
    attn = sum(least_seconds(*G.decode_attention_work(GH, p), peaks)
               for p in pos)
    flash = least_seconds(*G.flash_prefill_work(GH, 1536), peaks)
    scan = least_seconds(*G.ssd_scan_work(GH, 1536), peaks)
    read = lambda n: harness.metric_reader(ROOT, n)(run)
    assert G.layer_count(GH, "full") == 1 and G.layer_count(GH, "mamba") == 9
    assert read("decode_attention_roofline.hybrid") == pytest.approx(
        100 * attn / 9e-3)
    assert read("flash_prefill_roofline.hybrid") == pytest.approx(
        100 * flash / 4e-3)
    assert read("ssd_scan_roofline.hybrid") == pytest.approx(
        100 * 9 * scan / 2e-3)
    assert read("decode_attention_roofline") == pytest.approx(
        10 * read("decode_attention_roofline.hybrid"))


def _smoke_root(tmp):
    """A root holding the benchmark's files with the granite cell at the
    program's smoke sizes and a short, light load."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp)
    shutil.copytree(os.path.join(ROOT, "bench", "metrics"),
                    os.path.join(tmp, "bench", "metrics"))
    os.makedirs(os.path.join(tmp, "bench", "configs"))
    os.makedirs(os.path.join(tmp, "bench", "traffic"))
    cfg = dict(GH, hidden_size=64, num_attention_heads=4,
               num_key_value_heads=1, intermediate_size=32,
               shared_intermediate_size=64, num_local_experts=4,
               num_experts_per_tok=3, mamba_n_heads=8, mamba_d_head=16,
               mamba_d_state=16, vocab_size=512, attention_multiplier=1 / 16,
               published=dict(GH["published"], num_local_experts=8),
               serving={"smoke": True, "slots": 4, "max_seq": 256,
                        "seg_len": 4},
               # bfloat16 at width 64 over 10 layers reads far below this
               # (see tests/test_granite_hybrid.py); the cell's own limit
               # is set at its published size on the chip
               check={"widest_gap_limit": 0.05})
    with open(os.path.join(tmp, "bench", "configs",
                           "granite_4_0_h_small.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(ROOT, "bench", "traffic", "rag_chat.json")) as f:
        mix = json.load(f)
    mix.update(rate_per_s=20.0, lead_in_s=0.2,
               prompt_tokens={"median": 24, "sigma": 0.5, "min": 8,
                              "max": 60},
               output_tokens={"median": 6, "sigma": 0.5, "min": 2,
                              "max": 16})
    mix["sampling"] = dict(mix["sampling"], greedy_every=2)
    with open(os.path.join(tmp, "bench", "traffic", "rag_chat.json"),
              "w") as f:
        json.dump(mix, f)
    return tmp


def test_cell_rehearsal(tmp_path):
    root = _smoke_root(str(tmp_path))
    out = harness.run_cell(root, CELL, 2 ** 33 + 5, 1.0, False,
                           t_process=time.perf_counter(), require_tpu=False,
                           use_cache=False, log=lambda s: None)
    assert out["correct"] is True, out["compared"]
    assert out["attempted"] > 10 and out["failed"] == 0
    assert set(out["metrics"]) == {"output_tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    cell = harness.load_cell(root, CELL)
    assert {m["name"] for m in cell.per_layer} == {
        "batch_occupancy", "kv_reserved_over_used", "segment_gap_ms.tput",
        "decode_step_ms.tput", "prefill_ms_per_ktok.tput",
        "device_idle_share.tput", "expert_gmm_roofline",
        "mfu.decode.hybrid", "mfu.prefill.hybrid",
        "decode_attention_roofline.hybrid", "flash_prefill_roofline.hybrid",
        "ssd_scan_roofline.hybrid"}
