"""The ``lfm2moe_train_t4096`` cell's yardstick: the configuration keeps
every published number but the listed cuts, the arithmetic of the cut, the
roofline functions, how a device operation's conv scope is read, every
listed reader on a recorded trace, and that every new reader returns nothing
(and does not raise) where there is nothing to read, as on a parent tree."""

import json
import math
import os

import numpy as np
import pytest

import lfm2
import manifest
import moe
import roofline_lfm2

CELL = "lfm2moe_train_t4096"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the catalog row's top-level numbers (model-configs guide,
# architectures.jsonl, LFM2-8B-A1B)
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168, "max_position_embeddings": 128000,
    "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 24, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1,
    "use_expert_bias": True, "vocab_size": 65536}
REDUCED = {"num_hidden_layers", "layer_types", "num_dense_layers"}
NEW = ["lfm2_mfu_pct.train", "conv_mixer_ms_per_step.train",
       "conv_mixer_roofline_pct.train", "attn64_full_roofline_pct.train",
       "moe_expert_load_max.train"]
# the accepted readers that read this cell unchanged and list it
REUSED = ["collect_s.train", "trace_lower_s.train",
          "compile_or_load_s.train", "attn_full_ms_per_step.train",
          "attn_proj_ms_per_step.train", "mlp_ms_per_step.train",
          "moe_experts_ms_per_step.train", "moe_experts_roofline_pct.train",
          "moe_route_ms_per_step.train"]


@pytest.fixture(scope="module")
def cell():
    return manifest.Cell(CELL)


def test_the_configuration_keeps_every_published_number_but_the_cuts(cell):
    entry = manifest._by_name(cell.manifest["configs"], "lfm2-8b-a1b",
                              "config")
    assert set(entry["reduced"]) == REDUCED == set(cell.config["reduced"])
    c = cell.config
    for key, value in PUBLISHED.items():
        if key not in REDUCED:
            assert c[key] == value, key
    if os.path.exists(CATALOG):         # the row itself, where it is at hand
        row = next(r for r in map(json.loads, open(CATALOG))
                   if r["name"] == "LFM2-8B-A1B")
        assert entry["source"] == row["source_url"] == c["source"]
        for key, value in row["config"].items():
            if key not in REDUCED:
                assert c[key] == value, key
        assert c["layer_types"] == row["config"]["layer_types"][1:6]
    assert c["num_hidden_layers"] == 5 and c["num_dense_layers"] == 1
    assert c["published_num_hidden_layers"] == 24 \
        and c["published_num_dense_layers"] == 2
    assert c["layer_types"] == ["conv", "full_attention", "conv", "conv",
                                "conv"]
    # derived, not cut: every expert is held, the head size follows
    assert c["head_dim"] * c["num_attention_heads"] == c["hidden_size"]
    assert c["published_num_experts"] == c["num_experts"] == 32 \
        and c["held_experts"] == list(range(32))
    assert c["num_shared_experts"] == 0 and c["tie_embedding"] is True
    assert c["router_bias_init_std"] == 0.0 \
        and c["router_bias_update_rate"] == 0.03
    for key in ("assumed", "deployment", "source"):
        assert c[key]


def test_the_cut_holds_the_parameters_the_issue_counted(cell):
    ref = manifest.load_module(
        os.path.join(cell.suite, "reference", "lfm2.py"), "t_ref_lfm2")
    per_layer = {}
    for name, shape in ref.shapes(cell.config).items():
        per_layer[ref._group(name)] = per_layer.get(ref._group(name), 0) \
            + math.prod(shape)
    conv = 2048 * 6144 + 2048 * 2048 + 3 * 2048
    attention = (32 + 16) * 64 * 2048 + 2048 * 2048 + 2 * 64
    experts = 32 * 3 * 2048 * 1792
    assert (conv, attention, experts) == (16_783_360, 10_485_888,
                                          352_321_536)
    assert per_layer["0"] == conv + 3 * 2048 * 7168 + 2 * 2048 == 60_827_648
    assert per_layer["1"] == attention + 32 * 2048 + 32 + experts + 2 * 2048
    assert per_layer["2"] == per_layer["3"] == per_layer["4"] \
        == conv + 32 * 2048 + 32 + experts + 2 * 2048 == 369_174_560
    assert per_layer["top"] == 65536 * 2048 + 2048      # the table ONCE
    total = sum(per_layer.values())
    assert total == 1_665_448_064 + 4 * 32      # and 128 floats of bias
    assert round(total * 6 / 1e9, 2) == 9.99    # GB at 6 bytes
    traffic = cell.traffic
    assert (traffic["batch"], traffic["seq_len"], traffic["pool"]) \
        == (1, 4096, 4)
    assert cell.spec["job_params"]["checked_steps"] == 2
    assert cell.spec["modules"] == {"reference": "reference/lfm2.py",
                                    "system": "systems/lfm2.py"}


def test_roofline_counts(cell):
    cfg, z = cell.config, roofline_lfm2
    assert z.layers(cfg, "conv") == 4 and z.layers(cfg, "full_attention") == 1
    # 4 conv mixers' projections, one attention layer's, the dense FFN, four
    # routers with 4 experts a token, the table once
    want = 4 * 4 * 2048 * 2048 + (48 * 64 * 2048 + 2048 * 2048) \
        + 3 * 2048 * 7168 + 4 * (32 * 2048 + 4 * 3 * 2048 * 1792) \
        + 65536 * 2048
    assert z.matmul_params_per_token(cfg) == want == 432_275_456
    fl = z.attention_flops(cfg, 1, 4096)
    assert fl["fwd"] == 32 * (4096 * 4097 // 2) * 4 * 64 \
        and fl["bwd"] == 2.5 * fl["fwd"]
    by = z.attention_bytes(cfg, 1, 4096, 2)
    assert by["fwd"] == 2 * 4096 * 64 * (32 + 8 + 8 + 32) + 32 * 4096 * 4
    # a conv mixer: 16.78M matmul parameters, bound by operations (0.70 ms
    # forward at 197 TFLOP/s against 0.20 ms of bytes at 819 GB/s)
    assert z.conv_flops(cfg, 1, 4096) == {"fwd": 2 * 4 * 2048 ** 2 * 4096,
                                          "bwd": 4 * 4 * 2048 ** 2 * 4096}
    wide, mats = 4096 * 2048 * 2, 4 * 2048 ** 2 * 2
    assert z.conv_bytes(cfg, 1, 4096, 2) == {"fwd": 8 * wide + mats,
                                             "bwd": 9 * wide + 2 * mats}
    per_token = z.train_flops_per_token(cfg, 4096)
    # 10.6 TFLOP of matmuls and 0.24 of attention a step of 4096 tokens;
    # the head 3.3 of them (27% of the matmuls)
    assert round(6 * want * 4096 / 1e12, 1) == 10.6
    assert round(per_token * 4096 / 1e12, 2) == 10.86
    assert round(6 * 65536 * 2048 / (6 * want), 2) == 0.31
    # the accepted expert roofline finds every key it reads in this file
    import roofline_moe
    assert roofline_moe.expert_params(cfg) == 3 * 2048 * 1792
    assert roofline_moe.grouped_flops(cfg, 16384) \
        == 6 * 3 * 2048 * 1792 * 16384
    assert roofline_moe.grouped_bytes(cfg, 0, 0, 2) == 32 * 11_010_048 * 2


@pytest.mark.parametrize("op_name,scope", [
    ("jit(step)/jvp(HybridDecoderLM)/block0/conv/gate/mul", "gate"),
    ("jit(step)/transpose(jvp(HybridDecoderLM))/block3/conv/gate/"
     "causal_conv1d/mul", "gate"),
    ("jit(step)/jvp(HybridDecoderLM)/block2/conv/in_proj/dot_general",
     "proj"),
    ("jit(step)/transpose(jvp(HybridDecoderLM))/block4/conv/out_proj/"
     "dot_general", "proj"),
    ("jit(step)/jvp(HybridDecoderLM)/block1/attn_full/rope/mul", None),
    ("jit(step)/jvp(HybridDecoderLM)/block2/moe/route/top_k:", None),
    ("jit(step)/jvp(HybridDecoderLM)/ln_f/mul", None), ("", None)])
def test_conv_scope(op_name, scope):
    assert lfm2.conv_scope(op_name) == scope


def test_new_readers_return_nothing_where_there_is_nothing(cell, monkeypatch):
    mine = [m["name"] for m in cell.manifest["per_layer"]
            if m.get("workloads") == [CELL]]
    assert mine == NEW
    empty = {"config": cell.config, "chips": 1, "batch": 1, "seq_len": 4096,
             "peaks": manifest.load_peaks("TPU v5 lite")}
    for name in mine:
        assert cell.reader(name).read(dict(empty)) is None, name
    # the busiest expert of the profiled steps over the even 512
    even = np.full(32, 512.0, np.float32)
    busy = even.copy()
    busy[3], busy[4] = 1536.0, 0.0
    monkeypatch.setattr(moe, "STEP_COUNTS", [[busy] * 4, [even] * 4,
                                             [even, even, busy / 2, even]])
    view = dict(empty, profiled_steps=2)
    assert cell.reader("moe_expert_load_max.train").read(view) == 1.5
    assert cell.reader("moe_expert_load_max.train").read(
        dict(view, profiled_steps=3)) == 3.0
    monkeypatch.setattr(moe, "STEP_COUNTS", [])
    assert cell.reader("moe_expert_load_max.train").read(view) is None
    # with tokens and a window the utilization is a number under 100
    got = cell.reader("lfm2_mfu_pct.train").read(
        dict(empty, tokens=4096 * 180, window_s=45.0))
    assert got == pytest.approx(
        100 * 4096 * 4 * roofline_lfm2.train_flops_per_token(
            cell.config, 4096) / 197e12)
    # the other configurations read nothing from them
    for other in ("phi4flash_train_t8192", "kexaone_train_t4096",
                  "gpt2m_train_t1024"):
        cfg = manifest.Cell(other).config
        for name in mine:
            assert cell.reader(name).read(dict(
                empty, config=cfg, tokens=1, window_s=1.0,
                profiled_steps=2)) is None, (other, name)


def test_the_cell_is_in_the_manifest_with_appended_names_only(cell):
    names = [w["name"] for w in cell.manifest["workloads"]]
    assert names == ["gpt2m_train_t1024", "cgpt13_train_t2048",
                     "phi4flash_train_t8192", "kexaone_train_t4096", CELL]
    assert cell.chips == 1 \
        and all(w["chips"] == 1 for w in cell.manifest["workloads"])
    assert all(len(w["why"]) <= 200 for w in cell.manifest["workloads"])
    assert all(len(c["why"]) <= 200 for c in cell.manifest["configs"])
    assert [c["name"] for c in cell.manifest["configs"]][-1] == "lfm2-8b-a1b"
    reported = {m["name"] for m in cell.per_layer()}
    for name in NEW + REUSED + [
            "step_ms.train", "device_idle_pct.train",
            "blocks_ms_per_step.train", "head_loss_ms_per_step.train",
            "optimizer_ms_per_step.train", "unattributed_ms_per_step.train",
            "host_issue_ms_per_step.train"]:
        assert name in reported, name
    for name in ("hybrid_mfu_pct.train", "mfu_pct.train", "moe_mfu_pct.train",
                 "moe_held_load_gap.train", "moe_shared_ms_per_step.train",
                 "attn_window_ms_per_step.train",
                 "attn128_full_roofline_pct.train",
                 "ssm_scan_fwd_ms_per_step.train"):
        assert name not in reported, name
    assert {m["name"] for m in cell.end_to_end()} \
        == {"train_tokens_per_s", "setup_s"}
    for m in cell.manifest["end_to_end"] + cell.manifest["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert m["workloads"][-1] == CELL
            assert m["name"] in NEW + REUSED + ["train_tokens_per_s"]
    assert [m["name"] for m in cell.manifest["per_layer"]][-5:] == NEW


# ``data/lfm2_named_2steps.xplane.pb``: two profiled steps of a THREE-layer
# model of this family (d256, 4 query heads of 64 on 2 key/value heads, T512,
# conv + dense 512, attention + sparse, conv + sparse, 8 experts of 128 ALL
# held, top-2, no shared expert, tied vocabulary 1024, float32 logits, bf16)
# through the benchmark's own Trainer on a TPU v5e (my chip run, PR 33), cut
# as ``kexaone_named_2steps.xplane.pb`` was. The expected numbers were summed
# straight from the protobuf with regular expressions of another script's
# own, not by the code under test.
TINY = {"hidden_size": 256, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 64, "intermediate_size": 512,
        "moe_intermediate_size": 128, "num_experts": 8,
        "published_num_experts": 8, "held_experts": list(range(8)),
        "num_experts_per_tok": 2, "num_shared_experts": 0, "conv_L_cache": 3,
        "vocab_size": 1024, "num_hidden_layers": 3, "num_dense_layers": 1,
        "layer_types": ["conv", "full_attention", "conv"]}
# ms inside the two bench/train/step annotations, both steps together
SCOPES_MS = {"route": 0.010618126, "dispatch": 0.125211798,
             "experts": 0.16200453, "combine": 0.115264842,
             "balance": 0.000972188, "conv": 0.034738906,
             "gate": 0.004768046, "mlp": 0.018806406,
             "attn_full": 0.04365492}
KERNELS_MS = {"full": 0.0124275 + 0.01867}
# the tokens that chose each expert in those two steps, layer by layer
COUNTS = [[[96, 153, 168, 62, 74, 114, 200, 157],
           [169, 145, 85, 75, 134, 167, 80, 169]],
          [[149, 84, 97, 130, 140, 198, 132, 94],
           [123, 97, 176, 147, 89, 128, 152, 112]]]


def test_every_listed_reader_on_a_recorded_trace(cell, tmp_path, monkeypatch):
    import shutil
    import xplane
    name = "lfm2_named_2steps.xplane.pb"
    where = tmp_path / "plugins" / "profile" / "one"
    where.mkdir(parents=True)
    shutil.copy(os.path.join(cell.suite, "tests", "data", name), where / name)
    reduced = xplane.reduce_planes(xplane.read_planes(str(where / name)),
                                   chips=1)
    monkeypatch.setattr(moe, "STEP_COUNTS", [
        [np.asarray(c, np.float32) for c in step] for step in COUNTS])
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    view = {"trace": reduced, "trace_dir": str(tmp_path), "profiled_steps": 2,
            "config": TINY, "chips": 1, "batch": 1, "seq_len": 512,
            "peaks": peaks}

    def read(metric):
        return cell.reader(metric).read(view)

    # the new readers
    assert read("conv_mixer_ms_per_step.train") \
        == pytest.approx(SCOPES_MS["conv"] / 2, rel=1e-6)
    assert lfm2.conv_seconds(view)["gate"] * 1e3 \
        == pytest.approx(SCOPES_MS["gate"] / 2, rel=1e-6)
    # a mixer by hand, bound by BYTES at this size: 8 + 9 passes over 512 x
    # 256 bf16 values and 3 x the two matrices' 4 x 256 x 256, two layers
    moved = 17 * 512 * 256 * 2 + 3 * 4 * 256 * 256 * 2
    assert 2 * 4 * 256 ** 2 * 512 / 197e12 < (8 * 512 * 256 * 2
                                              + 4 * 256 ** 2 * 2) / 819e9
    assert read("conv_mixer_roofline_pct.train") == pytest.approx(
        100 * 2 * (moved / 819e9) / (SCOPES_MS["conv"] / 2 / 1e3), rel=1e-6)
    # attention by hand, bound by bytes too: q and o 262144 bytes each, k and
    # v 131072, the row statistic 8192; forward once, backward twice
    fwd, bwd = 2 * 262144 + 2 * 131072 + 8192, 4 * 262144 + 4 * 131072 + 16384
    assert read("attn64_full_roofline_pct.train") == pytest.approx(
        100 * ((fwd + bwd) / 819e9) / (KERNELS_MS["full"] / 2 / 1e3),
        rel=2e-4)
    # the busiest expert of the two steps: 200 of 512 x 2 / 8
    assert read("moe_expert_load_max.train") == 200 / 128
    got = cell.reader("lfm2_mfu_pct.train").read(
        dict(view, tokens=512 * 100, window_s=1.0))
    assert 0 < got < 100
    # the accepted readers that list this cell read it unchanged
    assert read("moe_experts_ms_per_step.train") \
        == pytest.approx(SCOPES_MS["experts"] / 2, rel=1e-6)
    assert read("moe_route_ms_per_step.train") == pytest.approx(
        sum(SCOPES_MS[k] for k in ("route", "dispatch", "combine",
                                   "balance")) / 2, rel=1e-6)
    assert read("attn_full_ms_per_step.train") \
        == pytest.approx(KERNELS_MS["full"] / 2, rel=2e-4)
    assert read("attn_proj_ms_per_step.train") \
        == pytest.approx(SCOPES_MS["attn_full"] / 2, rel=1e-6)
    assert read("mlp_ms_per_step.train") \
        == pytest.approx(SCOPES_MS["mlp"] / 2, rel=1e-6)
    # the grouped products by hand: 1024 pairs a layer and step whatever the
    # routing, every expert active; bound by bytes at this size (a pair
    # moves 2 x 2688 bytes through the six products; an expert's three
    # matrices are 98304 parameters, read twice and written once)
    moved = 5376 * 1024 + (2 * 8 + 8) * 98304 * 2
    assert read("moe_experts_roofline_pct.train") == pytest.approx(
        100 * 2 * (moved / 819e9) / (SCOPES_MS["experts"] / 2 / 1e3),
        rel=1e-6)
    # and the held load is the even share by construction
    assert moe.held_per_token(view) == 2.0
