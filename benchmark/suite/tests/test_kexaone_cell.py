"""The ``kexaone_train_t4096`` cell's yardstick: the configuration keeps
every published number but the listed cuts, the arithmetic of the cut, the
roofline functions, how a device operation's scope is read, the new readers
on a recorded trace, and that every new reader returns nothing (and does not
raise) where there is nothing to read, as on a parent tree."""

import json
import math
import os

import numpy as np
import pytest

import manifest
import moe
import roofline_moe

CELL = "kexaone_train_t4096"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the catalog row's top-level numbers (model-configs guide,
# architectures.jsonl, K-EXAONE-236B-A23B)
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 128, "hidden_size": 6144,
    "intermediate_size": 18432, "max_position_embeddings": 262144,
    "moe_intermediate_size": 2048, "n_group": 1, "norm_topk_prob": True,
    "num_attention_heads": 64, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 8,
    "num_nextn_predict_layers": 1, "num_shared_experts": 1,
    "rms_norm_eps": 1e-05, "routed_scaling_factor": 2.5,
    "sliding_window": 128, "tie_word_embeddings": False, "topk_group": 1,
    "vocab_size": 153600, "hidden_act": "silu", "scoring_func": "sigmoid",
    "model_type": "exaone_moe", "sliding_window_pattern": "LLLG",
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "mtp_layer_types": ["full_attention"], "mtp_sliding_windows": [0]}
REDUCED = {"num_hidden_layers", "layer_types", "mlp_layer_types",
           "sliding_windows", "num_experts", "num_attention_heads",
           "num_key_value_heads", "vocab_size", "num_nextn_predict_layers"}
# never a width: the guide's list
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "head_dim", "num_experts_per_tok", "sliding_window")


@pytest.fixture(scope="module")
def cell():
    return manifest.Cell(CELL)


def test_the_configuration_keeps_every_published_number_but_the_cuts(cell):
    entry = manifest._by_name(cell.manifest["configs"], "k-exaone-236b",
                              "config")
    assert set(entry["reduced"]) == REDUCED == set(cell.config["reduced"])
    assert not REDUCED & set(WIDTHS)
    for key, value in PUBLISHED.items():
        if key not in REDUCED:
            assert cell.config[key] == value, key
    if os.path.exists(CATALOG):         # the row itself, where it is at hand
        row = next(r for r in map(json.loads, open(CATALOG))
                   if r["name"] == "K-EXAONE-236B-A23B")
        assert entry["source"] == row["source_url"] == cell.config["source"]
        for key, value in row["config"].items():
            if key not in REDUCED:
                assert cell.config[key] == value, key
        for key in ("layer_types", "mlp_layer_types", "sliding_windows"):
            assert cell.config[key] == row["config"][key][:5], key
    c = cell.config
    assert c["published_num_experts"] == 128 and c["num_experts"] == 8
    assert c["held_experts"] == list(range(8))
    assert c["published_num_attention_heads"] == 64 \
        and c["num_attention_heads"] == 8 == len(c["held_query_heads"])
    assert c["published_num_key_value_heads"] == 8 \
        and c["num_key_value_heads"] == 1
    assert c["vocab_size"] * 8 == c["published_vocab_size"] == 153600
    assert c["published_num_hidden_layers"] == 48
    assert c["num_nextn_predict_layers"] == 0 \
        and c["published_num_nextn_predict_layers"] == 1
    assert c["layer_types"] == ["sliding_attention"] * 3 \
        + ["full_attention", "sliding_attention"]
    assert c["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    # the bias starts at zero and the balancing rate is inside the issue's
    # range
    assert c["router_bias_init_std"] == 0.0
    assert 0.01 <= c["router_bias_update_rate"] <= 0.1
    for key in ("assumed", "deployment", "source"):
        assert c[key]


def test_the_cut_holds_the_parameters_the_issue_counted(cell):
    ref = manifest.load_module(
        os.path.join(cell.suite, "reference", "kexaone.py"), "t_ref_kexaone")
    shapes = ref.shapes(cell.config)
    per_layer = {}
    for name, shape in shapes.items():
        per_layer[ref._group(name)] = per_layer.get(ref._group(name), 0) \
            + math.prod(shape)
    attention = 10 * 128 * 6144 + 6144 * 1024 + 2 * 128
    assert attention == 14_156_032                 # the issue: 14.16M
    assert per_layer["0"] == attention + 3 * 6144 * 18432 + 2 * 6144
    assert per_layer["1"] == per_layer["4"] == attention + 128 * 6144 + 128 \
        + 9 * 3 * 6144 * 2048 + 2 * 6144           # 354.7M, the bias among
    assert per_layer["top"] == 2 * 19200 * 6144 + 6144
    total = sum(per_layer.values())
    assert total == 2_008_616_192 + 4 * 128        # and 512 floats of bias
    assert round(total * 6 / 1e9, 2) == 12.05      # GB at 6 bytes
    traffic = cell.traffic
    assert (traffic["batch"], traffic["seq_len"], traffic["pool"]) \
        == (1, 4096, 4)
    assert cell.spec["job_params"]["checked_steps"] >= 2


def test_roofline_counts(cell):
    cfg = cell.config
    z = roofline_moe
    assert z.expert_params(cfg) == 37_748_736
    assert z.layers(cfg, True) == 4 and z.layers(cfg, False) == 1
    # a sparse layer: 14.16M of attention, 0.79M of router, a shared expert
    # and half an expert a token at the even share
    sparse = dict(cfg, layer_types=["full_attention"],
                  mlp_layer_types=["sparse"], vocab_size=0)
    assert z.even_share(cfg) == 0.5
    assert z.matmul_params_per_token(sparse) == 14_155_776 + 786_432 \
        + 37_748_736 + 0.5 * 37_748_736
    # visible pairs of a window of 128 over 4096 rows, of a causal square
    fl = z.attention_flops(cfg, 1, 4096, 128)
    assert fl["fwd"] == 8 * (128 * 4096 - 128 * 127 // 2) * 4 * 128
    assert z.attention_flops(cfg, 1, 4096)["fwd"] \
        == 8 * (4096 * 4097 // 2) * 4 * 128
    assert fl["bwd"] == 2.5 * fl["fwd"]
    by = z.attention_bytes(cfg, 1, 4096, 2)
    assert by["fwd"] == 2 * 4096 * 128 * (8 + 1 + 1 + 8) + 8 * 4096 * 4
    # 2048 pairs: six products of 2 * 37.75M operations a row
    assert z.grouped_flops(cfg, 2048) == 6 * 37_748_736 * 2048
    assert z.grouped_bytes(cfg, 0, 0, 2) == 8 * 37_748_736 * 2
    per_token = z.train_flops_per_token(cfg, 4096)
    # the issue's count: layer 0 and the projections 413.7M parameters
    dense = 5 * 14_155_776 + 3 * 6144 * 18432
    assert round(dense / 1e6, 1) == 410.5
    # 758.1M matmul parameters a token: 18.6 TFLOP of matmuls and 0.15 of
    # attention a step of 4096 tokens
    assert round(z.matmul_params_per_token(cfg) / 1e6, 1) == 758.1
    assert round(per_token * 4096 / 1e12, 1) == 18.8
    # the experts at their even share unless their pairs were counted
    assert z.train_flops_per_token(cfg, 4096, 0.5) == per_token
    assert z.train_flops_per_token(cfg, 4096, 1.0) - per_token \
        == 6 * 4 * 0.5 * 37_748_736


@pytest.mark.parametrize("op_name,scope", [
    ("jit(step)/jvp(HybridDecoderLM)/block1/moe/route/top_k:", "route"),
    ("jit(step)/transpose(jvp(HybridDecoderLM))/block4/moe/transpose("
     "transpose(jvp(HybridDecoderLM)))/block4/moe/jvp(experts)/moe_tgmm/"
     "pallas_call", "experts"),
    ("jit(step)/jvp(HybridDecoderLM)/block2/moe/while/body/combine/"
     "scatter-add", None),
    ("jit(step)/jvp(HybridDecoderLM)/block2/moe/combine/scatter-add",
     "combine"),
    ("jit(step)/jvp(HybridDecoderLM)/block3/moe/shared/down/dot_general",
     "shared"),
    ("jit(step)/jvp(HybridDecoderLM)/block3/moe/balance/sign", "balance"),
    ("jit(step)/jvp(HybridDecoderLM)/block1/attn_window/rope/mul", None),
    ("jit(step)/jvp(HybridDecoderLM)/block0/mlp/gate_up/dot_general", None),
    ("", None)])
def test_scope_of(op_name, scope):
    assert moe.scope_of(op_name) == scope


def test_new_readers_return_nothing_where_there_is_nothing(cell, monkeypatch):
    mine = [m["name"] for m in cell.manifest["per_layer"]
            if m.get("workloads") == [CELL]]
    assert sorted(mine) == sorted([
        "moe_mfu_pct.train", "moe_experts_ms_per_step.train",
        "moe_experts_roofline_pct.train", "moe_route_ms_per_step.train",
        "moe_shared_ms_per_step.train", "moe_held_load_gap.train",
        "attn128_window_roofline_pct.train",
        "attn128_full_roofline_pct.train"])
    empty = {"config": cell.config, "chips": 1, "batch": 1, "seq_len": 4096,
             "peaks": manifest.load_peaks("TPU v5 lite")}
    for name in mine:
        assert cell.reader(name).read(dict(empty)) is None, name
    # what the cell's trainer hands in after each step: every expert
    # layer's count of the tokens that chose each of the 128 experts
    def counts(*held_counts):
        out = np.zeros(128, np.float32)
        out[:len(held_counts)] = held_counts
        out[100] = 4096.0           # an absent expert's: not this chip's
        return out
    monkeypatch.setattr(moe, "STEP_COUNTS", [
        [counts(5), counts(7)],
        [counts(*[237.5] * 8), counts(*[312.5] * 8)],
        [counts(*[300.0] * 7), counts(*[211.5] * 8)]])
    # the profiled steps are the newest; held experts a token from them
    view = dict(empty, profiled_steps=2)
    assert moe.newest_steps(view) == [
        [(1900.0, 8), (2100.0, 7)], [(2500.0, 8), (1692.0, 8)]]
    assert moe.held_per_token(view) == 2048 / 4096
    assert moe.held_load_ratio(view) == 1.0
    assert cell.reader("moe_held_load_gap.train").read(view) == 0.0
    # starved or swamped, the gap is the same
    assert moe.held_load_ratio(dict(view, profiled_steps=1)) \
        == (2100 + 1692) / 4096
    assert cell.reader("moe_held_load_gap.train").read(
        dict(view, profiled_steps=1)) == 1 - (2100 + 1692) / 4096
    # a run whose steps handed nothing in, as no parent can
    monkeypatch.setattr(moe, "STEP_COUNTS", [])
    assert moe.newest_steps(view) is None
    assert cell.reader("moe_held_load_gap.train").read(view) is None
    # with tokens and a window the utilization is a number under 100
    got = cell.reader("moe_mfu_pct.train").read(
        dict(empty, tokens=4096 * 180, window_s=45.0))
    assert 0 < got < 100
    # GPT-2's and the hybrid's configurations read nothing from it
    other = manifest.Cell("phi4flash_train_t8192").config
    assert cell.reader("moe_mfu_pct.train").read(
        dict(empty, config=other, tokens=1, window_s=1.0)) is None
    assert moe.attention_roofline_pct(dict(empty, config=other), True) is None


def test_the_cell_is_in_the_manifest_with_appended_names_only(cell):
    names = [w["name"] for w in cell.manifest["workloads"]]
    assert names[-1] == CELL and cell.chips == 1 and len(names) == 4
    assert all(w["chips"] == 1 for w in cell.manifest["workloads"])
    assert all(len(w["why"]) <= 200 for w in cell.manifest["workloads"])
    assert all(len(c["why"]) <= 200 for c in cell.manifest["configs"])
    reported = {m["name"] for m in cell.per_layer()}
    for name in ("step_ms.train", "device_idle_pct.train",
                 "blocks_ms_per_step.train", "head_loss_ms_per_step.train",
                 "optimizer_ms_per_step.train", "attn_window_ms_per_step.train",
                 "attn_full_ms_per_step.train", "attn_proj_ms_per_step.train",
                 "mlp_ms_per_step.train", "collect_s.train",
                 "moe_experts_roofline_pct.train",
                 "moe_held_load_gap.train"):
        assert name in reported, name
    for name in ("hybrid_mfu_pct.train", "mfu_pct.train",
                 "attn_window_roofline_pct.train",
                 "ssm_scan_fwd_ms_per_step.train"):
        assert name not in reported, name
    assert {m["name"] for m in cell.end_to_end()} \
        == {"train_tokens_per_s", "setup_s"}
    spec = json.load(open(os.path.join(cell.suite, "cells", CELL + ".json")))
    assert spec["job"] == "train_model"
    assert spec["modules"] == {"reference": "reference/kexaone.py",
                               "system": "systems/kexaone.py"}
    # what the parent had is still there, in its order
    parent_cells = ["gpt2m_train_t1024", "cgpt13_train_t2048",
                    "phi4flash_train_t8192"]
    assert names[:3] == parent_cells
    for m in cell.manifest["per_layer"]:
        if "workloads" in m and CELL in m["workloads"]:
            assert m["workloads"][-1] == CELL


# ``data/kexaone_named_2steps.xplane.pb``: two profiled steps of a THREE-layer
# model of this family (d256, 2 query heads of 128 on 1 key/value head, T512,
# window 128, dense 512, 16 experts of 128 of which 4 are held, top-4, a
# shared expert, vocabulary 1024, bf16) through the benchmark's own Trainer
# on a TPU v5e (my chip run, PR 31), cut to the device's Steps / XLA Modules /
# XLA Ops lines and the host's Python line, HLO text cut after 48 characters
# (the Mosaic marker kept), of the metadata's stats only ``tf_op``. The
# expected numbers were summed straight from the protobuf with a regular
# expression of another script's own, not by the code under test.
TINY = {"hidden_size": 256, "num_attention_heads": 2,
        "num_key_value_heads": 1, "head_dim": 128, "sliding_window": 128,
        "intermediate_size": 512, "moe_intermediate_size": 128,
        "published_num_experts": 16, "num_experts": 4,
        "held_experts": [4, 5, 6, 7], "num_experts_per_tok": 4,
        "num_shared_experts": 1, "vocab_size": 1024, "num_hidden_layers": 3,
        "layer_types": ["sliding_attention", "full_attention",
                        "sliding_attention"],
        "mlp_layer_types": ["dense", "sparse", "sparse"]}
# ms inside the two bench/train/step annotations, both steps together
SCOPES_MS = {"route": 0.103386642, "dispatch": 0.14124492,
             "experts": 0.104169844, "combine": 0.271621798,
             "shared": 0.011395312, "balance": 0.014897422}
KERNELS_MS = {"window": 0.018984844 + 0.015747734 + 0.010485,
              "full": 0.0063 + 0.010326172}
# what the program counted in those two steps, layer by layer
PAIRS, ACTIVE = [[325.0, 468.0], [295.0, 245.0]], [[4, 4], [4, 4]]


def test_new_readers_on_a_recorded_trace(cell, tmp_path, monkeypatch):
    import shutil
    import xplane
    name = "kexaone_named_2steps.xplane.pb"
    where = tmp_path / "plugins" / "profile" / "one"
    where.mkdir(parents=True)
    shutil.copy(os.path.join(cell.suite, "tests", "data", name), where / name)
    reduced = xplane.reduce_planes(xplane.read_planes(str(where / name)),
                                   chips=1)
    # 16 experts, of which 4..7 are held: the counts of the two steps
    def counts(pairs, active):
        out = np.full(16, 99.0, np.float32)
        out[4:8] = 0.0
        out[4:4 + active] = pairs / active
        return out
    monkeypatch.setattr(moe, "STEP_COUNTS", [
        [counts(PAIRS[layer][step], ACTIVE[layer][step])
         for layer in range(2)] for step in range(2)])
    view = {"trace": reduced, "trace_dir": str(tmp_path), "profiled_steps": 2,
            "config": TINY, "chips": 1, "batch": 1, "seq_len": 512,
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}

    def read(metric):
        return cell.reader(metric).read(view)

    assert read("moe_experts_ms_per_step.train") \
        == pytest.approx(SCOPES_MS["experts"] / 2, rel=1e-6)
    assert read("moe_shared_ms_per_step.train") \
        == pytest.approx(SCOPES_MS["shared"] / 2, rel=1e-6)
    assert read("moe_route_ms_per_step.train") == pytest.approx(
        sum(SCOPES_MS[k] for k in ("route", "dispatch", "combine",
                                   "balance")) / 2, rel=1e-6)
    # 1333 pairs of 512 x 2 x 2 (tokens, layers, steps) at an even share of
    # 4 x 4 / 16 = 1 a token
    assert read("moe_held_load_gap.train") == 1 - 1333 / 2048
    # the grouped products by hand: bound by bytes at this size. A pair moves
    # 2 x 2688 bytes through the six products (x, gate_up, act, y and their
    # gradients); an expert's three matrices are 98304 parameters, read twice
    # by each active expert and written once by each held one
    moved = 5376 * 1333 + 4 * (2 * 4 + 4) * 98304 * 2
    assert read("moe_experts_roofline_pct.train") == pytest.approx(
        100 * (moved / 819e9) / (SCOPES_MS["experts"] / 1e3), rel=1e-6)
    # attention by hand, bound by bytes too: q and o 262144 bytes each, k and
    # v 131072, the row statistic 4096; forward once, backward twice
    fwd, bwd = 2 * 262144 + 2 * 131072 + 4096, 4 * 262144 + 4 * 131072 + 8192
    # (``xplane.py`` reads a kernel's events in whole nanoseconds, the
    # protobuf holds picoseconds: 2e-4 of room)
    least = (fwd + bwd) / 819e9
    assert read("attn128_window_roofline_pct.train") == pytest.approx(
        100 * 2 * least / (KERNELS_MS["window"] / 2 / 1e3), rel=2e-4)
    assert read("attn128_full_roofline_pct.train") == pytest.approx(
        100 * 1 * least / (KERNELS_MS["full"] / 2 / 1e3), rel=2e-4)
    # the readers of PR 26 that list this cell read it unchanged
    assert read("attn_window_ms_per_step.train") \
        == pytest.approx(KERNELS_MS["window"] / 2, rel=2e-4)
    assert read("attn_full_ms_per_step.train") \
        == pytest.approx(KERNELS_MS["full"] / 2, rel=2e-4)
    assert read("mlp_ms_per_step.train") > 0
    assert read("attn_proj_ms_per_step.train") > 0
    # utilization: 6 per matmul parameter with the experts by the pairs
    # counted, a number under 100
    got = cell.reader("moe_mfu_pct.train").read(
        dict(view, tokens=512 * 100, window_s=1.0))
    assert 0 < got < 100
