"""The ``lingflash_train_t4096`` cell's yardstick: the configuration keeps
every published number but the listed cuts (against the catalog's row where
it is at hand), the arithmetic of the cut, the roofline functions at
hand-computed shapes, how a device operation's scope is read, every new
reader on a small named trace (built here: kernel launches by their HLO
names, operations by their scopes) and on a recorded trace of another
family, where each returns nothing and does not raise, as on a parent tree.
Manifest entries are found BY NAME."""

import json
import math
import os

import pytest

import ling
import manifest
import moe
import roofline
import roofline_kda
import scopes
import xplane

CELL, CONFIG = "lingflash_train_t4096", "ling-3.0-flash"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers", "num_experts", "vocab_size",
           "num_nextn_predict_layers"}
# every published width, as ISSUE 41 lists them
WIDTHS = {"hidden_size": 2560, "num_attention_heads": 32, "head_dim": 128,
          "num_key_value_heads": 32, "qk_head_dim": 192,
          "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
          "kv_lora_rank": 512, "q_lora_rank": None, "intermediate_size": 6144,
          "moe_intermediate_size": 768,
          "moe_shared_expert_intermediate_size": 768,
          "num_shared_experts": 1, "num_experts_per_tok": 8, "n_group": 8,
          "topk_group": 4, "routed_scaling_factor": 2.5,
          "rope_theta": 6000000, "rotary_dim": 64,
          "partial_rotary_factor": 0.5, "rope_interleave": True,
          "short_conv_kernel_size": 4, "kda_lower_bound": -5,
          "kda_safe_gate": True, "layer_group_size": 6,
          "first_k_dense_replace": 2, "rms_norm_eps": 1e-06,
          "tie_word_embeddings": False, "topk_method": "noaux_tc",
          "score_function": "sigmoid", "norm_topk_prob": True,
          "moe_router_enable_expert_bias": True, "use_qk_norm": True,
          "mtp_loss_scaling_factor": 0, "model_type": "bailing_hybrid"}
NEW = ["kda_fwd_ms_per_step.train", "kda_bwd_ms_per_step.train",
       "kda_fwd_roofline_pct.train", "kda_bwd_roofline_pct.train",
       "kda_mixer_ms_per_step.train", "mla_attn_roofline_pct.train",
       "mla_proj_ms_per_step.train", "moe_route_groups_ms_per_step.train",
       "ling_mfu_pct.train", "kda_state_gb.train"]
# the accepted metrics that list this cell, read by code that was there
REUSED = ["collect_s.train", "trace_lower_s.train", "compile_or_load_s.train",
          "mlp_ms_per_step.train", "attn_full_ms_per_step.train",
          "moe_experts_ms_per_step.train",
          "moe_experts_roofline_pct.train", "moe_route_ms_per_step.train",
          "moe_shared_ms_per_step.train", "moe_held_load_gap.train",
          "import_s.train", "net_build_s.train", "first_run_s.train",
          "step_compiled_in_process.train", "device_reserved_gb.train",
          "device_headroom_gb.train", "host_rss_peak_gb.train",
          "host_issue_window_ms_per_step.train", "slow_steps_pct.train",
          "slow_step_issue_excess_ms_per_step.train",
          "slow_step_readback_excess_ms_per_step.train"]


@pytest.fixture(scope="module")
def cell():
    return manifest.Cell(CELL)


def test_the_configuration_keeps_every_published_number_but_the_cuts(cell):
    entry = manifest._by_name(cell.manifest["configs"], CONFIG, "config")
    c = cell.config
    assert set(entry["reduced"]) == REDUCED == set(c["reduced"])
    for key, value in WIDTHS.items():
        assert c[key] == value, key
    if os.path.exists(CATALOG):         # the row itself, where it is at hand
        row = next(r for r in map(json.loads, open(CATALOG))
                   if r["name"] == "Ling-3.0-flash")
        assert entry["source"] == row["source_url"] == c["source"]
        assert {k for k, v in row["config"].items() if c.get(k) != v} \
            == REDUCED
        for key in REDUCED:
            assert c["published_" + key] == row["config"][key], key
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"],
            c["num_nextn_predict_layers"]) == (7, 16, 19648, 0)
    assert (c["published_num_hidden_layers"], c["published_num_experts"],
            c["published_vocab_size"],
            c["published_num_nextn_predict_layers"]) == (42, 512, 157184, 1)
    assert 8 * c["vocab_size"] == c["published_vocab_size"]
    assert c["held_experts"] == list(range(16))
    # the source's layer 1 and one whole group; the latent layer closes it
    assert c["source_layers"] == [1, 6, 7, 8, 9, 10, 11]
    assert c["layer_types"] == ["mla" if (i + 1) % c["layer_group_size"] == 0
                                else "kda" for i in c["source_layers"]]
    assert c["mlp_layer_types"] == [
        "dense" if i < c["first_k_dense_replace"] else "sparse"
        for i in c["source_layers"]]
    # the clamp is not built: the layers held carry none
    for limits in ("expert_swiglu_limit_list",
                   "share_expert_swiglu_limit_list"):
        assert len(c[limits]) == 42
        assert all(c[limits][i] == 0 for i in c["source_layers"]), limits
    assert c["kda_half_life_tokens"] == [16, 4096]
    assert c["router_bias_update_rate"] == 0.03 \
        and c["router_bias_init_std"] == 0.0
    for point in ("block", "layer_pattern", "kda", "mla", "router",
                  "swiglu_limit", "weights", "experts"):
        assert c["assumed"][point], point
    assert "32 v5e chips" in c["deployment"] and c["source"]


def test_the_cut_holds_the_parameters_the_issue_counted(cell):
    ref = manifest.load_module(
        os.path.join(cell.suite, "reference", "ling.py"), "t_ref_ling")
    c = cell.config
    per_group = {}
    for name, shape in ref.shapes(c).items():
        per_group[ref._group(name)] = per_group.get(ref._group(name), 0) \
            + math.prod(shape)
    wide = 2560 * 4096
    kda = 6 * wide + 3 * 4096 * 4 + 32 * 2560 + 32 + 4096 + 128
    mla = 2560 * 6144 + 2560 * 576 + 512 * 8192 + 2560 * 32 + 4096 * 2560 \
        + 512 + 192 + 128
    dense, expert = 3 * 2560 * 6144, 3 * 2560 * 768
    sparse = 512 * 2560 + 512 + 17 * expert
    assert (wide, kda, mla, dense, expert, sparse) == (
        10_485_760, 63_049_888, 31_966_016, 47_185_920, 5_898_240,
        101_581_312)
    gains = 2 * 2560
    assert per_group["0"] == kda + dense + gains == 110_240_928
    assert all(per_group[str(i)] == kda + sparse + gains == 164_636_320
               for i in range(1, 6))
    assert per_group["6"] == mla + sparse + gains == 133_552_448
    assert per_group["top"] == 2 * 19648 * 2560 + 2560 == 100_600_320
    total = sum(per_group.values())
    said = c["parameters"]
    assert total == 1_167_575_296 == said["total"] \
        and said["layers"] == [per_group[str(i)] for i in range(7)] \
        and said["tables"] + said["final_gain"] == per_group["top"]
    assert said["trained"] == total - 6 * 512 == 1_167_572_224
    assert round(said["trained"] * 6 / 1e9, 2) == 7.01 == said["state_gb"]
    # the held experts' pairs a layer at an even load, and their rows
    assert 4096 * 8 * 16 // 512 == 1024 and 1024 // 16 == 64
    traffic = cell.traffic
    assert (traffic["batch"], traffic["seq_len"], traffic["pool"]) \
        == (1, 4096, 4)
    assert cell.spec["job_params"]["checked_steps"] == 2 \
        and cell.spec["job_params"]["profiled_steps"] == 6 \
        and cell.spec["job_params"]["dtype"] == "bfloat16"
    kexaone = manifest.Cell("kexaone_train_t4096").spec["job_params"]
    assert cell.spec["job_params"]["adam"] == kexaone["adam"]
    assert cell.spec["modules"] == {"reference": "reference/ling.py",
                                    "system": "systems/ling.py"}
    assert set(cell.spec["limits"]) == {"loss_gap", "grad_norm_gap",
                                        "delta_norm_gap", "window_loss_ratio"}


def test_roofline_counts(cell):
    cfg, z = cell.config, roofline_kda
    assert (z.layers(cfg, "kda"), z.layers(cfg, "mla")) == (6, 1)
    # the recurrence: three products of a 128 x 128 state with a vector a
    # token a head, 98,304 operations; 12.9 GFLOP a layer forward
    fl = z.kda_flops(cfg, 1, 4096)
    assert fl == {"fwd": 4096 * 32 * 6 * 128 * 128, "bwd": 2 * fl["fwd"]}
    assert fl["fwd"] == 12_884_901_888
    by = z.kda_bytes(cfg, 1, 4096, 2)
    x, a, beta, state = 4096 * 4096 * 2, 4096 * 4096 * 4, 4096 * 32 * 4, \
        32 * 128 * 128 * 4
    assert by == {"fwd": 4 * x + a + beta + state,
                  "bwd": 8 * x + 2 * a + 2 * beta + state}
    # bound by BYTES forward (0.25 ms at 819 GB/s against 0.065 ms of
    # operations at 197 TFLOP/s) and backward (0.50 against 0.13)
    peaks = manifest.load_peaks("TPU v5 lite")
    for which, ms in (("fwd", 0.25), ("bwd", 0.50)):
        least, bound = roofline.roofline_seconds(fl[which], by[which], peaks)
        assert round(least * 1e3, 2) == ms and bound == "memory", which
    # latent attention: scores at 192, values at 128, by visible pairs
    wide = roofline.flash_flops(1, 32, 4096, 192)
    narrow = roofline.flash_flops(1, 32, 4096, 128)
    got = z.mla_flops(cfg, 1, 4096)
    assert got["fwd"] == (wide["fwd"] + narrow["fwd"]) / 2
    # (roofline.py counts half of T x T as visible)
    assert got["fwd"] == 32 * (4096 * 4096 // 2) * 2 * (192 + 128)
    assert got["bwd"] == 32 * (4096 * 4096 // 2) * 2 * (3 * 192 + 2 * 128)
    # a token's matrices: six projections and beta; the latent layer's five
    assert z.mixer_params(cfg, "kda") == 6 * 10_485_760 + 81_920
    assert z.mixer_params(cfg, "mla") == 31_966_016 - 832
    even = 8 * 16 / 512
    sparse = 512 * 2560 + 5_898_240 * (1 + even)
    per_token = z.matmul_params_per_token(cfg)
    assert per_token == 19648 * 2560 + 6 * z.mixer_params(cfg, "kda") \
        + z.mixer_params(cfg, "mla") + 47_185_920 + 6 * sparse
    assert round(per_token / 1e6) == 560
    flops = z.train_flops_per_token(cfg, 4096)
    assert flops == 6.0 * per_token + 6 * 3 * fl["fwd"] / 4096 \
        + (got["fwd"] + got["bwd"]) / 4096
    # 14.6 TFLOP a step of 4096 tokens by the least count, 1.6% of it the
    # recurrence and 4.2% the latent layer's scores
    assert round(flops * 4096 / 1e12, 1) == 14.6
    assert round(6 * 3 * fl["fwd"] / (flops * 4096), 3) == 0.016
    assert round((got["fwd"] + got["bwd"]) / (flops * 4096), 3) == 0.042
    # with the pairs counted (twice the even share) the experts' term grows
    assert z.train_flops_per_token(cfg, 4096, 2 * even) - flops \
        == pytest.approx(6 * 6 * even * 5_898_240)


@pytest.mark.parametrize("op_name,scope", [
    ("jit(step)/jvp(HybridDecoderLM)/block0/kda/proj/dot_general", "kda"),
    ("jit(step)/transpose(jvp(HybridDecoderLM))/block3/kda/scan/kda/"
     "cumsum", "kda"),
    ("jit(step)/jvp(HybridDecoderLM)/block5/kda/gate/logistic:", "kda"),
    ("jit(step)/jvp(HybridDecoderLM)/block6/mla/rope/mul", "mla"),
    ("jit(step)/transpose(jvp(HybridDecoderLM))/block6/mla/out/"
     "dot_general", "mla"),
    ("jit(step)/jvp(HybridDecoderLM)/block2/moe/route/groups/top_k",
     "groups"),
    ("jit(step)/jvp(HybridDecoderLM)/block2/moe/route/dot_general", None),
    ("jit(step)/jvp(HybridDecoderLM)/block0/mlp/gate_up/dot_general", None),
    ("jit(step)/jvp(HybridDecoderLM)/ln_f/mul", None), ("", None)])
def test_scope_of(op_name, scope):
    assert ling.scope_of(op_name) == scope
    if scope == "groups":       # a part of the accepted route scope
        assert moe.scope_of(op_name) == "route"


def _view(cell, **more):
    return dict({"config": cell.config, "chips": 1, "batch": 1,
                 "seq_len": 4096,
                 "peaks": manifest.load_peaks("TPU v5 lite")}, **more)


def test_new_readers_on_a_small_named_trace(cell, tmp_path, monkeypatch):
    """Two profiled steps of a model with two delta-rule layers and one
    latent layer: two forward and two backward launches a step by their HLO
    names, the flash launches, and operations under the scopes."""
    op_s = {"tpu_custom_call/kda_fwd": 0.004, "tpu_custom_call/kda_fwd.1":
            0.006, "tpu_custom_call/kda_bwd": 0.010,
            "tpu_custom_call/kda_bwd.1": 0.014,
            "tpu_custom_call/flash_fwd": 0.004,
            "tpu_custom_call/flash_bwd_fused": 0.012, "fusion.12": 0.7,
            "tpu_custom_call/kda_fwdish": 9.0,
            "tpu_custom_call/retention_fwd": 5.0}
    reduced = {"op_s": op_s,
               "annotations": {"bench/train/step": [(1.0, 1.5), (1.5, 2.0)]}}
    view = _view(cell, trace=reduced, profiled_steps=2,
                 trace_dir=str(tmp_path))

    def read(metric):
        return cell.reader(metric).read(view)

    assert read("kda_fwd_ms_per_step.train") == pytest.approx(5.0)
    assert read("kda_bwd_ms_per_step.train") == pytest.approx(12.0)
    # one launch against its least time: 0.2497 ms of bytes forward, 0.4992
    # backward; launches of 2.5 and 6 ms
    by = roofline_kda.kda_bytes(cell.config, 1, 4096, 2)
    assert read("kda_fwd_roofline_pct.train") \
        == pytest.approx(100 * by["fwd"] / 819e9 / 2.5e-3)
    assert read("kda_bwd_roofline_pct.train") \
        == pytest.approx(100 * by["bwd"] / 819e9 / 6e-3)
    assert 9 < read("kda_fwd_roofline_pct.train") < 11
    # the flash launches, the latent layer's alone here, by the accepted name
    assert read("attn_full_ms_per_step.train") == pytest.approx(8.0)
    fl = roofline_kda.mla_flops(cell.config, 1, 4096)
    assert read("mla_attn_roofline_pct.train") == pytest.approx(
        100 * (fl["fwd"] + fl["bwd"]) / 197e12 / 8e-3)
    assert 39 < read("mla_attn_roofline_pct.train") < 40
    # operations by their scopes, outside the kernels, inside the window
    where = tmp_path / "plugins" / "profile" / "one"
    where.mkdir(parents=True)
    (where / "t.xplane.pb").write_bytes(b"")
    top = "jit(step)/jvp(HybridDecoderLM)/"
    ops = [("%fusion.1 = bf16[] fusion()", top + "block0/kda/proj/dot_general",
            1.0e9, 1.2e9),
           ("%fusion.2 = f32[] fusion()", top + "block1/kda/gate/logistic",
            1.9e9, 2.3e9),
           ("%c = custom-call(), custom_call_target=\"tpu_custom_call\"",
            top + "block0/kda/scan/kda/kda_fwd", 1.2e9, 1.6e9),
           ("%fusion.3 = bf16[] fusion()", top + "block2/mla/proj/dot_general",
            1.6e9, 1.7e9),
           ("%fusion.4 = f32[] fusion()",
            top + "block1/moe/route/groups/top_k", 1.7e9, 1.74e9),
           ("%fusion.5 = bf16[] fusion()",
            top + "block0/mlp/down/dot_general", 1.74e9, 1.9e9),
           ("%while.1 = while()", top + "block0/kda/scan/while", 1.0e9,
            2.0e9)]
    monkeypatch.setattr(scopes, "read_ops", lambda path: {0: ops})
    ling._scopes_in.cache_clear()
    assert xplane.short_name(ops[2][0]).startswith(xplane.MOSAIC_PREFIX)
    # 0.2 s of proj and the 0.1 s of gate that lie inside the window, a step
    assert read("kda_mixer_ms_per_step.train") \
        == pytest.approx((0.2 + 0.1) / 2 * 1e3)
    assert read("mla_proj_ms_per_step.train") == pytest.approx(50.0)
    assert read("moe_route_groups_ms_per_step.train") == pytest.approx(20.0)
    ling._scopes_in.cache_clear()
    # the op's count of ONE launch times the six layers: nothing is
    # recomputed, so all are live at once
    monkeypatch.setattr(ling, "KDA_STATS", {
        "launches": 6, "chunk": 128, "chunks": 32,
        "state_bytes_kept": 32 * 32 * 128 * 128 * 4})
    assert read("kda_state_gb.train") == pytest.approx(6 * 0.067108864)
    got = cell.reader("ling_mfu_pct.train").read(
        dict(view, tokens=4096 * 200, window_s=45.0))
    assert got == pytest.approx(
        100 * 4096 * 200 / 45 * roofline_kda.train_flops_per_token(
            cell.config, 4096) / 197e12)
    assert 30 < got < 35


def test_new_readers_return_nothing_where_there_is_nothing(cell, tmp_path,
                                                           monkeypatch):
    mine = [m["name"] for m in cell.manifest["per_layer"]
            if m.get("workloads") == [CELL]]
    assert mine == NEW
    # as in a process whose program traced no delta-rule launch
    monkeypatch.setattr(ling, "KDA_STATS", {})
    for name in mine:
        assert cell.reader(name).read(_view(cell)) is None, name
    # a recorded trace of another family (two steps of a small conv / expert
    # model on a v5e): no delta-rule launch, no kda or mla scope, no groups
    import shutil
    name = "lfm2_named_2steps.xplane.pb"
    where = tmp_path / "plugins" / "profile" / "one"
    where.mkdir(parents=True)
    shutil.copy(os.path.join(cell.suite, "tests", "data", name), where / name)
    reduced = xplane.reduce_planes(xplane.read_planes(str(where / name)),
                                   chips=1)
    for other in ("lfm2moe_train_t4096", "kexaone_train_t4096",
                  "brumby_train_t8192", "gpt2m_train_t1024"):
        view = _view(cell, config=manifest.Cell(other).config, trace=reduced,
                     trace_dir=str(tmp_path), profiled_steps=2, tokens=1,
                     window_s=1.0)
        for metric in mine:
            assert cell.reader(metric).read(view) is None, (other, metric)


def test_the_cell_is_in_the_manifest_by_name(cell):
    entry = manifest._by_name(cell.manifest["workloads"], CELL, "workload")
    assert (entry["config"], entry["traffic"], entry["chips"]) \
        == (CONFIG, "train_b1_t4096", 1)
    assert all(len(w["why"]) <= 200 for w in cell.manifest["workloads"])
    assert all(len(c["why"]) <= 200 for c in cell.manifest["configs"])
    assert sum(w["config"] == CONFIG for w in cell.manifest["workloads"]) == 1
    # the new entries are the last of their lists
    assert cell.manifest["workloads"][-1]["name"] == CELL \
        and cell.manifest["configs"][-1]["name"] == CONFIG \
        and [m["name"] for m in cell.manifest["per_layer"][-len(NEW):]] == NEW
    reported = {m["name"] for m in cell.per_layer()}
    for name in NEW + REUSED + [
            "step_ms.train", "device_idle_pct.train",
            "blocks_ms_per_step.train", "head_loss_ms_per_step.train",
            "optimizer_ms_per_step.train", "unattributed_ms_per_step.train",
            "host_issue_ms_per_step.train"]:
        assert name in reported, name
    for name in ("hybrid_mfu_pct.train", "mfu_pct.train", "moe_mfu_pct.train",
                 "lfm2_mfu_pct.train", "retention_mfu_pct.train",
                 "attn_proj_ms_per_step.train", "attn_full_roofline_pct.train",
                 "flash_ms_per_step.train", "moe_expert_load_max.train"):
        assert name not in reported, name
    assert {m["name"] for m in cell.end_to_end()} \
        == {"train_tokens_per_s", "setup_s"}
    listed = [m["name"] for m in cell.manifest["end_to_end"]
              + cell.manifest["per_layer"] if CELL in m.get("workloads", ())]
    assert sorted(listed) == sorted(NEW + REUSED + ["train_tokens_per_s"])
    for m in cell.manifest["per_layer"]:
        if m["name"] in NEW:
            assert m["moves"] == "train_tokens_per_s" and m["layer"] in (
                "kernels", "model step (train)")
            assert callable(cell.reader(m["name"]).read)
