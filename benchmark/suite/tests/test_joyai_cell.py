"""The ``joyai_train_t4096`` cell's yardstick: the configuration keeps every
published number but the listed cuts (against the catalog's row where it is
at hand), the arithmetic of the cut, the model's least count of operations
at hand-computed shapes, how a device operation's scope is read, every new
reader on a small named trace (built here: the flash launches by their HLO
names, operations by their scopes) and on a recorded trace of another
family, where each returns nothing and does not raise, as on a parent tree.
Manifest entries are found BY NAME: a later cell appended after this one
breaks nothing here."""

import json
import math
import os

import pytest

import joyai
import manifest
import moe
import roofline
import roofline_joyai
import roofline_kda
import scopes
import xplane

CELL, CONFIG = "joyai_train_t4096", "joyai-llm-flash"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers", "n_routed_experts", "vocab_size"}
# every published width, as ISSUE 46 lists them
WIDTHS = {"hidden_size": 2048, "num_attention_heads": 32,
          "num_key_value_heads": 32, "head_dim": 64, "q_lora_rank": 1536,
          "kv_lora_rank": 512, "qk_head_dim": 192, "qk_nope_head_dim": 128,
          "qk_rope_head_dim": 64, "v_head_dim": 128,
          "intermediate_size": 7168, "moe_intermediate_size": 768,
          "n_shared_experts": 1, "num_experts_per_tok": 8, "n_group": 1,
          "topk_group": 1, "routed_scaling_factor": 2.5,
          "rope_theta": 32000000, "rope_interleave": True,
          "rope_scaling": None, "rms_norm_eps": 1e-06,
          "first_k_dense_replace": 1, "moe_layer_freq": 1,
          "num_nextn_predict_layers": 1, "tie_word_embeddings": False,
          "attention_bias": False, "topk_method": "noaux_tc",
          "scoring_func": "sigmoid", "norm_topk_prob": True,
          "hidden_act": "silu", "model_type": "joyai_llm_flash"}
NEW = ["joyai_mfu_pct.train", "joyai_mla_attn_roofline_pct.train",
       "joyai_mla_proj_ms_per_step.train", "mtp_ms_per_step.train",
       "mtp_head_loss_ms_per_step.train", "mtp_logits_gb.train"]
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
MLA, DENSE, EXPERT = 26_345_472, 44_040_192, 4_718_592   # matrices alone


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
                   if r["name"] == "JoyAI-LLM-Flash")
        assert entry["source"] == row["source_url"] == c["source"]
        assert {k for k, v in row["config"].items() if c.get(k, "") != v} \
            == REDUCED
        assert set(row["config"]) <= set(c)
        assert c["published_num_hidden_layers"] \
            == row["config"]["num_hidden_layers"]
        assert c["published_num_experts"] == row["config"]["n_routed_experts"]
        assert c["published_vocab_size"] == row["config"]["vocab_size"]
    assert (c["num_hidden_layers"], c["n_routed_experts"], c["vocab_size"]) \
        == (5, 32, 16160)
    assert (c["published_num_hidden_layers"], c["published_num_experts"],
            c["published_vocab_size"]) == (40, 256, 129280)
    assert 8 * c["vocab_size"] == c["published_vocab_size"]
    assert c["held_experts"] == list(range(32)) \
        and len(c["held_experts"]) == c["n_routed_experts"]
    # the leading dense layer and four of the 39 that follow
    assert c["source_layers"] == [0, 1, 2, 3, 4]
    assert c["layer_types"] == ["mla"] * 5
    assert c["mlp_layer_types"] == [
        "dense" if i < c["first_k_dense_replace"] else "sparse"
        for i in c["source_layers"]]
    # the keys the accepted readers read by name repeat the published ones
    assert c["num_shared_experts"] == c["n_shared_experts"]
    assert c["router_bias_update_rate"] == 0.03 \
        and c["router_bias_init_std"] == 0.0 and c["mtp_loss_weight"] == 0.3
    assert (c["mtp_hidden"], c["mtp_concat"]) == ("after ln_f",
                                                  "embedding, hidden")
    for point in ("block", "mla", "router", "experts", "mtp", "weights"):
        assert c["assumed"][point], point
    for said in ("0.3", "after ln_f", "embedding half first",
                 "no stop-gradient"):
        assert said in c["assumed"]["mtp"], said
    assert "64 v5e chips" in c["deployment"] \
        and "8 stages of five layers" in c["deployment"]
    assert "1 layer in 6" in c["reduced"]["num_hidden_layers"] \
        and "1 in 41" in c["reduced"]["num_hidden_layers"]


def test_the_cut_holds_the_parameters_the_issue_counted(cell):
    ref = manifest.load_module(
        os.path.join(cell.suite, "reference", "joyai.py"), "t_ref_joyai")
    c = cell.config
    per_group = {}
    for name, shape in ref.shapes(c).items():
        per_group[ref._group(name)] = per_group.get(ref._group(name), 0) \
            + math.prod(shape)
    d = 2048
    mla = d * 1536 + 1536 * 6144 + d * 576 + 512 * 8192 + 4096 * d \
        + 1536 + 512
    dense, expert, router = 3 * d * 7168, 3 * d * 768, 256 * d
    sparse = router + 33 * expert
    assert (d * 1536, 1536 * 6144, d * 576, 512 * 8192, 4096 * d) == (
        3_145_728, 9_437_184, 1_179_648, 4_194_304, 8_388_608)
    assert (mla, dense, expert, router, sparse) == (
        26_347_520, 44_040_192, 4_718_592, 524_288, 156_237_824)
    assert (mla - 2048, dense, expert) == (MLA, DENSE, EXPERT)
    gains, bias = 2 * d, 256
    assert per_group["0"] == mla + dense + gains == 70_391_808
    assert all(per_group[str(i)] == mla + sparse + gains + bias
               for i in range(1, 6))
    assert mla + sparse + gains == 182_589_440
    block = 182_589_440 + 2 * d * d + 3 * d
    assert block == 190_984_192
    tables = 2 * 16160 * d
    # ``top``: the tables, ln_f and the block's leaves outside its layer
    assert per_group["top"] == tables + d + 2 * d * d + 3 * d
    assert tables == 66_191_360
    trained = sum(per_group.values()) - 5 * bias
    said = c["parameters"]
    assert trained == 70_391_808 + 4 * 182_589_440 + block + tables + d \
        == 1_057_927_168 == said["trained"]
    assert said["layers"] == [70_391_808] + [182_589_440] * 4 \
        and said["tables"] == tables and said["final_gain"] == d \
        and said["selection_bias_float32"] == 5 * 256
    assert round(trained * 6 / 1e9, 2) == 6.35 == said["state_gb"]
    assert 0.37 < trained * 6 / 16.91e9 < 0.38
    # the whole model by the same count, without and with the block
    whole = 2 * 129280 * d + d + per_group["0"] \
        + 39 * (mla + router + 257 * expert + gains)
    assert round(whole / 1e9, 1) == 48.9 \
        and f"{whole:,}" in said["whole_model"]
    # the held experts' pairs a layer at an even load, and their rows
    assert 4096 * 8 * 32 // 256 == 4096 and 4096 // 32 == 128
    traffic = cell.traffic
    assert (traffic["batch"], traffic["seq_len"], traffic["pool"]) \
        == (1, 4096, 4)
    assert cell.spec["job_params"]["checked_steps"] == 2 \
        and cell.spec["job_params"]["profiled_steps"] == 6 \
        and cell.spec["job_params"]["dtype"] == "bfloat16"
    kexaone = manifest.Cell("kexaone_train_t4096").spec["job_params"]
    assert cell.spec["job_params"]["adam"] == kexaone["adam"]
    assert cell.spec["modules"] == {"reference": "reference/joyai.py",
                                    "system": "systems/joyai.py"}
    assert set(cell.spec["limits"]) == {"loss_gap", "grad_norm_gap",
                                        "delta_norm_gap", "window_loss_ratio"}


def test_the_models_least_count(cell):
    cfg, z = cell.config, roofline_joyai
    assert z.attention_layers(cfg) == 6
    assert z.mixer_params(cfg) == MLA
    # latent attention: scores at 192, values at 128, by visible pairs
    fl = roofline_kda.mla_flops(cfg, 1, 4096)
    assert fl["fwd"] == 32 * (4096 * 4096 // 2) * 2 * (192 + 128)
    assert fl["bwd"] == 32 * (4096 * 4096 // 2) * 2 * (3 * 192 + 2 * 128)
    even = 8 * 32 / 256
    assert z.mlp_params(cfg, "dense", even) == DENSE
    sparse = 256 * 2048 + EXPERT * (1 + even)
    assert z.mlp_params(cfg, "sparse", even) == sparse
    head = 16160 * 2048
    per_token = z.matmul_params_per_token(cfg, 4096)
    assert per_token == 6 * MLA + DENSE + 5 * sparse + head \
        + 2 * 2048 * 2048 + head * 4095 / 4096
    # a sparse layer: 36.3M matrix parameters a token, of them 26.3M the
    # mixer's; forward 2.97e11 operations of matrices beside 1.72e11 of
    # scores: MLA's projections and scores 83% of it, the scores alone 37%
    layer = MLA + sparse
    assert round(layer / 1e6, 1) == 36.3
    assert round(2 * layer * 4096 / 1e11, 2) == 2.97 \
        and round(fl["fwd"] / 1e11, 2) == 1.72
    whole = 2 * layer * 4096 + fl["fwd"]
    assert round((2 * MLA * 4096 + fl["fwd"]) / whole, 2) == 0.83 \
        and round(fl["fwd"] / whole, 2) == 0.37
    flops = z.train_flops_per_token(cfg, 4096)
    assert flops == 6.0 * per_token \
        + 6 * (fl["fwd"] + fl["bwd"]) / 4096
    # 11.7 TFLOP a step of 4096 tokens by the least count, 32% of it the
    # six layers' scores, 14% the two passes through the head, 22% the
    # prediction block with its head
    assert round(flops * 4096 / 1e12, 1) == 11.7
    assert round(6 * (fl["fwd"] + fl["bwd"]) / (flops * 4096), 2) == 0.32
    assert round(6 * 2 * head / flops, 2) == 0.14
    block = 6 * (layer + 2 * 2048 * 2048 + head * 4095 / 4096) \
        + (fl["fwd"] + fl["bwd"]) / 4096
    assert round(block / flops, 2) == 0.22
    # with the pairs counted (twice the even share) the experts' term grows
    assert z.train_flops_per_token(cfg, 4096, 2 * even) - flops \
        == pytest.approx(6 * 5 * even * EXPERT)
    # the experts' roofline reads this configuration through the accepted
    # functions: 4096 pairs a layer, 32 held
    import roofline_moe
    assert roofline_moe.even_share(cfg) == even \
        and roofline_moe.expert_params(cfg) == EXPERT


@pytest.mark.parametrize("op_name,scope", [
    ("jit(step)/jvp(HybridDecoderLM)/mtp0/embed/embedding/gather", "mtp"),
    ("jit(step)/transpose(jvp(HybridDecoderLM))/mtp0/proj/eh_proj/"
     "dot_general", "mtp"),
    ("jit(step)/jvp(HybridDecoderLM)/mtp0/block5/mla/attn/flash_fwd", "mtp"),
    ("jit(step)/jvp(HybridDecoderLM)/mtp0/block5/moe/experts/moe_gmm/"
     "pallas_call:", "mtp"),
    ("jit(step)/jvp(HybridDecoderLM)/mtp0/head/head/dot_general",
     "mtp_head_loss"),
    ("jit(step)/transpose(jvp(HybridDecoderLM))/mtp0/head/norm/mul",
     "mtp_head_loss"),
    ("jit(step)/jvp(loss)/NextTokenLoss/mtp/rows/reduce_sum",
     "mtp_head_loss"),
    ("jit(step)/jvp(loss)/NextTokenLoss/main/rows/reduce_sum", None),
    ("jit(step)/jvp(HybridDecoderLM)/block4/mla/proj/qa_proj/dot_general",
     None),
    ("jit(step)/jvp(HybridDecoderLM)/head/dot_general", None), ("", None)])
def test_scope_of(op_name, scope):
    assert joyai.scope_of(op_name) == scope
    if "block5/moe" in op_name:   # counted as a layer by the accepted ones
        assert moe.scope_of(op_name) == "experts" \
            and scopes.layer_of(op_name) == "blocks"
    if "mtp0/head" in op_name:
        assert scopes.layer_of(op_name) == "head_loss"


def _view(cell, **more):
    return dict({"config": cell.config, "chips": 1, "batch": 1,
                 "seq_len": 4096,
                 "peaks": manifest.load_peaks("TPU v5 lite")}, **more)


def test_new_readers_on_a_small_named_trace(cell, tmp_path, monkeypatch):
    """Two profiled steps: six forward and six backward flash launches a
    step by their HLO names, and operations under the scopes."""
    op_s = {"fusion.12": 0.7, "tpu_custom_call/flash_fwdish": 9.0,
            "tpu_custom_call/kda_fwd": 5.0}
    for i in range(6):
        tail = f".{i}" if i else ""
        op_s["tpu_custom_call/flash_fwd" + tail] = 0.004
        op_s["tpu_custom_call/flash_bwd_fused" + tail] = 0.012
    reduced = {"op_s": op_s,
               "annotations": {"bench/train/step": [(1.0, 1.5), (1.5, 2.0)]}}
    view = _view(cell, trace=reduced, profiled_steps=2,
                 trace_dir=str(tmp_path))

    def read(metric):
        return cell.reader(metric).read(view)

    # the flash launches by the accepted name: 6 x 16 ms over two steps
    assert read("attn_full_ms_per_step.train") == pytest.approx(48.0)
    fl = roofline_kda.mla_flops(cell.config, 1, 4096)
    assert read("joyai_mla_attn_roofline_pct.train") == pytest.approx(
        100 * 6 * (fl["fwd"] + fl["bwd"]) / 197e12 / 48e-3)
    assert 39 < read("joyai_mla_attn_roofline_pct.train") < 40
    # operations by their scopes, inside the window
    where = tmp_path / "plugins" / "profile" / "one"
    where.mkdir(parents=True)
    (where / "t.xplane.pb").write_bytes(b"")
    top = "jit(step)/jvp(HybridDecoderLM)/"
    ops = [("%fusion.1 = bf16[] fusion()",
            top + "block0/mla/proj/qa_proj/dot_general", 1.0e9, 1.2e9),
           ("%fusion.2 = bf16[] fusion()",
            top + "mtp0/block5/mla/proj/qb_proj/dot_general", 1.9e9, 2.3e9),
           ("%c = custom-call(), custom_call_target=\"tpu_custom_call\"",
            top + "mtp0/block5/mla/attn/flash_fwd", 1.2e9, 1.6e9),
           ("%fusion.3 = f32[] fusion()", top + "mtp0/head/head/dot_general",
            1.6e9, 1.7e9),
           ("%fusion.4 = f32[] fusion()",
            "jit(step)/jvp(loss)/NextTokenLoss/mtp/rows/reduce_sum", 1.7e9,
            1.74e9),
           ("%fusion.5 = f32[] fusion()",
            "jit(step)/jvp(loss)/NextTokenLoss/main/rows/reduce_sum", 1.74e9,
            1.9e9),
           ("%while.1 = while()", top + "mtp0/block5/moe/while", 1.0e9,
            2.0e9)]
    monkeypatch.setattr(scopes, "read_ops", lambda path: {0: ops})
    joyai._scopes_in.cache_clear()
    joyai.ling._scopes_in.cache_clear()
    assert xplane.short_name(ops[2][0]).startswith(xplane.MOSAIC_PREFIX)
    # the projections outside the kernels, trunk and block: 0.2 s and the
    # 0.1 s of the block's that lie inside the window, a step
    assert read("joyai_mla_proj_ms_per_step.train") \
        == pytest.approx((0.2 + 0.1) / 2 * 1e3)
    # everything of the block, its flash launch included: 0.1 + 0.4 + 0.1
    # + 0.04 s
    assert read("mtp_ms_per_step.train") == pytest.approx(0.64 / 2 * 1e3)
    assert read("mtp_head_loss_ms_per_step.train") \
        == pytest.approx(0.14 / 2 * 1e3)
    joyai._scopes_in.cache_clear()
    joyai.ling._scopes_in.cache_clear()
    # the model's own count of the second logits: 4096 x 16160 float32
    monkeypatch.setattr(joyai, "MTP_STATS", {
        "launches": 1, "depth": 1, "positions": 4095,
        "logits_bytes": 4096 * 16160 * 4})
    assert read("mtp_logits_gb.train") == pytest.approx(0.26476544)
    moe.STEP_COUNTS.clear()     # no step handed counts in: the even share
    got = cell.reader("joyai_mfu_pct.train").read(
        dict(view, tokens=4096 * 270, window_s=45.0))
    assert got == pytest.approx(
        100 * 4096 * 270 / 45 * roofline_joyai.train_flops_per_token(
            cell.config, 4096) / 197e12)
    assert 30 < got < 36


def test_new_readers_return_nothing_where_there_is_nothing(cell, tmp_path,
                                                           monkeypatch):
    mine = [m["name"] for m in cell.manifest["per_layer"]
            if m.get("workloads") == [CELL]]
    assert mine == NEW
    # as in a process whose program traced no prediction block
    monkeypatch.setattr(joyai, "MTP_STATS", {})
    for name in mine:
        assert cell.reader(name).read(_view(cell)) is None, name
    # a recorded trace of another family (two steps of a small conv / expert
    # model on a v5e): no mtp0 scope, no second loss
    import shutil
    name = "lfm2_named_2steps.xplane.pb"
    where = tmp_path / "plugins" / "profile" / "one"
    where.mkdir(parents=True)
    shutil.copy(os.path.join(cell.suite, "tests", "data", name), where / name)
    reduced = xplane.reduce_planes(xplane.read_planes(str(where / name)),
                                   chips=1)
    for other in ("lfm2moe_train_t4096", "kexaone_train_t4096",
                  "lingflash_train_t4096", "gpt2m_train_t1024"):
        view = _view(cell, config=manifest.Cell(other).config, trace=reduced,
                     trace_dir=str(tmp_path), profiled_steps=2, tokens=1,
                     window_s=1.0)
        for metric in mine:
            assert cell.reader(metric).read(view) is None, (other, metric)
    # and on this cell's configuration over a trace without the scopes
    view = _view(cell, trace=reduced, trace_dir=str(tmp_path),
                 profiled_steps=2)
    for metric in ("mtp_ms_per_step.train", "mtp_head_loss_ms_per_step.train",
                   "joyai_mla_proj_ms_per_step.train"):
        assert cell.reader(metric).read(view) is None, metric


def test_the_cell_is_in_the_manifest_by_name(cell):
    entry = manifest._by_name(cell.manifest["workloads"], CELL, "workload")
    assert (entry["config"], entry["traffic"], entry["chips"]) \
        == (CONFIG, "train_b1_t4096", 1)
    assert all(len(w["why"]) <= 200 for w in cell.manifest["workloads"])
    assert all(len(c["why"]) <= 200 for c in cell.manifest["configs"])
    assert sum(w["config"] == CONFIG for w in cell.manifest["workloads"]) == 1
    names = [m["name"] for m in cell.manifest["per_layer"]]
    assert [n for n in names if n in NEW] == NEW     # together, in order
    reported = {m["name"] for m in cell.per_layer()}
    for name in NEW + REUSED + [
            "step_ms.train", "device_idle_pct.train",
            "blocks_ms_per_step.train", "head_loss_ms_per_step.train",
            "optimizer_ms_per_step.train", "unattributed_ms_per_step.train",
            "host_issue_ms_per_step.train"]:
        assert name in reported, name
    # the readers that ask for another family's keys stay off this cell:
    # the new ones above stand for them
    for name in ("ling_mfu_pct.train", "mla_attn_roofline_pct.train",
                 "mla_proj_ms_per_step.train", "moe_mfu_pct.train",
                 "hybrid_mfu_pct.train", "mfu_pct.train",
                 "attn_proj_ms_per_step.train", "kda_state_gb.train",
                 "moe_route_groups_ms_per_step.train",
                 "moe_expert_load_max.train"):
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
