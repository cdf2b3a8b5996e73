"""The ``jamba2_train_t8192`` cell's yardstick: the configuration keeps every
published number but the one listed cut (against the catalog's row where it
is at hand), the arithmetic of the cut, the model's least count of
operations at hand-computed shapes, how a recomputed operation's scope is
read, every new reader on a small named trace (built here: the scan and
flash launches by their HLO names, operations by their scopes) and on a
recorded trace of another family, where each returns nothing and does not
raise, as on a parent tree. Manifest entries are found BY NAME: a later cell
appended after this one breaks nothing here."""

import json
import math
import os

import pytest

import hybrid
import jamba
import manifest
import roofline
import roofline_hybrid
import roofline_jamba
import scopes
import xplane

CELL, CONFIG = "jamba2_train_t8192", "jamba2-3b"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers"}
# every published key, as ISSUE 49 lists them
PUBLISHED = {"attn_layer_offset": 7, "attn_layer_period": 14,
             "expert_layer_offset": 1, "expert_layer_period": 2,
             "hidden_act": "silu", "hidden_size": 2560,
             "intermediate_size": 8192, "mamba_conv_bias": True,
             "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 160,
             "mamba_expand": 2, "mamba_proj_bias": False,
             "max_position_embeddings": 262144, "model_type": "jamba",
             "num_attention_heads": 20, "num_experts": 1,
             "num_experts_per_tok": 1, "num_key_value_heads": 1,
             "num_logits_to_keep": 1, "rms_norm_eps": 1e-06,
             "sliding_window": None, "tie_word_embeddings": True,
             "use_mamba_kernels": True, "vocab_size": 65536}
NEW = ["jamba_mfu_pct.train", "jamba_scan_fwd_roofline_pct.train",
       "jamba_scan_bwd_roofline_pct.train", "jamba_attn_roofline_pct.train",
       "jamba_remat_ms_per_step.train", "jamba_logits_gb.train"]
# the accepted metrics that list this cell, read by code that was there
REUSED = ["collect_s.train", "trace_lower_s.train", "compile_or_load_s.train",
          "ssm_scan_fwd_ms_per_step.train", "ssm_scan_bwd_ms_per_step.train",
          "attn_full_ms_per_step.train", "mamba_ms_per_step.train",
          "mlp_ms_per_step.train", "attn_proj_ms_per_step.train",
          "import_s.train", "net_build_s.train", "first_run_s.train",
          "step_compiled_in_process.train", "device_reserved_gb.train",
          "device_headroom_gb.train", "host_rss_peak_gb.train",
          "host_issue_window_ms_per_step.train", "slow_steps_pct.train",
          "slow_step_issue_excess_ms_per_step.train",
          "slow_step_readback_excess_ms_per_step.train"]
T = 8192


@pytest.fixture(scope="module")
def cell():
    return manifest.Cell(CELL)


def test_the_configuration_keeps_every_published_number_but_the_cut(cell):
    entry = manifest._by_name(cell.manifest["configs"], CONFIG, "config")
    c = cell.config
    assert set(entry["reduced"]) == REDUCED == set(c["reduced"])
    for key, value in PUBLISHED.items():
        assert c[key] == value, key
    if os.path.exists(CATALOG):         # the row itself, where it is at hand
        row = next(r for r in map(json.loads, open(CATALOG))
                   if r["name"] == "AI21-Jamba2-3B")
        assert entry["source"] == row["source_url"] == c["source"]
        assert {k for k, v in row["config"].items() if c.get(k, "") != v} \
            == REDUCED
        assert set(row["config"]) <= set(c)
        assert set(row["config"]) - REDUCED == set(PUBLISHED)
        assert c["published_num_hidden_layers"] \
            == row["config"]["num_hidden_layers"] == 28
    assert c["num_hidden_layers"] == 14 == len(c["layer_kinds"])
    assert c["source_layers"] == list(range(14))
    # one whole period by the family's index rule, 13 : 1 as published 26 : 2
    assert c["layer_kinds"] == [
        "attn_full" if i % c["attn_layer_period"] == c["attn_layer_offset"]
        else "mamba" for i in range(14)]
    assert c["layer_kinds"].count("mamba") == 13
    assert (c["mamba_inner_norm"], c["recompute_blocks"],
            c["float32_logits"], c["initializer_range"]) \
        == (True, True, True, 0.02)
    for point in ("layer_order", "experts", "attention", "mamba", "norm",
                  "weights", "head"):
        assert c["assumed"][point], point
    for said in ("dt_r (160), B (16) and C (16)", "dt_proj HAS a bias"):
        assert said in c["assumed"]["mamba"], said
    assert "NO positional encoding" in c["assumed"]["attention"]
    assert "not given" in c["assumed"]["layer_order"]
    assert "two v5e chips" in c["deployment"] \
        and "two stages of 14 whole layers" in c["deployment"]
    assert "Depth is the one cut" in c["reduced"]["num_hidden_layers"]


def test_the_cut_holds_the_parameters_the_issue_counted(cell):
    ref = manifest.load_module(
        os.path.join(cell.suite, "reference", "jamba.py"), "t_ref_jamba")
    c, said = cell.config, cell.config["parameters"]
    d, F, Di, N, K, R = 2560, 8192, 5120, 16, 4, 160
    w_in, conv, w_x = 2 * Di * d, Di * K + Di, (R + 2 * N) * Di
    w_dt, a_log, skip, w_out = Di * R + Di, Di * N, Di, d * Di
    gains = R + 2 * N
    assert (w_in, conv, w_x, w_dt, a_log, skip, w_out, gains) == (
        26_214_400, 25_600, 983_040, 824_320, 81_920, 5_120, 13_107_200, 192)
    mamba = w_in + conv + w_x + w_dt + a_log + skip + w_out + gains
    swiglu = 3 * d * F
    assert (mamba, swiglu) == (41_241_792, 62_914_560) \
        == (said["mamba_mixer"], said["swiglu"])
    mamba_layer = mamba + swiglu + 2 * d
    attention = 2560 * d + 2 * 128 * d + d * 2560
    assert (2560 * d, 128 * d) == (6_553_600, 327_680)
    attention_layer = attention + swiglu + 2 * d
    assert (mamba_layer, attention, attention_layer) == (
        104_161_472, 13_762_560, 76_682_240) == (
        said["mamba_layer"], said["attention_mixer"],
        said["attention_layer"])
    layers = 13 * mamba_layer + attention_layer
    table = 65536 * d
    trained = layers + d + table
    assert (layers, table, trained) == (1_430_781_376, 167_772_160,
                                        1_598_556_096) \
        == (said["layers"], said["table_once"], said["trained"])
    assert said["final_gain"] == d
    # the reference's leaves add up to it, the table ONCE
    shapes = ref.shapes(c)
    assert sum(math.prod(s) for s in shapes.values()) == trained
    assert sum(n == "embed" for n in shapes) == 1
    per_layer = {}
    for name, shape in shapes.items():
        if name.startswith("layers/"):
            i = int(name.rsplit("/", 1)[1])
            per_layer[i] = per_layer.get(i, 0) + math.prod(shape)
    assert [per_layer[i] for i in range(14)] \
        == [mamba_layer] * 7 + [attention_layer] + [mamba_layer] * 6
    assert round(trained * 6 / 1e9, 2) == 9.59 == said["state_gb"]
    assert 0.567 < trained * 6 / 16.909e9 < 0.568
    whole = 26 * mamba_layer + 2 * attention_layer + d + table
    assert whole == 3_029_337_472 and f"{whole:,}" in said["whole_model"]
    assert round(whole * 6 / 1e9, 1) == 18.2
    # a launch keeps 128 chunk starts of 16 x 5120 float32: 42 MB
    assert T // 64 * N * Di * 4 == 41_943_040
    # the float32 logits: 2.1 GB, twice lfm2moe_train_t4096's
    assert T * 65536 * 4 == 2_147_483_648
    traffic = cell.traffic
    assert (traffic["batch"], traffic["seq_len"], traffic["pool"]) \
        == (1, T, 4)
    job = cell.spec["job_params"]
    assert (job["checked_steps"], job["profiled_steps"], job["dtype"],
            job["reference_row_block"]) == (2, 6, "bfloat16", 1)
    phi4 = manifest.Cell("phi4flash_train_t8192").spec["job_params"]
    assert job["adam"] == phi4["adam"]
    assert cell.spec["modules"] == {"reference": "reference/jamba.py",
                                    "system": "systems/jamba.py"}
    assert set(cell.spec["limits"]) == {"loss_gap", "grad_norm_gap",
                                        "delta_norm_gap", "window_loss_ratio"}


def test_the_models_least_count(cell):
    cfg = cell.config
    d, Di = 2560, 5120
    # the matrices a token's forward multiplies by, from roofline_hybrid's
    # count as it is: it reads this configuration's keys
    mamba = 2 * Di * d + (160 + 32) * Di + Di * 160 + d * Di
    attention = (2560 + 256) * d + d * 2560
    mlp, head = 3 * d * 8192, 65536 * d
    assert (mamba, attention) == (41_123_840, 13_762_560)
    assert roofline_hybrid.matmul_params(cfg) \
        == 13 * (mamba + mlp) + attention + mlp + head == 1_596_948_480
    # plain grouped-query attention: 4 D a visible pair a query head
    pairs = T * (T + 1) // 2
    fl = roofline_jamba.attention_flops(cfg, 1, T)
    assert fl["fwd"] == 20 * pairs * 4 * 128 and fl["bwd"] == 2.5 * fl["fwd"]
    assert round(fl["fwd"] / 1e11, 2) == 3.44
    # differential attention's count (a value twice as wide) is half as
    # much again: the accepted hybrid reader would overstate this layer
    assert roofline_hybrid.attention_flops(cfg, 1, T)["fwd"] \
        == 1.5 * fl["fwd"]
    by = roofline_jamba.attention_bytes(cfg, 1, T, 2)
    q, kv, row = 20 * T * 128 * 2, 1 * T * 128 * 2, 20 * T * 4
    assert by["fwd"] == 2 * q + 2 * kv + row
    assert by["bwd"] == 2 * (2 * q + 2 * kv) + 2 * row
    # operations bound it: 1.75 ms forward at the peak against 0.11 of bytes
    peaks = manifest.load_peaks("TPU v5 lite")
    assert roofline.roofline_seconds(fl["fwd"], by["fwd"], peaks)[1] \
        == "compute"
    # the scan by its bytes, as phi4flash's (the very same sizes)
    sb = roofline_hybrid.scan_bytes(cfg, 1, T, 2)
    assert sb == roofline_hybrid.scan_bytes(
        manifest.Cell("phi4flash_train_t8192").config, 1, T, 2)
    assert sb["fwd"] == 3 * T * Di * 2 + 2 * T * 16 * 2
    per_token = roofline_jamba.train_flops_per_token(cfg, T)
    scan = roofline_hybrid.SCAN_FLOPS_PER_STATE * Di * 16
    assert per_token == 6.0 * 1_596_948_480 \
        + (fl["fwd"] + fl["bwd"]) / T + 13 * scan
    # 7.9e13 operations a step: the head about 10% of it (5% at 28
    # layers), the attention layer 1.5%, the mamba mixers' matrices 33%
    step = per_token * T
    assert round(step / 1e13, 2) == 7.99
    assert round(6 * head * T / step, 3) == 0.103
    assert round((fl["fwd"] + fl["bwd"]) / step, 3) == 0.015
    assert round(6 * 13 * mamba * T / step, 2) == 0.33
    assert round(6 * 14 * mlp * T / step, 2) == 0.54
    deployed = 6.0 * (26 * (mamba + mlp) + 2 * (attention + mlp) + head) \
        + 2 * (fl["fwd"] + fl["bwd"]) / T + 26 * scan
    assert round(6 * head / deployed, 3) == 0.054


@pytest.mark.parametrize("op_name,again", [
    ("jit(step)/transpose(jvp(HybridDecoderLM))/checkpoint/"
     "rematted_computation/block3/mamba/inner_norm/dt_norm/mul", True),
    ("jit(step)/transpose(jvp(HybridDecoderLM))/checkpoint/"
     "rematted_computation/block7/attn_full/flash_fwd", True),
    ("jit(step)/transpose(jvp(HybridDecoderLM))/checkpoint/"
     "rematted_computation/block0/mlp/gate_up/dot_general:", True),
    ("jit(step)/jvp(HybridDecoderLM)/checkpoint/block3/mamba/ssm_scan/"
     "pallas_call", False),
    ("jit(step)/transpose(jvp(HybridDecoderLM))/checkpoint/block3/mamba/"
     "in_proj/dot_general", False),
    ("jit(step)/jvp(HybridDecoderLM)/block13/mamba/in_proj/dot_general",
     False),
    ("jit(step)/transpose(jvp(HybridDecoderLM))/rematted_computation/"
     "dot_general", False),
    ("jit(step)/jvp(HybridDecoderLM)/head/dot_general", False), ("", False)])
def test_rematted(op_name, again):
    assert jamba.rematted(op_name) is again
    if "block3/mamba" in op_name:   # the accepted readers see the kind
        assert hybrid.kind_of(op_name) == "mamba" \
            and scopes.layer_of(op_name) == "blocks"
    if "block0/mlp" in op_name:
        assert hybrid.kind_of(op_name) == "mlp"
    if "block7/attn_full" in op_name:
        assert hybrid.kind_of(op_name) == "attn_full"


def _view(cell, **more):
    return dict({"config": cell.config, "chips": 1, "batch": 1,
                 "seq_len": T,
                 "peaks": manifest.load_peaks("TPU v5 lite")}, **more)


def test_new_readers_on_a_small_named_trace(cell, tmp_path, monkeypatch):
    """Two profiled steps: 25 forward and 13 backward scan launches, two
    flash forwards and one fused backward a step by their HLO names, and
    operations under the scopes."""
    op_s = {"fusion.12": 0.7, "tpu_custom_call/ssm_scan_fwdish": 9.0,
            "tpu_custom_call/retention_fwd": 5.0}
    for i in range(25):
        op_s["tpu_custom_call/ssm_scan_fwd" + (f".{i}" if i else "")] = 0.004
    for i in range(13):
        op_s["tpu_custom_call/ssm_scan_bwd" + (f".{i}" if i else "")] = 0.012
    op_s.update({"tpu_custom_call/flash_fwd": 0.006,
                 "tpu_custom_call/flash_fwd.1": 0.006,
                 "tpu_custom_call/flash_bwd_fused": 0.016})
    reduced = {"op_s": op_s,
               "annotations": {"bench/train/step": [(1.0, 1.5), (1.5, 2.0)]}}
    view = _view(cell, trace=reduced, profiled_steps=2,
                 trace_dir=str(tmp_path))

    def read(metric):
        return cell.reader(metric).read(view)

    # the accepted readers: every launch of a step, the recomputed among them
    assert read("ssm_scan_fwd_ms_per_step.train") == pytest.approx(50.0)
    assert read("ssm_scan_bwd_ms_per_step.train") == pytest.approx(78.0)
    assert read("attn_full_ms_per_step.train") == pytest.approx(14.0)
    # the new ones: bytes a launch x the launches SEEN over the HBM peak
    sb = roofline_hybrid.scan_bytes(cell.config, 1, T, 2)
    assert read("jamba_scan_fwd_roofline_pct.train") == pytest.approx(
        100 * 25 * sb["fwd"] / 819e9 / 50e-3)
    assert read("jamba_scan_bwd_roofline_pct.train") == pytest.approx(
        100 * 13 * sb["bwd"] / 819e9 / 78e-3)
    assert 15 < read("jamba_scan_fwd_roofline_pct.train") < 16
    assert 8 < read("jamba_scan_bwd_roofline_pct.train") < 9
    # the accepted share multiplies by the mamba LAYERS, 13, where 25
    # launches ran, and would read about half: it does not list the cell
    assert hybrid.scan_roofline_pct(view, "fwd") == pytest.approx(
        read("jamba_scan_fwd_roofline_pct.train") * 13 / 25)
    fl = roofline_jamba.attention_flops(cell.config, 1, T)
    assert read("jamba_attn_roofline_pct.train") == pytest.approx(
        100 * (2 * fl["fwd"] + fl["bwd"]) / 197e12 / 14e-3)
    assert 55 < read("jamba_attn_roofline_pct.train") < 57
    # operations by their scopes, inside the window
    where = tmp_path / "plugins" / "profile" / "one"
    where.mkdir(parents=True)
    (where / "t.xplane.pb").write_bytes(b"")
    fwd = "jit(step)/jvp(HybridDecoderLM)/"
    bwd = "jit(step)/transpose(jvp(HybridDecoderLM))/"
    again = bwd + "checkpoint/rematted_computation/"
    ops = [("%fusion.1 = bf16[] fusion()",
            fwd + "block0/mamba/in_proj/dot_general", 1.0e9, 1.2e9),
           ("%fusion.2 = bf16[] fusion()",
            again + "block0/mamba/in_proj/dot_general", 1.9e9, 2.3e9),
           ("%c = custom-call(), custom_call_target=\"tpu_custom_call\"",
            again + "block0/mamba/ssm_scan/pallas_call", 1.2e9, 1.6e9),
           ("%fusion.3 = bf16[] fusion()",
            again + "block7/attn_full/qkv/dot_general", 1.6e9, 1.7e9),
           ("%fusion.4 = bf16[] fusion()",
            bwd + "block0/mlp/down/dot_general", 1.7e9, 1.9e9),
           ("%while.1 = while()", again + "block1/mamba/while", 1.0e9,
            2.0e9)]
    monkeypatch.setattr(scopes, "read_ops", lambda path: {0: ops})
    jamba._rematted_in.cache_clear()
    hybrid._kinds_in.cache_clear()
    assert xplane.short_name(ops[2][0]).startswith(xplane.MOSAIC_PREFIX)
    # the second forwards, the kernel INCLUDED: 0.1 (of 0.4) + 0.4 + 0.1 s
    assert read("jamba_remat_ms_per_step.train") \
        == pytest.approx(0.6 / 2 * 1e3)
    # the accepted scope readers count first, recomputed and backward
    # operations of a kind alike, kernels left out
    assert read("mamba_ms_per_step.train") == pytest.approx(0.3 / 2 * 1e3)
    assert read("attn_proj_ms_per_step.train") == pytest.approx(0.1 / 2 * 1e3)
    assert read("mlp_ms_per_step.train") == pytest.approx(0.2 / 2 * 1e3)
    jamba._rematted_in.cache_clear()
    hybrid._kinds_in.cache_clear()
    # the system's own count of the float32 logits: 8192 x 65536 x 4
    monkeypatch.setattr(jamba, "HEAD_STATS", {"logits_bytes": T * 65536 * 4})
    assert read("jamba_logits_gb.train") == pytest.approx(2.147483648)
    got = cell.reader("jamba_mfu_pct.train").read(
        dict(view, tokens=T * 55, window_s=45.0))
    assert got == pytest.approx(
        100 * T * 55 / 45 * roofline_jamba.train_flops_per_token(
            cell.config, T) / 197e12)
    assert 49 < got < 50


def test_new_readers_return_nothing_where_there_is_nothing(cell, tmp_path,
                                                           monkeypatch):
    mine = [m["name"] for m in cell.manifest["per_layer"]
            if m.get("workloads") == [CELL]]
    assert mine == NEW
    # as in a process whose program counted nothing
    monkeypatch.setattr(jamba, "HEAD_STATS", {})
    for name in mine:
        assert cell.reader(name).read(_view(cell)) is None, name
    # a recorded trace of another family (two steps of a small conv / expert
    # model on a v5e): no scan launch, no recomputed scope
    import shutil
    name = "lfm2_named_2steps.xplane.pb"
    where = tmp_path / "plugins" / "profile" / "one"
    where.mkdir(parents=True)
    shutil.copy(os.path.join(cell.suite, "tests", "data", name), where / name)
    reduced = xplane.reduce_planes(xplane.read_planes(str(where / name)),
                                   chips=1)
    monkeypatch.setattr(jamba, "HEAD_STATS", {"logits_bytes": 1})
    for other in ("phi4flash_train_t8192", "brumby_train_t8192",
                  "lfm2moe_train_t4096", "gpt2m_train_t1024"):
        view = _view(cell, config=manifest.Cell(other).config, trace=reduced,
                     trace_dir=str(tmp_path), profiled_steps=2, tokens=1,
                     window_s=1.0)
        for metric in mine:
            assert cell.reader(metric).read(view) is None, (other, metric)
    # and on this cell's configuration over a trace without the launches
    # or the scope
    view = _view(cell, trace=reduced, trace_dir=str(tmp_path),
                 profiled_steps=2)
    jamba._rematted_in.cache_clear()
    for metric in ("jamba_scan_fwd_roofline_pct.train",
                   "jamba_scan_bwd_roofline_pct.train",
                   "jamba_remat_ms_per_step.train"):
        assert cell.reader(metric).read(view) is None, metric
    jamba._rematted_in.cache_clear()


def test_the_cell_is_in_the_manifest_by_name(cell):
    entry = manifest._by_name(cell.manifest["workloads"], CELL, "workload")
    assert (entry["config"], entry["traffic"], entry["chips"]) \
        == (CONFIG, "train_b1_t8192", 1)
    assert all(len(w["why"]) <= 200 for w in cell.manifest["workloads"])
    assert all(len(c["why"]) <= 200 for c in cell.manifest["configs"])
    assert sum(w["config"] == CONFIG for w in cell.manifest["workloads"]) == 1
    assert len(cell.manifest["workloads"]) >= 9
    names = [m["name"] for m in cell.manifest["per_layer"]]
    assert [n for n in names if n in NEW] == NEW     # together, in order
    reported = {m["name"] for m in cell.per_layer()}
    for name in NEW + REUSED + [
            "step_ms.train", "device_idle_pct.train",
            "blocks_ms_per_step.train", "head_loss_ms_per_step.train",
            "optimizer_ms_per_step.train", "unattributed_ms_per_step.train",
            "host_issue_ms_per_step.train",
            "idle_in_issue_ms_per_step.train"]:
        assert name in reported, name
    # the readers that would misread this cell stay off it: the new ones
    # above stand for them (the accepted scan shares multiply by layers,
    # the accepted hybrid counts differential attention)
    for name in ("ssm_scan_fwd_roofline_pct.train",
                 "ssm_scan_bwd_roofline_pct.train", "hybrid_mfu_pct.train",
                 "attn_full_roofline_pct.train", "mfu_pct.train",
                 "attn128_full_roofline_pct.train", "gmu_ms_per_step.train",
                 "attn_window_ms_per_step.train",
                 "retention_mfu_pct.train", "moe_mfu_pct.train"):
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
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
