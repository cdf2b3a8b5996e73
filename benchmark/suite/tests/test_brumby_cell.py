"""The ``brumby_train_t8192`` cell's yardstick: the configuration keeps every
published number but the listed cuts, the arithmetic of the cut, the
roofline functions at a hand-computed shape, how a device operation's
retention scope is read, every new reader on a small named trace (built
here: kernel launches by their HLO names, operations by their scopes) and
on a recorded trace of another family, where each returns nothing and does
not raise, as on a parent tree. Manifest entries are found BY NAME."""

import json
import math
import os

import pytest

import brumby
import manifest
import roofline_retention
import scopes
import xplane

CELL, CONFIG = "brumby_train_t8192", "brumby-14b-base"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the catalog row's top-level numbers (model-configs guide,
# architectures.jsonl, Brumby-14B-Base)
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 5120, "intermediate_size": 17408,
    "max_position_embeddings": 32768, "max_window_layers": 40,
    "model_type": "brumby", "num_attention_heads": 40,
    "num_hidden_layers": 40, "num_key_value_heads": 8, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}
REDUCED = {"num_hidden_layers", "vocab_size"}
NEW = ["retention_fwd_ms_per_step.train", "retention_bwd_ms_per_step.train",
       "retention_fwd_roofline_pct.train", "retention_bwd_roofline_pct.train",
       "retention_mixer_ms_per_step.train", "retention_mfu_pct.train",
       "retention_state_gb.train"]
# the accepted metrics that list this cell, read by code that was there
REUSED = ["collect_s.train", "trace_lower_s.train", "compile_or_load_s.train",
          "mlp_ms_per_step.train", "import_s.train", "net_build_s.train",
          "first_run_s.train", "step_compiled_in_process.train",
          "device_reserved_gb.train", "device_headroom_gb.train",
          "host_rss_peak_gb.train", "host_issue_window_ms_per_step.train",
          "slow_steps_pct.train", "slow_step_issue_excess_ms_per_step.train",
          "slow_step_readback_excess_ms_per_step.train"]


@pytest.fixture(scope="module")
def cell():
    return manifest.Cell(CELL)


def test_the_configuration_keeps_every_published_number_but_the_cuts(cell):
    entry = manifest._by_name(cell.manifest["configs"], CONFIG, "config")
    assert set(entry["reduced"]) == REDUCED == set(cell.config["reduced"])
    c = cell.config
    for key, value in PUBLISHED.items():
        if key not in REDUCED:
            assert c[key] == value, key
    if os.path.exists(CATALOG):         # the row itself, where it is at hand
        row = next(r for r in map(json.loads, open(CATALOG))
                   if r["name"] == "Brumby-14B-Base")
        assert entry["source"] == row["source_url"] == c["source"]
        assert row["config"] == PUBLISHED
    assert c["num_hidden_layers"] == 5 and c["vocab_size"] == 18992
    assert c["published_num_hidden_layers"] == 40 \
        and c["published_vocab_size"] == 151936 == 8 * c["vocab_size"]
    assert c["head_dim"] * c["num_attention_heads"] == c["hidden_size"]
    assert c["retention_degree"] == 2 and c["retention_eps"] == 1.0
    assert (c["gate_half_life_min"], c["gate_half_life_max"]) == (64, 8192)
    assert c["recompute_blocks"] is True
    for point in ("degree", "gate", "normalisation", "qk_norm_rope",
                  "gate_bias", "weights", "block"):
        assert c["assumed"][point], point
    assert c["deployment"] and c["source"]


def test_the_cut_holds_the_parameters_the_issue_counted(cell):
    ref = manifest.load_module(
        os.path.join(cell.suite, "reference", "brumby.py"), "t_ref_brumby")
    per_group = {}
    for name, shape in ref.shapes(cell.config).items():
        per_group[ref._group(name)] = per_group.get(ref._group(name), 0) \
            + math.prod(shape)
    qkv, w_o = 5120 * (40 + 16) * 128, 5120 * 5120
    swiglu = 5120 * 34816 + 17408 * 5120
    gate, gains = 5120 * 8 + 8, 2 * 5120 + 2 * 128
    assert (qkv, w_o, swiglu, gate, gains) == (
        36_700_160, 26_214_400, 267_386_880, 40_968, 10_496)
    layer = qkv + w_o + swiglu + gate + gains
    assert layer == 330_352_904
    assert all(per_group[str(i)] == layer for i in range(5))
    assert per_group["top"] == 2 * 18992 * 5120 + 5120 == 194_483_200
    total = sum(per_group.values())
    assert total == ref.parameter_count(cell.config) == 1_846_247_720 \
        == cell.config["parameters"]["total"]
    assert cell.config["parameters"]["layers"] == 5 * layer
    assert round(total * 6 / 1e9, 2) == 11.08 \
        == cell.config["parameters"]["state_gb"]
    # an eighth of the published model, and the floor of four layers
    assert 40 * layer + 2 * 151936 * 5120 + 5120 == 14_769_945_920 \
        == 8 * total - 7 * 5120
    assert total - layer == 1_515_894_816
    traffic = cell.traffic
    assert (traffic["batch"], traffic["seq_len"], traffic["pool"]) \
        == (1, 8192, 4)
    assert cell.spec["job_params"]["checked_steps"] == 2 \
        and cell.spec["job_params"]["profiled_steps"] == 6
    assert cell.spec["modules"] == {"reference": "reference/brumby.py",
                                    "system": "systems/brumby.py"}
    assert set(cell.spec["limits"]) == {"loss_gap", "grad_norm_gap",
                                        "delta_norm_gap", "window_loss_ratio"}


def test_roofline_counts(cell):
    cfg, z = cell.config, roofline_retention
    assert z.state_shape(cfg) == (8256, 129)
    # a layer's matmuls (the gate's 40,960 among them), five of them, the
    # head's slice once: 1,749M a token, the head 5.6% of it
    layer = 36_700_160 + 26_214_400 + 267_386_880 + 40_960
    assert z.matmul_params_per_token(cfg) == 5 * layer + 18992 * 5120 \
        == 1_748_951_040
    assert round(18992 * 5120 / z.matmul_params_per_token(cfg), 3) == 0.056
    # at 8192 tokens the pairs form is the lesser: 40 heads x 33.56M visible
    # pairs x 512 against (40 + 8) x 8192 tokens x 2 x 8256 x 129
    fl = z.retention_flops(cfg, 1, 8192)
    pairs = 40 * (8192 * 8193 // 2) * 4 * 128
    state = 8192 * 48 * 2 * 8256 * 129
    assert pairs == 687_278_653_440 and state == 837_568_954_368
    assert fl == {"fwd": pairs, "bwd": 2.5 * pairs, "form": "pairs"}
    # and from 2 x 8256 x 129 x 48 / (40 x 256) = 9985 tokens on, the state
    long = z.retention_flops(cfg, 1, 16384)
    assert long["form"] == "state" and long["fwd"] == 2 * state
    assert z.retention_flops(cfg, 2, 8192)["fwd"] == 2 * pairs
    by = z.retention_bytes(cfg, 1, 8192, 2)
    once = 2 * 8192 * 128 * (40 + 8 + 8 + 40) + 8192 * 8 * 4
    kept = 8 * 8256 * 129 * 4
    assert by == {"fwd": once + kept, "bwd": 2 * once + kept}
    # the forward is bound by operations: 3.49 ms at 197 TFLOP/s against
    # 0.29 ms of bytes at 819 GB/s
    assert round(pairs / 197e12 * 1e3, 2) == 3.49 \
        and round(by["fwd"] / 819e9 * 1e3, 2) == 0.29
    per_token = z.train_flops_per_token(cfg, 8192)
    assert per_token == 6.0 * 1_748_951_040 + 5 * 3.5 * pairs / 8192
    # 86.0 TFLOP of matmuls and 12.0 of retention a step of 8192 tokens
    assert round(6 * 1_748_951_040 * 8192 / 1e12, 1) == 86.0
    assert round(per_token * 8192 / 1e12, 1) == 98.0


@pytest.mark.parametrize("op_name,inside", [
    ("jit(step)/jvp(HybridDecoderLM)/block0/retention/qkv/dot_general", True),
    ("jit(step)/transpose(jvp(HybridDecoderLM))/checkpoint/"
     "rematted_computation/block3/retention/rope/mul", True),
    ("jit(step)/jvp(HybridDecoderLM)/checkpoint/block4/retention/gate/"
     "log_sigmoid:", True),
    ("jit(step)/transpose(jvp(HybridDecoderLM))/block1/retention/scan/"
     "cumsum", True),
    ("jit(step)/jvp(HybridDecoderLM)/block1/mlp/gate_up/dot_general", False),
    ("jit(step)/jvp(HybridDecoderLM)/block1/attn_full/rope/mul", False),
    ("jit(step)/jvp(HybridDecoderLM)/ln_f/mul", False), ("", False)])
def test_mixer_scope(op_name, inside):
    assert brumby.mixer_scope(op_name) is inside


def _view(cell, **more):
    return dict({"config": cell.config, "chips": 1, "batch": 1,
                 "seq_len": 8192,
                 "peaks": manifest.load_peaks("TPU v5 lite")}, **more)


def test_new_readers_on_a_small_named_trace(cell, tmp_path, monkeypatch):
    """Two profiled steps of a two-layer model that recomputes its blocks:
    four forward launches and two backward a step by their HLO names, and
    operations under the mixer's scope and outside it."""
    op_s = {"tpu_custom_call/retention_fwd": 0.016,
            "tpu_custom_call/retention_fwd.1": 0.018,
            "tpu_custom_call/retention_fwd.2": 0.016,
            "tpu_custom_call/retention_fwd.3": 0.018,
            "tpu_custom_call/retention_bwd": 0.040,
            "tpu_custom_call/retention_bwd.1": 0.044,
            "tpu_custom_call/flash_fwd": 0.5, "fusion.12": 0.7,
            "tpu_custom_call/retention_fwdish": 9.0}
    reduced = {"op_s": op_s,
               "annotations": {"bench/train/step": [(1.0, 1.5), (1.5, 2.0)]}}
    view = _view(cell, trace=reduced, profiled_steps=2,
                 trace_dir=str(tmp_path))

    def read(metric):
        return cell.reader(metric).read(view)

    assert read("retention_fwd_ms_per_step.train") == pytest.approx(34.0)
    assert read("retention_bwd_ms_per_step.train") == pytest.approx(42.0)
    # one launch against its least time: 3.489 ms forward (the pairs form
    # over the bf16 peak), 8.722 backward; launches of 8.5 and 21 ms
    least = 687_278_653_440 / 197e12
    assert read("retention_fwd_roofline_pct.train") \
        == pytest.approx(100 * least / 8.5e-3)
    assert read("retention_bwd_roofline_pct.train") \
        == pytest.approx(100 * 2.5 * least / 21e-3)
    assert 40 < read("retention_fwd_roofline_pct.train") < 42
    # the mixer's operations outside the kernels, inside the window
    where = tmp_path / "plugins" / "profile" / "one"
    where.mkdir(parents=True)
    (where / "t.xplane.pb").write_bytes(b"")
    scope = "jit(step)/jvp(HybridDecoderLM)/block0/retention/"
    ops = [("%fusion.1 = bf16[] fusion()", scope + "qkv/dot_general",
            1.0e9, 1.2e9),
           ("%fusion.2 = bf16[] fusion()", scope + "rope/mul", 1.9e9, 2.3e9),
           ("%c = custom-call(), custom_call_target=\"tpu_custom_call\"",
            scope + "scan/retention_fwd", 1.2e9, 1.6e9),
           ("%fusion.3 = bf16[] fusion()",
            "jit(step)/jvp(HybridDecoderLM)/block0/mlp/down/dot_general",
            1.6e9, 1.9e9),
           ("%while.1 = while()", scope + "scan/while", 1.0e9, 2.0e9)]
    monkeypatch.setattr(scopes, "read_ops", lambda path: {0: ops})
    brumby._mixer_in.cache_clear()
    assert xplane.short_name(ops[2][0]).startswith(xplane.MOSAIC_PREFIX)
    # 0.2 s of qkv and the 0.1 s of rope that lie inside the window, a step
    assert read("retention_mixer_ms_per_step.train") \
        == pytest.approx((0.2 + 0.1) / 2 * 1e3)
    brumby._mixer_in.cache_clear()
    # the op's count of ONE launch: a block recomputed at a time holds one
    # layer's kept states at once
    monkeypatch.setattr(brumby, "RETENTION_STATS", {
        "launches": 5, "chunk": 256, "chunks": 32,
        "state_bytes_kept": 8 * 32 * (65 * 128 * 128 * 2 + 128 * 128 * 4)})
    assert read("retention_state_gb.train") == pytest.approx(0.562036736)
    got = cell.reader("retention_mfu_pct.train").read(
        dict(view, tokens=8192 * 30, window_s=45.0))
    assert got == pytest.approx(
        100 * 8192 * 30 / 45 * roofline_retention.train_flops_per_token(
            cell.config, 8192) / 197e12)
    assert 30 < got < 34


def test_new_readers_return_nothing_where_there_is_nothing(cell, tmp_path):
    mine = [m["name"] for m in cell.manifest["per_layer"]
            if m.get("workloads") == [CELL]]
    assert mine == NEW
    for name in mine:
        assert cell.reader(name).read(_view(cell)) is None, name
    # a recorded trace of another family (two steps of a small conv / expert
    # model on a v5e): no retention launch, no retention scope
    import shutil
    name = "lfm2_named_2steps.xplane.pb"
    where = tmp_path / "plugins" / "profile" / "one"
    where.mkdir(parents=True)
    shutil.copy(os.path.join(cell.suite, "tests", "data", name), where / name)
    reduced = xplane.reduce_planes(xplane.read_planes(str(where / name)),
                                   chips=1)
    for other in ("lfm2moe_train_t4096", "phi4flash_train_t8192",
                  "gpt2m_train_t1024"):
        view = _view(cell, config=manifest.Cell(other).config, trace=reduced,
                     trace_dir=str(tmp_path), profiled_steps=2, tokens=1,
                     window_s=1.0)
        for metric in mine:
            assert cell.reader(metric).read(view) is None, (other, metric)


def test_the_cell_is_in_the_manifest_by_name(cell):
    entry = manifest._by_name(cell.manifest["workloads"], CELL, "workload")
    assert (entry["config"], entry["traffic"], entry["chips"]) \
        == (CONFIG, "train_b1_t8192", 1)
    assert all(len(w["why"]) <= 200 for w in cell.manifest["workloads"])
    assert all(len(c["why"]) <= 200 for c in cell.manifest["configs"])
    assert sum(w["config"] == CONFIG for w in cell.manifest["workloads"]) == 1
    reported = {m["name"] for m in cell.per_layer()}
    for name in NEW + REUSED + [
            "step_ms.train", "device_idle_pct.train",
            "blocks_ms_per_step.train", "head_loss_ms_per_step.train",
            "optimizer_ms_per_step.train", "unattributed_ms_per_step.train",
            "host_issue_ms_per_step.train"]:
        assert name in reported, name
    # attn_proj_ms_per_step.train is not extended: hybrid.KINDS does not
    # know the new scope
    for name in ("hybrid_mfu_pct.train", "mfu_pct.train", "moe_mfu_pct.train",
                 "lfm2_mfu_pct.train", "attn_proj_ms_per_step.train",
                 "attn_full_ms_per_step.train",
                 "ssm_scan_fwd_ms_per_step.train"):
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
