"""The cell ``phi4flash_train_t8192``: its files load through
``manifest.Cell``, the configuration keeps the published widths, ``reduced``
matches, and ``roofline_hybrid.py`` counts what a hand count gives at small
shapes."""

import json
import os

import pytest

from conftest import ROOT, SUITE

import manifest
import roofline_hybrid as rh

CELL = "phi4flash_train_t8192"
# the catalog's row (model-configs guide, architectures.jsonl): every number
# of its ``config``
PUBLISHED = {"embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
             "intermediate_size": 10240, "layer_norm_eps": 1e-05,
             "max_position_embeddings": 262144, "mb_per_layer": 2,
             "model_type": "phi4flash", "num_attention_heads": 40,
             "num_hidden_layers": 32, "num_key_value_heads": 20,
             "resid_pdrop": 0, "sliding_window": 512,
             "tie_word_embeddings": True, "mlp_bias": False,
             "lm_head_bias": False, "vocab_size": 200064}


@pytest.fixture(scope="module")
def cell():
    return manifest.Cell(CELL)


def test_the_cells_files_load(cell):
    assert cell.chips == 1 and cell.spec["job"] == "train_model"
    assert cell.traffic == {**cell.traffic, "kind": "token_batches",
                            "batch": 1, "seq_len": 8192, "pool": 4}
    for which in ("reference", "system"):
        assert os.path.exists(os.path.join(SUITE,
                                           cell.spec["modules"][which]))
    assert hasattr(cell.job(), "run")
    params = cell.spec["job_params"]
    assert params["dtype"] == "bfloat16" and params["checked_steps"] == 3
    assert params["adam"] == {"lr": 0.0003, "beta1": 0.9, "beta2": 0.999,
                              "epsilon": 1e-08}
    assert set(cell.spec["limits"]) == {"loss_gap", "grad_norm_gap",
                                        "delta_norm_gap",
                                        "window_loss_ratio"}


def test_the_configuration_keeps_the_published_widths(cell):
    cfg = cell.config
    entry = manifest._by_name(cell.manifest["configs"], "phi4-mini-flash",
                              "config")
    assert cfg["source"] == entry["source"]
    changed = {k for k, v in PUBLISHED.items() if cfg.get(k) != v}
    assert changed == set(entry["reduced"]) == set(cfg["reduced"]) \
        == {"num_hidden_layers", "vocab_size"}
    assert cfg["published_vocab_size"] == PUBLISHED["vocab_size"]
    assert cfg["published_num_hidden_layers"] == 32
    # the cut: an eighth of the vocabulary, a whole period and every kind
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    kinds = cfg["layer_kinds"]
    assert len(kinds) == cfg["num_hidden_layers"] in (6, 8)
    assert set(kinds) == {"mamba", "attn_window", "attn_full", "attn_cross",
                          "gmu"}
    assert all((k in ("mamba", "gmu")) == (i % 2 == 0)
               for i, k in enumerate(kinds))
    assert cfg["mamba_expand"] * cfg["hidden_size"] == 5120
    assert (cfg["mamba_d_state"], cfg["mamba_d_conv"],
            cfg["mamba_dt_rank"]) == (16, 4, 160)
    assert "assumed" in cfg and "deployment" in cfg


def test_the_cell_reports_what_the_manifest_says(cell):
    per_layer = {m["name"] for m in cell.per_layer()}
    assert {m["name"] for m in cell.end_to_end()} == {"train_tokens_per_s",
                                                      "setup_s"}
    # readers of GPT-2's keys and of every flash name stay with their cells
    assert not per_layer & {"mfu_pct.train", "flash_ms_per_step.train",
                            "flash_roofline_pct.train",
                            "flash_fwd_ms_per_step.train",
                            "flash_bwd_ms_per_step.train",
                            "flash_fwd_roofline_pct.train",
                            "flash_bwd_roofline_pct.train"}
    for name in ("step_ms.train", "blocks_ms_per_step.train",
                 "device_idle_pct.train", "collect_s.train",
                 "hybrid_mfu_pct.train", "ssm_scan_fwd_roofline_pct.train",
                 "attn_window_roofline_pct.train",
                 "attn_full_roofline_pct.train", "mamba_ms_per_step.train",
                 "gmu_ms_per_step.train", "mlp_ms_per_step.train",
                 "attn_proj_ms_per_step.train"):
        assert name in per_layer, name
        assert hasattr(cell.reader(name), "read")
    # a reader finds nothing in a view without a trace, and says so
    view = {"config": cell.config, "batch": 1, "seq_len": 8192, "chips": 1}
    for name in per_layer:
        if "hybrid" in name or "ssm" in name or "attn_" in name \
                or name.split("_")[0] in ("mamba", "gmu", "mlp"):
            assert cell.reader(name).read(view) is None, name


TINY = {"hidden_size": 8, "intermediate_size": 16, "num_attention_heads": 4,
        "num_key_value_heads": 2, "sliding_window": 3, "vocab_size": 10,
        "mamba_expand": 2, "mamba_d_state": 2, "mamba_dt_rank": 1,
        "layer_kinds": ["mamba", "attn_window", "attn_full", "gmu",
                        "attn_cross"]}


def test_visible_pairs_by_hand():
    # T = 5 causal: 1 + 2 + 3 + 4 + 5; window 3: 1 + 2 + 3 + 3 + 3
    assert rh.visible_pairs(5) == 15
    assert rh.visible_pairs(5, 3) == 12
    assert rh.visible_pairs(5, 5) == rh.visible_pairs(5, 9) == 15
    assert rh.visible_pairs(8192, 512) == 512 * 8192 - 512 * 511 // 2


def test_roofline_counts_by_hand():
    d, F, Di, D = 8, 16, 16, 2
    mlp = 3 * d * F
    mamba = 2 * Di * d + (1 + 4) * Di + Di * 1 + d * Di
    attn = (4 * D + 2 * 2 * D) * d + d * 4 * D
    cross = 2 * 4 * D * d
    gmu = 2 * Di * d
    assert rh.matmul_params(TINY) == 5 * mlp + mamba + 2 * attn + cross \
        + gmu + 10 * d
    fl = rh.attention_flops(TINY, 2, 5)
    # 2 rows x 4 heads x 15 pairs x (QK^T 2 D + PV 2 (2 D)) operations
    assert fl["fwd"] == 2 * 4 * 15 * (2 * D + 2 * 2 * D)
    assert fl["bwd"] == 2.5 * fl["fwd"]
    assert rh.attention_flops(TINY, 2, 5, 3)["fwd"] == fl["fwd"] * 12 / 15
    by = rh.attention_bytes(TINY, 1, 5, 2)
    q, k, v, o, row = 4 * 5 * D * 2, 2 * 5 * D * 2, 2 * 5 * D * 2, \
        4 * 5 * 2 * D * 2, 4 * 5 * 4
    assert by["fwd"] == q + k + v + o + row
    assert by["bwd"] == 2 * (q + k + v + o) + 2 * row
    sc = rh.scan_bytes(TINY, 1, 5, 2)
    assert sc["fwd"] == 3 * 5 * Di * 2 + 2 * 5 * 2 * 2
    assert sc["bwd"] == 5 * 5 * Di * 2 + 4 * 5 * 2 * 2
    per_token = rh.train_flops_per_token(TINY, 5)
    attention = 3.5 * (2 * fl["fwd"] / 2
                       + rh.attention_flops(TINY, 1, 5, 3)["fwd"]) / 5
    assert per_token == pytest.approx(
        6 * rh.matmul_params(TINY) + attention + 18 * Di * 2)


def test_the_cells_counts_match_the_issues_arithmetic(cell):
    cfg = cell.config
    # 7680 T^2 operations forward for a full layer (to the +T/2 of the
    # diagonal), an eighth of that through the window
    full = rh.attention_flops(cfg, 1, 8192)["fwd"]
    assert full == pytest.approx(7680 * 8192 ** 2, rel=2e-4)
    assert rh.attention_flops(cfg, 1, 8192, 512)["fwd"] / full \
        == pytest.approx(1 / 8, rel=0.04)
    # 915M parameters: 851M in the layers' matrices and vectors, 64M embedded
    assert rh.matmul_params(cfg) == pytest.approx(915e6, rel=2e-3)
