"""Tier-1 bench guard (regression class: a round-5 capture lost every leg
to an rc=1 at backend init, and the broken bench rode along silently for a
round).

Contract: ``bench.py`` on its explicit CPU harness (``MXTPU_BENCH_FALLBACK=1``;
``main()`` never re-executes itself onto it) must exit 0 and emit ONE
parseable JSON line on stdout with that harness's full key set.
``MXTPU_BENCH_SMOKE=1`` shrinks iteration counts so this runs in tier-1 time;
the code path (imports, backend pin, every scenario, JSON emission) is the
full one."""

import json
import os
import subprocess
import sys

import pytest

import conftest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_fallback_bench(tmp_path, extra_env=None, args=()):
    env = conftest.subprocess_env()
    # the explicit CPU harness
    env["MXTPU_BENCH_FALLBACK"] = "1"
    env["MXTPU_BENCH_SMOKE"] = "1"
    # ratchet candidates land in the test's tmp dir, never the repo file
    env["MXTPU_BENCH_BASELINE_PATH"] = str(tmp_path / "BENCH_BASELINE.json")
    env.update(extra_env or {})
    p = subprocess.run(
        [sys.executable, os.path.join(_REPO, "bench.py"), *args],
        env=env, capture_output=True, text=True, timeout=480)
    assert p.returncode == 0, (
        f"bench.py cpu-fallback child exited rc={p.returncode}\n"
        f"stderr tail:\n{p.stderr[-2000:]}")
    lines = [l for l in p.stdout.strip().splitlines() if l.strip()]
    assert lines, f"no stdout from bench.py; stderr:\n{p.stderr[-2000:]}"
    return json.loads(lines[-1]), p    # the single JSON line contract


def test_bench_cpu_fallback_exits_zero_and_emits_json(tmp_path):
    doc, _ = _run_fallback_bench(tmp_path)
    assert doc["fallback"] == "cpu"
    assert doc["metric"] == "lenet_train_imgs_per_sec"
    assert doc["value"] > 0
    assert doc["loss_end"] < doc["loss_start"]       # it actually trained
    # every fallback scenario must keep emitting its keys
    assert {"checkpoint", "input_pipeline", "zero_dp", "resilience",
            "compile_caches", "mfu", "trace", "fsdp", "serving",
            "elastic", "quant", "long_context", "observability",
            "traffic", "analysis", "ratchet"} <= set(doc)
    # analysis leg (ISSUE 20): lint + audit both ran and both report the
    # contract-zero finding counts of the committed tree
    analysis = doc["analysis"]
    assert "error" not in analysis, analysis
    assert analysis["lint"]["trees"] == ["mxtpu", "tests", "bench.py"]
    assert analysis["lint"]["findings"] == 0
    assert analysis["lint"]["wall_s"] > 0
    assert analysis["audit"]["rc"] == 0
    assert analysis["audit"]["findings"] == 0
    assert analysis["audit"]["programs"] >= 6
    # resilience leg (ISSUE 8): injected ckpt io_error retried, injected
    # mid-epoch crash survived by a supervised restart, final params equal
    # to the fault-free baseline
    resil = doc["resilience"]
    assert "error" not in resil, resil
    assert resil["params_match"] is True
    assert resil["restarts"] >= 1
    assert resil["retries"] >= 1
    assert resil["faults_injected"] >= 2
    zdp = doc["zero_dp"]
    assert zdp["dp"] >= 1
    assert zdp["zero1"]["opt_state_bytes_per_device"] > 0
    assert zdp["replicated"]["step_ms"] > 0 and zdp["zero1"]["step_ms"] > 0
    # fsdp leg (ISSUE 9): the MXTPU_ZERO_STAGE ladder ran all three stages,
    # stage 3 shrank param+slot residency, and the final loss stayed
    # bit-identical across stages (dim-0-only sharding contract)
    fsdp = doc["fsdp"]
    assert "error" not in fsdp, fsdp
    assert fsdp["dp"] >= 1
    for stage in ("stage1", "stage2", "stage3"):
        assert fsdp[stage]["step_ms"] > 0
        assert fsdp[stage]["param_bytes_per_device"] > 0
        assert fsdp[stage]["slot_bytes_per_device"] > 0
    assert fsdp["loss_bit_parity"] is True
    # the shrink rides the ratchet: present under the smoke harness key
    assert doc["ratchet"]["current"]["fsdp_param_slot_shrink"] \
        == fsdp["param_slot_shrink"]
    if fsdp["dp"] > 1:   # ring legs are (N-1)/N: zero at dp=1
        assert fsdp["param_slot_shrink"] > 1.0
        for stage in ("stage1", "stage2", "stage3"):
            assert fsdp[stage]["comm_bytes_per_step"] > 0
        assert fsdp["stage3"]["param_bytes_per_device"] \
            < fsdp["stage1"]["param_bytes_per_device"]
        assert fsdp["stage2"]["grad_bytes_per_device"] \
            <= fsdp["stage1"]["grad_bytes_per_device"]
    # serving leg (ISSUE 10): Poisson-arrival continuous batching beat the
    # serial per-request baseline on the same trace, decode stayed bit-exact
    # with solo generate, and goodput rides the ratchet
    serving = doc["serving"]
    assert "error" not in serving, serving
    assert serving["decode_match"] is True
    assert serving["goodput_tok_s"] > 0
    assert serving["serial_goodput_tok_s"] > 0
    # headline acceptance is >= 2x; tier-1 asserts a loaded-machine-safe
    # floor, the full margin is visible in the emitted doc
    assert serving["goodput_vs_serial"] >= 1.5, serving
    assert serving["ttft_p99_ms"] >= serving["ttft_p50_ms"] > 0
    assert serving["completed"] == serving["requests"]
    assert 0 < serving["slot_occupancy"] <= 1
    assert doc["ratchet"]["current"]["serving_goodput"] \
        == serving["goodput_tok_s"]
    # shared-prefix leg (ISSUE 13): the system prompt prefilled ONCE
    # (hit rate (N-1)/N), p99 TTFT beat the serialized-prefill baseline,
    # decode stayed bit-exact, and both ride the ratchet
    prefix = serving["prefix"]
    assert prefix["decode_match"] is True
    n = prefix["requests"]
    assert prefix["hit_rate"] >= (n - 1) / n
    assert prefix["hit_tokens"] == (n - 1) * prefix["shared_prefix_tokens"]
    assert prefix["ttft_p99_improvement"] > 1.0, prefix
    assert prefix["baseline"]["hit_rate"] == 0          # reuse was OFF
    assert doc["ratchet"]["current"]["prefix_hit_rate"] \
        == prefix["hit_rate"]
    assert doc["ratchet"]["current"]["serving_ttft_p99_inv"] \
        == pytest.approx(1e3 / prefix["ttft_p99_ms"])
    # speculative-decode A/B leg (ISSUE 18): the same draftable trace served
    # spec-off and spec-on at chunk=1 — decode bit-exact in BOTH legs (the
    # accept/reject contract), speculation demonstrably engaged (the mean
    # emitted tokens per verify dispatch beat plain decode's 1.0), the
    # drafted-token ledger balances, and both headline numbers ride the
    # ratchet under the smoke harness key
    spec = serving["spec"]
    assert spec["decode_match"] is True
    assert spec["off"]["decode_match"] is True
    assert spec["on"]["decode_match"] is True
    assert spec["off"]["spec_dispatches"] == 0          # A/B is honest
    assert spec["on"]["spec_dispatches"] > 0
    assert spec["on"]["tokens_accepted"] + spec["on"]["tokens_rejected"] \
        == spec["on"]["tokens_drafted"] > 0
    assert spec["accept_len_mean"] > 1.0, spec
    assert spec["spec_decode_speedup"] > 0
    assert doc["ratchet"]["current"]["spec_decode_speedup"] \
        == spec["spec_decode_speedup"]
    assert doc["ratchet"]["current"]["accept_len_mean"] \
        == spec["accept_len_mean"]
    # drafter A/B (ISSUE 19): the draft-LM seam served the same trace
    # bit-exact (advisory contract) and actually drafted
    draft_lm = spec["draft_lm"]
    assert draft_lm["decode_match"] is True
    assert draft_lm["draft_lm_calls"] > 0
    assert draft_lm["tokens_drafted"] > 0
    # router leg (ISSUE 19): 2-replica router over the same trace — zero
    # drops, bit-exact, affinity engaged, >1.5x virtual-clock scale-out,
    # and the goodput/TTFT pair rides the ratchet; the sharded-replica
    # probe degrades gracefully in the 1-device subprocess
    router = serving["router"]
    assert router["decode_match"] is True
    assert router["requests_dropped"] == 0
    assert router["routed_affinity"] >= 1
    assert sum(router["placement"].values()) == router["requests"]
    assert router["scaleout_goodput_vs_single"] >= 1.5, router
    assert router["ttft_p99_ms"] >= router["ttft_p50_ms"] > 0
    assert router["sharded_replica"] == {"devices": 1, "skipped": True}
    assert doc["ratchet"]["current"]["router_goodput"] \
        == router["goodput_tok_s"] > 0
    assert doc["ratchet"]["current"]["router_ttft_p99_inv"] \
        == pytest.approx(1e3 / router["ttft_p99_ms"])
    # TTFT decomposition keys shipped by the engine stats
    assert serving["ttft_queue_wait_ms_mean"] >= 0
    assert serving["ttft_prefill_ms_mean"] > 0
    # quant leg (ISSUE 14): int8 paged-KV shrank resident KV >= 1.9x at the
    # same slot count, greedy decode stayed token-exact, the quantized fused
    # step trained, and both headline ratios ride the ratchet
    quant = doc["quant"]
    assert "error" not in quant, quant
    assert quant["kv_bytes_shrink"] >= 1.9
    assert quant["int8_kv"]["decode_match"] == quant["requests"]
    assert quant["int8_kv"]["kv_dtype"] == "int8"
    assert quant["fp32"]["kv_dtype"] == "float32"
    assert quant["int8_kv"]["kv_bytes_resident"] \
        < quant["fp32"]["kv_bytes_resident"]
    assert quant["resident_slots_at_fp32_budget"]["int8_kv"] \
        > quant["resident_slots_at_fp32_budget"]["fp32"]
    assert quant["train_step_ms_int8"] > 0
    assert quant["train_loss_end_int8"] == pytest.approx(
        quant["train_loss_end_fp32"], rel=0.05)
    assert doc["ratchet"]["current"]["kv_bytes_shrink"] \
        == quant["kv_bytes_shrink"]
    assert doc["ratchet"]["current"]["quant_decode_speedup"] \
        == quant["quant_decode_speedup"]
    # fused dequant-attention decode (ISSUE 16): the quant leg A/Bs BOTH
    # decode-kernel variants token-exactly, each probe reporting which
    # kernel actually served its decode steps
    variants = quant["int8_kv"]["variants"]
    assert set(variants) == {"pallas", "xla"}
    for kern, leg in variants.items():
        assert leg["decode_kernel"] == kern, variants
        assert leg["decode_steps"] > 0
    assert quant["quant_decode_speedup"] > 0
    assert quant["decode_step_ms_fp32"] > 0
    assert quant["decode_step_ms_int8_kv"] > 0
    # long-context leg (ISSUE 16): T2048 + T4096 MFU points emitted and
    # mfu_t2048 rides the ratchet next to quant_decode_speedup
    lctx = doc["long_context"]
    assert "error" not in lctx, lctx
    for key in ("t2048", "t4096"):
        assert lctx[key]["step_ms"] > 0
        assert lctx[key]["tokens_s"] > 0
    assert lctx["mfu_t2048"] is not None and lctx["mfu_t2048"] > 0
    assert doc["ratchet"]["current"]["mfu_t2048"] == lctx["mfu_t2048"]
    # traffic leg (ISSUE 17): the same seeded multi-tenant trace served
    # FIFO vs SLO-scheduled — decode bit-exact in both legs, goodput under
    # per-tenant SLO on the ratchet, and the dry-run autoscaler recorded
    # decisions without ever actuating
    traffic = doc["traffic"]
    assert "error" not in traffic, traffic
    assert traffic["decode_match"] is True
    assert traffic["requests"] > 0
    assert traffic["goodput_under_slo"] > 0
    assert traffic["sched"]["preempted"] >= 0
    assert "interactive" in traffic["sched"]["ttft_by_tier"]
    assert traffic["sched"]["autoscale_dry_run"]["actuated"] is False
    assert doc["ratchet"]["current"]["goodput_under_slo"] \
        == traffic["goodput_under_slo"]
    # elastic leg (ISSUE 11): one live in-place dp shrink mid-fit — no
    # restart, no steps lost, bit-exact with a cold resume — and a serving
    # drain/adopt handoff that dropped nothing
    elastic = doc["elastic"]
    assert "error" not in elastic, elastic
    assert elastic["resizes"] == 1
    assert elastic["resize_latency_ms"] > 0
    assert elastic["steps_lost"] == 0
    assert elastic["restart_fallbacks"] == 0
    assert elastic["params_match_cold_resume"] is True
    assert elastic["serving"]["requests_dropped"] == 0
    assert elastic["serving"]["decode_match"] is True
    assert elastic["serving"]["drained"] == elastic["serving"]["adopted"]
    # observability leg (ISSUE 15): telemetry (tracer + latency histograms)
    # costs < 3% step time, and the in-process Prometheus/JSON scrape
    # round-tripped for real
    obs = doc["observability"]
    assert "error" not in obs, obs
    assert obs["overhead_frac"] < 0.03, obs
    assert obs["steps_per_s_off"] > 0 and obs["steps_per_s_telemetry"] > 0
    assert obs["prometheus_ok"] is True and obs["json_ok"] is True
    assert obs["scrape_ms"] > 0 and obs["scrape_bytes"] > 0
    assert obs["step_ms_p99"] >= obs["step_ms_p50"] > 0
    # the comm leg's all_to_all anomaly probe shipped its point timing
    a2a = doc.get("comm", {}).get("all_to_all_probe")
    if a2a is not None:
        assert a2a["shard_map_ms"] > 0 and a2a["jit_reshard_ms"] > 0
    # MFU block (ISSUE 6 ratchet inputs): nonzero mfu, steps/s, tail latency
    mfu = doc["mfu"]
    assert mfu["mfu"] is not None and mfu["mfu"] > 0
    assert mfu["steps_per_sec"] > 0
    assert mfu["p99_step_ms"] > 0 and mfu["p50_step_ms"] > 0
    assert mfu["p99_step_ms"] >= mfu["p50_step_ms"]
    assert mfu["flops_per_step"] > 0
    # trace block: the traced leg dumped real spans across named threads
    tr = doc["trace"]
    assert tr["spans"] > 0 and tr["events"] >= tr["spans"]
    assert "step" in tr["span_categories"]
    assert "feed" in tr["span_categories"]
    assert "ckpt" in tr["span_categories"]
    assert len(tr["threads"]) >= 2
    assert "step/compile" in tr["span_names"] or \
        "step/execute" in tr["span_names"]
    # the ratchet wrote a baseline CANDIDATE under the smoke-suffixed key
    base = json.load(open(tmp_path / "BENCH_BASELINE.json"))
    assert base["cpu-fallback-smoke"]["steps_per_sec"] > 0
    assert doc["ratchet"]["harness"] == "cpu-fallback-smoke"
    assert doc["ratchet"]["regressions"] == {}


def test_bench_leg_failure_yields_partial_json(tmp_path):
    """A scenario raising a (simulated) transient backend error — the
    round-5 crash shape — must NOT erase the scoreboard: the failing leg
    emits ``{"error": ...}``, a leg failing once is recovered by the shared
    ``retry_transient`` policy, and every other leg ships in an exit-0 JSON
    line."""
    doc, p = _run_fallback_bench(tmp_path, extra_env={
        # input_pipeline: fails every attempt → retries exhaust → error leg
        # zero_dp: fails once → the transient retry policy must recover it
        # quant + long_context + traffic: fail every attempt too — more
        # exhausted legs, and they keep this scenario fast (each is benched
        # for real by the fallback test above / their CLI scenarios)
        "MXTPU_BENCH_FAIL_LEG":
            "input_pipeline,quant,long_context,traffic,zero_dp:1",
        "MXTPU_BENCH_RETRY_BACKOFF_S": "0.01",
        "MXTPU_RETRY_BACKOFF_MAX_S": "0.05",
    })
    assert "error" in doc["input_pipeline"]
    assert "UNAVAILABLE" in doc["input_pipeline"]["error"]
    assert doc["input_pipeline"]["retried"] is True
    assert "error" in doc["quant"]
    assert "error" in doc["long_context"]
    assert "error" in doc["traffic"]
    # the retried leg recovered — full payload, no error key
    assert "error" not in doc["zero_dp"]
    assert doc["zero_dp"]["zero1"]["step_ms"] > 0
    assert "retrying" in p.stderr
    # the remaining legs are populated and the headline survived
    assert doc["value"] > 0
    assert "error" not in doc["checkpoint"]
    assert doc["mfu"]["steps_per_sec"] > 0


def test_bench_resilience_scenario_cli(tmp_path):
    """``bench.py resilience`` (ISSUE 8 satellite): the resilience-only CLI
    path must exit 0 and emit a single resilience JSON doc — fault injected
    mid-run, supervised resume, params parity with the fault-free run."""
    doc, _ = _run_fallback_bench(tmp_path, args=("resilience",))
    assert doc["metric"] == "resilience_supervised_resume"
    assert doc["value"] == 1.0
    resil = doc["resilience"]
    assert resil["params_match"] is True
    assert resil["attempts"] == resil["restarts"] + 1
    assert resil["restart_latency_ms"] > 0


def test_bench_serving_scenario_cli(tmp_path):
    """``bench.py serving`` (ISSUE 10): the serving-only CLI path must exit
    0 and emit a single serving JSON doc — Poisson arrivals, p50/p99 TTFT,
    goodput vs the serial virtual-clock baseline, bit-exact decode."""
    doc, _ = _run_fallback_bench(tmp_path, args=("serving",))
    assert doc["metric"] == "serving_goodput_tok_s"
    assert doc["value"] > 0
    serving = doc["serving"]
    assert serving["decode_match"] is True
    assert serving["goodput_vs_serial"] >= 1.5, serving
    assert serving["deadline_ms"] > 0
    assert serving["per_token_p99_ms"] >= serving["per_token_p50_ms"] > 0
    # serving-only runs ratchet too: TTFT (inverse) + prefix hit rate land
    # under the serving-smoke harness key alongside goodput
    prefix = serving["prefix"]
    assert prefix["hit_rate"] >= (prefix["requests"] - 1) / prefix["requests"]
    assert prefix["decode_match"] is True
    # spec A/B leg (ISSUE 18) ships in the serving-only doc too: bit-exact
    # both legs, speedup + accept length on the ratchet
    spec = serving["spec"]
    assert spec["off"]["decode_match"] is True
    assert spec["on"]["decode_match"] is True
    assert spec["accept_len_mean"] > 1.0, spec
    assert spec["on"]["tokens_accepted"] + spec["on"]["tokens_rejected"] \
        == spec["on"]["tokens_drafted"] > 0
    assert spec["draft_lm"]["decode_match"] is True
    # router leg (ISSUE 19) ships in the serving-only doc too
    router = serving["router"]
    assert router["decode_match"] is True
    assert router["requests_dropped"] == 0
    assert router["scaleout_goodput_vs_single"] >= 1.5, router
    assert router["sharded_replica"]["skipped"] is True
    cur = doc["ratchet"]["current"]
    assert cur["serving_goodput"] == serving["goodput_tok_s"]
    assert cur["prefix_hit_rate"] == prefix["hit_rate"]
    assert cur["serving_ttft_p99_inv"] > 0
    assert cur["spec_decode_speedup"] == spec["spec_decode_speedup"] > 0
    assert cur["accept_len_mean"] == spec["accept_len_mean"]
    assert cur["router_goodput"] == router["goodput_tok_s"] > 0
    assert cur["router_ttft_p99_inv"] > 0
    assert doc["ratchet"]["harness"] == "serving-smoke"


def test_bench_elastic_scenario_cli(tmp_path):
    """``bench.py elastic`` (ISSUE 11): the elastic-only CLI path must exit
    0 and emit a single elastic JSON doc — live dp shrink with zero steps
    lost and cold-resume parity, serving handoff with zero drops."""
    doc, _ = _run_fallback_bench(tmp_path, args=("elastic",))
    assert doc["metric"] == "elastic_zero_loss_resize"
    assert doc["value"] == 1.0
    elastic = doc["elastic"]
    assert elastic["steps_lost"] == 0
    assert elastic["resize_latency_ms"] > 0
    assert elastic["params_match_cold_resume"] is True
    assert elastic["serving"]["requests_dropped"] == 0
    assert elastic["serving"]["decode_match"] is True


def test_bench_traffic_scenario_cli(tmp_path):
    """``bench.py traffic`` (ISSUE 17): the traffic-replay CLI path must
    exit 0 and emit a single traffic JSON doc — the SAME seeded bursty
    multi-tenant trace served FIFO then SLO-scheduled, decode bit-exact in
    BOTH legs (preempt/park/resume included), goodput-under-SLO on the
    ratchet under the smoke harness key, and the dry-run autoscaler
    recording decisions without ever touching an actuator."""
    doc, _ = _run_fallback_bench(tmp_path, args=("traffic",))
    assert doc["metric"] == "traffic_goodput_under_slo"
    assert doc["value"] > 0
    traffic = doc["traffic"]
    assert "error" not in traffic, traffic
    assert traffic["requests"] > 0
    assert traffic["kind"] == "bursty"
    # the acceptance pair: decode stays bit-exact under scheduling (both
    # legs, so preempted requests resumed token-exactly), and aggregate
    # goodput does not regress vs FIFO (loaded-machine slack on the floor;
    # the full margin is visible in the emitted doc)
    assert traffic["decode_match"] is True
    assert traffic["fifo"]["decode_match"] is True
    assert traffic["sched"]["decode_match"] is True
    assert traffic["goodput_vs_fifo"] >= 0.7, traffic
    assert traffic["goodput_under_slo"] == traffic["sched"][
        "goodput_under_slo"] > 0
    # tier-resolved TTFT shipped for both legs; the trace genuinely mixed
    # all three tiers
    for leg in ("fifo", "sched"):
        tiers = traffic[leg]["ttft_by_tier"]
        assert {"interactive", "standard", "batch"} <= set(tiers)
        for t in tiers.values():
            assert t["ttft_p99_ms"] >= t["ttft_p50_ms"] > 0
    assert traffic["interactive_ttft_p99_ms"] > 0
    assert traffic["interactive_ttft_p99_vs_fifo"] > 0
    # the SLO plane demonstrably engaged: batched prefill groups formed,
    # and preemption state round-tripped (resumed == preempted — nothing
    # parked was ever dropped)
    assert traffic["sched"]["prefill_groups"] >= 1
    assert traffic["sched"]["preempted"] == traffic["sched"]["resumed"]
    assert traffic["sched"]["shed"] == 0          # budgets are measure-only
    # dry-run autoscaler: one tick per submit, decisions recorded, nothing
    # actuated
    scale = traffic["sched"]["autoscale_dry_run"]
    assert scale["ticks"] == traffic["requests"]
    assert scale["actuated"] is False
    assert sum(scale["actions"].values()) == scale["ticks"]
    # sched+spec third leg (ISSUE 18): speculation under the full SLO
    # control plane — preemption included — replays the same trace bit-exact
    # and the drafted-token counters engaged
    spec = traffic["spec"]
    assert spec["decode_match"] is True
    assert spec["spec_dispatches"] > 0
    assert spec["tokens_drafted"] > 0
    assert spec["accept_len_mean"] > 1.0, spec
    assert spec["goodput_under_slo"] > 0
    cur = doc["ratchet"]["current"]
    assert cur["goodput_under_slo"] == traffic["goodput_under_slo"]
    assert doc["ratchet"]["harness"] == "traffic-smoke"
    assert doc["ratchet"]["regressions"] == {}


@pytest.mark.slow        # the fallback test above already runs the quant leg
def test_bench_quant_scenario_cli(tmp_path):
    """``bench.py quant`` (ISSUE 14): the quant-only CLI path must exit 0
    and emit a single quant JSON doc — fp32 vs int8-KV vs int8-KV+int8-W
    serving, the >= 1.9x KV shrink, token-exact int8-KV greedy decode, and
    the quantized fused-step timing, with both ratios on the ratchet."""
    doc, _ = _run_fallback_bench(tmp_path, args=("quant",))
    assert doc["metric"] == "kv_bytes_shrink"
    assert doc["value"] >= 1.9
    quant = doc["quant"]
    assert quant["int8_kv"]["decode_match"] == quant["requests"]
    assert quant["int8_kv_int8_w"]["decode_steps"] > 0
    assert 0 <= quant["weight_leg_token_agreement"] <= 1
    assert quant["quant_decode_speedup"] > 0
    assert quant["kv_block_shrink"] == pytest.approx(
        quant["kv_bytes_shrink"], rel=0.01)
    assert quant["quant_matmul_sites"] > 0
    # both fused decode-kernel variants served token-exactly (ISSUE 16)
    variants = quant["int8_kv"]["variants"]
    assert set(variants) == {"pallas", "xla"}
    for kern, leg in variants.items():
        assert leg["decode_kernel"] == kern
        assert leg["decode_match"] == 2
    cur = doc["ratchet"]["current"]
    assert cur["kv_bytes_shrink"] == quant["kv_bytes_shrink"]
    assert cur["quant_decode_speedup"] == quant["quant_decode_speedup"]
    assert doc["ratchet"]["harness"] == "quant-smoke"


@pytest.mark.slow   # the fallback test above already runs the telemetry leg
def test_bench_observability_scenario_cli(tmp_path):
    """``bench.py observability`` (ISSUE 15 satellite): the telemetry-only
    CLI path must exit 0 and emit a single observability JSON doc — tracer+
    histogram overhead vs the untraced loop, a real exporter scrape, and the
    ``telemetry_overhead_inv`` ratchet under the smoke harness key."""
    doc, _ = _run_fallback_bench(tmp_path, args=("observability",))
    assert doc["metric"] == "telemetry_overhead_frac"
    obs = doc["observability"]
    assert "error" not in obs, obs
    assert doc["value"] == obs["overhead_frac"]
    assert obs["overhead_frac"] < 0.03, obs
    assert obs["prometheus_ok"] is True and obs["json_ok"] is True
    assert obs["scrape_ms"] > 0
    cur = doc["ratchet"]["current"]
    assert cur["telemetry_overhead_inv"] == obs["overhead_inv"] > 0
    assert doc["ratchet"]["harness"] == "observability-smoke"


def test_bench_sanitized_leg_exits_zero_with_no_violations(tmp_path):
    """``bench.py --sanitize`` (ISSUE 5 satellite): the cpu-fallback child
    must still exit 0 with the sanitizers armed, emit the ``"sanitizer"``
    JSON block, and report ZERO violations — the committed training/
    checkpoint/input-pipeline paths are sanitizer-clean by contract. The
    scope now also runs one TRACED leg (ISSUE 6 satellite): sanitizers +
    tracing compose, still with zero violations.

    The long_context leg is failed out via the injection seam: the
    sanitize contract lives entirely in ``bench_sanitizer``'s own leg (the
    other fallback legs run unsanitized), and the long-context points pay
    two long-T compiles that the fallback test above already covers."""
    doc, _ = _run_fallback_bench(tmp_path, args=("--sanitize",), extra_env={
        "MXTPU_BENCH_FAIL_LEG": "long_context,traffic",
        "MXTPU_BENCH_RETRY_BACKOFF_S": "0.01",
        "MXTPU_RETRY_BACKOFF_MAX_S": "0.05",
    })
    san = doc["sanitizer"]
    assert san["violations"] == 0, san
    assert set(san["modes"]) == {"transfers", "donation", "retrace",
                                 "threads"}
    # the sanitized leg demonstrably ran its detectors
    assert san["stats"]["transfer_guards"] > 0
    assert san["stats"]["donation_poisons_armed"] > 0
    assert san["stats"]["ownership_checks"] > 0
    assert san["step_ms_sanitized"] > 0
    # tracing composed with the sanitizers: real spans, zero violations
    assert san["traced_leg"]["events"] > 0
    assert "step" in san["traced_leg"]["span_categories"]
