#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that mxtpu still starts on the chip.

One process drives the main path once through the entry points a user calls,
at the full width of the ``flagship`` preset (d1024, L8, H16, vocab 16384):

1. train — ``DataParallelTrainer`` + Adam over ``data_parallel_mesh()``, B8
   T1024 bf16, 7 steps on one memorised batch; the loss must fall and the
   lowered step must call the Pallas flash forward AND backward as Mosaic
   kernels (not the XLA reference ``_use_pallas`` gives way to in silence).
2. serve — ``ServingEngine(net, slots=4)`` answers six requests; every
   emitted token's logit must sit within ``TOL_SIGMA`` of that position's
   maximum when prompt+continuation runs through the training forward.
3. serve, int8 KV — a second engine with ``quant="int8_kv"``; the decode
   program at a 128-multiple KV bucket must call the Pallas dequant kernel.
4. kernels — every Pallas kernel the repo offers, alone, against the XLA
   reference: compiled or refused, and the max error (the grouped matmuls
   of an expert layer among them: uneven and empty groups, rows of no
   group). Then a tiny sparse decoder (grouped-query window / full
   attention, an expert layer holding a share of its experts, a shared
   expert, the balancing bias) trains four steps through
   ``DataParallelTrainer``: its grouped matmuls and flash launches must be
   Pallas call sites. A second tiny decoder (gated short convolutions beside
   grouped-query attention, an expert layer holding ALL its experts, a tied
   head with float32 logits) trains a step twice, once as it runs and once
   with every kernel site on its XLA formulation: first loss and every
   parameter's first gradient must agree. A third (power-retention layers
   on grouped heads of 128, every block recomputed in the backward) does
   the same: its ``retention_fwd`` / ``retention_bwd`` launches against the
   chunked ``lax`` form. A fourth (Kimi-Delta-Attention layers of 128-wide
   heads beside a latent-attention layer, expert layers under a
   group-limited router) does the same: ``kda_fwd`` / ``kda_bwd`` over three
   chunks and the flash launches at 192 / 128 against their ``lax`` forms.
   A fifth (latent attention with a query latent in both layers, a sparse
   layer, and a multi-token-prediction block trained beside the head through
   ``gluon.loss.NextTokenLoss``) does the same, and both tables must move.
   A sixth (Mamba-1 layers with the Jamba family's inner norms around one
   grouped-query attention layer without positions, every block but the
   last recomputed, leg ``jamba_train``) does the same: ``ssm_scan_fwd``
   under ``jax.checkpoint`` a second time, ``ssm_scan_bwd`` and the flash
   launches against the ``lax.scan`` and the XLA attention.
5. four chips, when there are four — leg 1 at B32 over dp=4 and a 2x2
   fsdp×tp serving engine, with where the bytes actually landed.

Any failed check or exception ends the process non-zero; there is no
per-leg containment. Without a TPU it exits 1 before building a model. The
only CPU mode is ``--rehearsal`` (``tiny`` preset, kernels interpreted): it
checks this file's control flow in the sandbox and in tier 1, never speed.

The last line of stdout is the verdict, one JSON object with exactly the
keys ``ok`` and ``device`` (platform, kind, count as JAX reports them). The
line before it is the JSON summary of the run (ending ``"claim": null``); the
full report also lands in ``chiprun_out/chip_smoke/report.json``. A run that
fails prints neither. Nothing here is a measurement of speed: wall times are
reported so a cold and a warm run can be told apart.
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import gc
import json
import os
import re
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")

# Leg 2/3 bound. The engine decodes with f32 KV and f32 activations over
# bf16 weights; the training forward is bf16 end to end, so its hidden state
# carries a relative error of about sqrt(roundings) * 2^-8 ~ 0.03 after 8
# blocks, i.e. ~0.03 sigma on a logit, where sigma is the spread of that
# position's logits over the vocabulary. A token the engine picked can then
# trail the reference's maximum by twice that. 0.25 sigma leaves room for
# int8 KV rounding on top and still rejects an fp8-sized precision drop
# (~0.8 sigma) or a wrong token (a random one trails by ~4 sigma).
TOL_SIGMA = 0.25

# Leg 4 bound: max |kernel - reference| over max |reference|. The kernels
# feed f32 tiles to the MXU at default precision (bf16 passes, 2^-8 per
# product) and store bf16 outputs; the reference is f32 at 'highest'.
KERNEL_REL_TOL = {"bfloat16": 5e-2, "float32": 3e-2}

SIZES = {
    "chip": dict(
        preset="flagship", vocab=16384,
        train=dict(B=8, T=1024, steps=7),
        serve=dict(slots=4, max_new=80, prompt_lens=(40, 150, 300), n=6),
        # (prompt, max_new): totals 120 -> KV bucket 128 and 250 -> 256, so
        # both decode programs take the Pallas kernel; the 64/160 prompt
        # buckets in between prefill through XLA
        int8=dict(slots=4, requests=((40, 80), (150, 100))),
        # (T, dtype)
        flash=dict(B=4, H=16, D=64, cases=(
            (1024, "bfloat16"), (2048, "bfloat16"),
            (1024, "float32"), (2048, "float32"))),
        decode=dict(S=4, H=16, D=64, int8_tot=(128, 512, 2048), fp8_tot=512),
        # rows, K, N, rows a group (uneven, two empty, 211 rows of no group)
        grouped=dict(M=2048, K=1024, N=2048,
                     sizes=(417, 0, 696, 1, 0, 354, 125, 244)),
        # a sparse decoder of the K-EXAONE family: widths in whole 128s
        sparse=dict(units=256, head_dim=128, heads=2, ffn=512, moe_ffn=128,
                    experts=16, held=(4, 5, 6, 7), top_k=4, vocab=1024,
                    T=512, steps=4),
        # a conv / attention decoder of the LFM2 family, every expert held
        conv_sparse=dict(units=256, head_dim=64, heads=4, kv_heads=2, ffn=512,
                         moe_ffn=128, experts=8, top_k=2, vocab=1024, T=512),
        # a retention decoder of the Brumby family: heads of 128, 3 chunks
        retention=dict(units=256, head_dim=128, heads=4, kv_heads=2, ffn=512,
                       vocab=1024, T=768),
        # a delta-rule / latent decoder of the Ling family: heads of 128, 3
        # chunks, keys of 128 + 64 from a latent of 128, 16 experts in 4
        # groups of which 2 are kept
        kda=dict(units=256, head_dim=128, heads=2, ffn=512, moe_ffn=128,
                 experts=16, held=(0, 1, 2, 3), top_k=2, n_group=4,
                 topk_group=2, vocab=1024, T=384,
                 mla=dict(latent_dim=128, nope_dim=128, rope_dim=64,
                          v_dim=128)),
        # a latent-attention decoder of the DeepSeek-V3 family with its
        # prediction block: keys of 128 + 64 from latents of 128 (kv) and
        # 256 (q), 16 experts of which 4 are held
        mtp=dict(units=256, heads=2, ffn=512, moe_ffn=128, experts=16,
                 held=(0, 1, 2, 3), top_k=2, vocab=1024, T=384,
                 mla=dict(latent_dim=128, nope_dim=128, rope_dim=64,
                          v_dim=128, q_latent_dim=256)),
        # a Mamba / attention decoder of the Jamba family: d_inner 512 (four
        # lane tiles) with 16 states, 2-on-1 heads of 128, six chunks of 64
        jamba=dict(units=256, heads=2, kv_heads=1, ffn=512, d_state=16,
                   dt_rank=16, vocab=1024, T=384),
        multi=dict(B=32, serve_n=4),
    ),
    "rehearsal": dict(
        preset="tiny", vocab=50,
        train=dict(B=4, T=128, steps=7),
        serve=dict(slots=4, max_new=70, prompt_lens=(8, 40), n=2),
        int8=dict(slots=4, requests=((8, 70), (40, 88))),
        flash=dict(B=1, H=2, D=32, cases=((128, "float32"),
                                          (128, "bfloat16"))),
        decode=dict(S=2, H=2, D=32, int8_tot=(128,), fp8_tot=128),
        grouped=dict(M=256, K=128, N=128, sizes=(100, 0, 37, 80)),
        sparse=dict(units=32, head_dim=8, heads=2, ffn=64, moe_ffn=16,
                    experts=8, held=(2, 3), top_k=2, vocab=50, T=32, steps=4),
        conv_sparse=dict(units=32, head_dim=8, heads=4, kv_heads=2, ffn=64,
                         moe_ffn=16, experts=4, top_k=2, vocab=50, T=32),
        retention=dict(units=32, head_dim=8, heads=4, kv_heads=2, ffn=64,
                       vocab=50, T=32),
        kda=dict(units=32, head_dim=8, heads=4, ffn=64, moe_ffn=16,
                 experts=8, held=(0, 1), top_k=2, n_group=4, topk_group=2,
                 vocab=50, T=32,
                 mla=dict(latent_dim=16, nope_dim=8, rope_dim=4, v_dim=8)),
        mtp=dict(units=32, heads=4, ffn=64, moe_ffn=16, experts=8,
                 held=(0, 1), top_k=2, vocab=50, T=32,
                 mla=dict(latent_dim=16, nope_dim=8, rope_dim=4, v_dim=8,
                          q_latent_dim=24)),
        jamba=dict(units=32, heads=4, kv_heads=1, ffn=64, d_state=8,
                   dt_rank=4, vocab=50, T=32),
        multi=dict(B=8, serve_n=2),
    ),
}

FLASH_FWD = "flash_fwd"
FLASH_BWD = "flash_bwd_fused"
DEQUANT_DECODE = "decode_attn_quant"
GROUPED = ("moe_gmm", "moe_tgmm")


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke FAILED: {msg}")


class CompileCounter:
    """Counts XLA compile requests and persistent-cache hits through JAX's
    own monitoring events; ``compiled`` is what actually went to the
    compiler."""

    def __init__(self):
        import jax
        self.requests = 0
        self.hits = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self) -> dict:
        return {"requests": self.requests, "cache_hits": self.hits,
                "compiled": self.requests - self.hits,
                "compile_s": round(self.seconds, 2)}

    @staticmethod
    def delta(after: dict, before: dict) -> dict:
        return {k: round(after[k] - before[k], 2) for k in after}


def mosaic_kernels(lowered_text: str) -> collections.Counter:
    """Pallas kernels a lowered program calls as Mosaic custom calls. An
    interpreted ``pallas_call`` lowers to plain HLO and leaves no
    ``tpu_custom_call``, so a kernel counted here is neither interpreted
    nor replaced by a reference path."""
    names = re.findall(r'kernel_name = "([^"]+)"', lowered_text)
    check(len(names) == lowered_text.count("@tpu_custom_call"),
          "a tpu_custom_call without a kernel_name in the lowered text")
    return collections.Counter(names)


def assert_all_on(platform: str, what: str) -> int:
    """Every live jax.Array sits on ``platform`` devices — parameters,
    optimizer state, batches and caches — whatever ``NDArray.context``
    says about it."""
    import jax
    live = jax.live_arrays()
    stray = [a.shape for a in live
             if any(d.platform != platform for d in a.devices())]
    check(not stray, f"{what}: {len(stray)} live arrays off {platform}, "
                     f"e.g. shapes {stray[:3]}")
    return len(live)


# -- leg 1: train ------------------------------------------------------------

def seq_loss(logits, y):
    from mxtpu.gluon.loss import SoftmaxCrossEntropyLoss
    b, t, v = logits.shape
    return SoftmaxCrossEntropyLoss()(
        logits.reshape((b * t, v)), y.reshape((b * t,)))


def new_net(sz):
    import mxtpu as mx
    from mxtpu.gluon.model_zoo import transformer_lm
    mx.rng.seed(0)
    net = transformer_lm(sz["preset"], vocab_size=sz["vocab"])
    net.initialize()
    net.cast("bfloat16")
    return net


def leg_train(sz, B: int, on_chip: bool) -> tuple:
    """Returns (report, trainer, batch) — the caller may inspect placement
    before letting them go."""
    from mxtpu import nd, optimizer
    from mxtpu.parallel import DataParallelTrainer, shard_batch
    from mxtpu.parallel.mesh import data_parallel_mesh

    T, steps, V = sz["train"]["T"], sz["train"]["steps"], sz["vocab"]
    net = new_net(sz)
    mesh = data_parallel_mesh()
    dpt = DataParallelTrainer(net, seq_loss,
                              optimizer.Adam(learning_rate=3e-4), mesh)
    rs = np.random.RandomState(0)
    x = shard_batch(nd.array(rs.randint(0, V, (B, T)).astype(np.int32)), mesh)
    y = shard_batch(nd.array(rs.randint(0, V, (B, T)).astype(np.float32)),
                    mesh)
    t0 = time.perf_counter()
    losses = [dpt.step(x, y)]                 # float(): waits for the device
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    losses += [dpt.step(x, y) for _ in range(steps - 1)]
    rest_s = time.perf_counter() - t0
    check(all(np.isfinite(losses)), f"train: non-finite loss {losses}")
    check(losses[-1] < losses[0],
          f"train: loss did not fall, {losses[0]:.4f} -> {losses[-1]:.4f}")

    text = dpt.lowered().as_text()
    kernels = mosaic_kernels(text)
    H = net.blocks[0].attn._heads
    scores = f"tensor<{B}x{H}x{T}x{T}x" in text   # the reference's T×T matrix
    if on_chip:
        for name in (FLASH_FWD, FLASH_BWD):
            check(kernels[name] >= len(net.blocks),
                  f"train: lowered step calls {name} {kernels[name]}x, "
                  f"expected one per block ({len(net.blocks)}) — the XLA "
                  f"reference took its place")
        check(not scores, "train: a (B,H,T,T) score tensor is in the lowered "
                          "step — the XLA attention reference ran")
    rep = {"B": B, "T": T, "dp": int(mesh.devices.size),
           "losses": [round(float(v), 4) for v in losses],
           "first_step_s": round(first_s, 2),
           "next_steps_s": round(rest_s, 2),
           "mosaic_kernels": dict(kernels), "reference_scores": scores}
    say(f"train B{B} T{T} dp{rep['dp']}: loss {losses[0]:.4f} -> "
        f"{losses[-1]:.4f}; first step {first_s:.1f}s, next {steps - 1} "
        f"{rest_s:.2f}s; mosaic {dict(kernels)}")
    return rep, dpt, (x, y)


# -- legs 2, 3, 5b: serve ----------------------------------------------------

def make_requests(sz, spec, seed: int):
    """[(prompt tokens, max_new)] from a seeded generator."""
    rs = np.random.RandomState(seed)
    if "requests" in spec:
        shape = spec["requests"]
    else:
        lens = rs.choice(spec["prompt_lens"], size=spec["n"])
        # every length at least once, so every prompt bucket compiles
        lens[:len(spec["prompt_lens"])] = spec["prompt_lens"]
        shape = [(int(n), spec["max_new"]) for n in lens]
    return [(rs.randint(0, sz["vocab"], n).tolist(), m) for n, m in shape]


def run_engine(net, requests, **engine_kwargs):
    """Start an engine through its public API, answer ``requests``
    submitted up front, stop it. Returns (outputs, stats, engine)."""
    from mxtpu import profiler
    from mxtpu.serving import ServingEngine
    profiler.reset_serving_stats()
    eng = ServingEngine(net, **engine_kwargs).start()
    try:
        handles = [eng.submit(p, max_new_tokens=m) for p, m in requests]
        outs = [h.result(timeout=900) for h in handles]
        stats = eng.stats()
    finally:
        eng.stop()
    return outs, stats, eng


def check_served(sz, requests, outs, stats, what: str) -> None:
    for (_, m), out in zip(requests, outs):
        check(len(out) == m, f"{what}: got {len(out)} tokens, wanted {m}")
        check(all(0 <= t < sz["vocab"] for t in out),
              f"{what}: token outside [0, {sz['vocab']})")
    check(stats["completed"] == len(requests),
          f"{what}: completed {stats['completed']} of {len(requests)}")
    check(stats["decode_steps"] > 0, f"{what}: no decode step ran")
    for k in ("rejected", "expired", "cancelled"):
        check(stats[k] == 0, f"{what}: {stats[k]} requests {k}")


def logit_margins(net, requests, outs) -> dict:
    """Prompt+continuation of every request through the TRAINING forward in
    one call; for each emitted token, how far its logit trails that
    position's maximum, in units of the position's logit spread."""
    import jax.numpy as jnp
    from mxtpu import autograd, nd
    totals = [len(p) + len(o) for (p, _), o in zip(requests, outs)]
    T = -(-max(totals) // 128) * 128      # causal: right padding is inert
    toks = np.zeros((len(requests), T), np.int32)
    emitted = np.zeros((len(requests), T), bool)
    for i, ((p, _), o) in enumerate(zip(requests, outs)):
        toks[i, :len(p) + len(o)] = p + o
        # the token AT position t was predicted by the logits at t - 1
        emitted[i, len(p) - 1:len(p) + len(o) - 1] = True
    with autograd.predict_mode():
        lg = net(nd.array(toks)).data.astype(jnp.float32)      # (N, T, V)
    nxt = jnp.asarray(np.roll(toks, -1, axis=1))
    picked = jnp.take_along_axis(lg, nxt[..., None], axis=-1)[..., 0]
    trail = np.asarray((lg.max(-1) - picked) / lg.std(-1))[emitted]
    check(np.isfinite(trail).all(), "reference logits are not finite")
    return {"tokens": int(trail.size), "ref_T": T,
            "worst_sigma": round(float(trail.max()), 4),
            "argmax_agree": round(float((trail == 0).mean()), 4)}


def serve_and_check(sz, net, spec, seed: int, what: str, **engine_kwargs):
    """One engine, its requests answered and checked: counts, token range,
    and every emitted token inside TOL_SIGMA of the training forward.
    Returns (report, requests, outputs, engine)."""
    requests = make_requests(sz, spec, seed)
    t0 = time.perf_counter()
    outs, stats, eng = run_engine(net, requests, slots=spec["slots"],
                                  **engine_kwargs)
    wall = time.perf_counter() - t0
    check_served(sz, requests, outs, stats, what)
    m = logit_margins(net, requests, outs)
    check(m["worst_sigma"] <= TOL_SIGMA,
          f"{what}: an emitted token trails the training forward's maximum "
          f"by {m['worst_sigma']} sigma (bound {TOL_SIGMA})")
    rep = {"requests": len(requests), "engine_s": round(wall, 2),
           "prompt_lens": [len(p) for p, _ in requests],
           "decode_steps": stats["decode_steps"],
           "kv_dtype": stats["kv_dtype"],
           "decode_kernel": stats.get("decode_kernel"),
           "tol_sigma": TOL_SIGMA, **m}
    say(f"{what}: {len(requests)} requests in {wall:.1f}s, "
        f"{stats['decode_steps']} decode dispatches, KV {stats['kv_dtype']}; "
        f"worst margin {m['worst_sigma']} sigma (bound {TOL_SIGMA}), argmax "
        f"agrees on {m['argmax_agree']:.1%} of {m['tokens']} tokens")
    return rep, requests, outs, eng


def leg_serve(sz, net) -> dict:
    rep, requests, outs, _ = serve_and_check(sz, net, sz["serve"], 1, "serve")
    # recorded, not gated: byte-identity with solo generate was pinned on
    # the CPU at rtol=0; on the chip tilings change with the batch shape
    from mxtpu import nd
    p0, m0 = requests[0]
    solo = net.generate(nd.array(np.asarray([p0], np.int32)), m0) \
        .asnumpy()[0, len(p0):].tolist()
    same = sum(a == b for a, b in zip(solo, outs[0]))
    rep["greedy_vs_solo_generate"] = {
        "prompt_len": len(p0), "same": same, "of": m0,
        "identical": same == m0}
    say(f"  greedy output vs solo generate (prompt {len(p0)}): {same}/{m0} "
        f"tokens equal — recorded, not gated")
    return rep


def serving_program_kernels(net, eng, requests, quant) -> list:
    """Which attention-read kernel each serving program got. The keys come
    from the engine's own key-site table (``audit_key_specs``), the programs
    from ``kv.audit_programs`` — built exactly as the engine builds them —
    and the verdict from each program's lowered text."""
    from mxtpu.ops.quant_attention import resolve_decode_kernel
    from mxtpu.quant.serve import parse_quant
    from mxtpu.serving import engine as engine_mod, kv
    D = kv.cache_dims(net)[2]
    spec = parse_quant(quant)
    geoms = [(len(p), len(p) + m) for p, m in requests]
    rows = []
    for name, keys_of, _ in engine_mod.audit_key_specs(
            net._max_len, eng.slots, eng.chunk, eng.prefill_chunk, k=1):
        if name == "serving_verify":
            continue
        for key in sorted({k for g in geoms for k in keys_of(*g)}):
            if name == "serving_decode":
                S, TOT, chunk = key
                progs = kv.audit_programs(net, S, TOT, chunk, 1, quant=spec)
            else:
                TOT, csize = key
                progs = kv.audit_programs(net, 1, TOT, 1, 1, PB=TOT,
                                          csize=csize, quant=spec)
            fn, args = next((f, a) for n, f, a in progs if n == name)
            kernels = mosaic_kernels(fn.lower(*args).as_text())
            rows.append({"program": name, "key": list(key), "bucket": TOT,
                         "resolved": resolve_decode_kernel(None, TOT=TOT,
                                                           D=D),
                         "mosaic_calls": kernels[DEQUANT_DECODE]})
    return rows


def leg_serve_int8(sz, net, on_chip: bool) -> dict:
    rep, requests, _, eng = serve_and_check(sz, net, sz["int8"], 2,
                                            "serve int8", quant="int8_kv")
    check(rep["kv_dtype"] == "int8", f"KV stored as {rep['kv_dtype']}")
    rows = serving_program_kernels(net, eng, requests, "int8_kv")
    for r in rows:
        say(f"  {r['program']} {tuple(r['key'])}: bucket {r['bucket']} -> "
            f"{r['resolved']}, {r['mosaic_calls']} Mosaic dequant calls")
        if on_chip:
            # the resolver's word and the lowered program must agree: no
            # interpret mode, no silent XLA stand-in
            check((r["mosaic_calls"] > 0) == (r["resolved"] == "pallas"),
                  f"serve int8: {r} — resolver and lowered text disagree")
    if on_chip:
        check(any(r["program"] == "serving_decode" and r["bucket"] % 128 == 0
                  and r["mosaic_calls"] > 0 for r in rows),
              "serve int8: no decode program at a 128-multiple bucket "
              "calls the Pallas dequant kernel")
    rep["programs"] = rows
    return rep


# -- leg 4: kernels against the XLA reference --------------------------------

def _try_kernel(name: str, shape: str, fn, args, ref, want, rel_tol) -> dict:
    """Lower, compile and run one kernel entry; never raises for a compiler
    refusal — the leg fails at the end with every line printed."""
    import jax
    row = {"kernel": name, "shape": shape}
    try:
        lowered = jax.jit(fn).lower(*args)
        row["mosaic_calls"] = dict(mosaic_kernels(lowered.as_text()))
        got = jax.block_until_ready(lowered.compile()(*args))
    except Exception as e:  # the compiler's refusal IS the finding
        row.update(status="refused",
                   message=f"{type(e).__name__}: {e}"[:1200])
        say(f"  {name} {shape}: REFUSED {row['message'][:300]}")
        return row
    got = got if isinstance(got, (tuple, list)) else (got,)
    errs = [float(np.max(np.abs(np.asarray(g, np.float32) - r))
                  / max(float(np.max(np.abs(r))), 1e-30))
            for g, r in zip(got, ref)]
    row.update(status="compiled", rel_err=round(max(errs), 5),
               rel_tol=rel_tol, expected_mosaic=list(want))
    say(f"  {name} {shape}: compiled, max rel err {row['rel_err']} "
        f"(bound {rel_tol}), mosaic {row['mosaic_calls']}")
    return row


def leg_kernels(sz, on_chip: bool) -> list:
    import jax
    import jax.numpy as jnp
    from mxtpu.ops import attention as att, quant_attention as qa
    from mxtpu.quant import kv_quant
    interpret = not on_chip
    rows = []

    # flash forward + backward against the f32 'highest' reference
    fl = sz["flash"]
    B, H, D = fl["B"], fl["H"], fl["D"]
    scale = 1.0 / np.sqrt(D)
    for T, dt in fl["cases"]:
        rs = np.random.RandomState(T)
        q, k, v, g = (jnp.asarray(rs.randn(B, H, T, D), dt) for _ in range(4))
        with jax.default_matmul_precision("highest"):
            f32 = [a.astype(jnp.float32) for a in (q, k, v)]
            (ro, _), vjp = jax.vjp(lambda a, b, c: att._chunk_reference_lse(
                a, b, c, True, scale), *f32)
            rg = vjp((g.astype(jnp.float32), jnp.zeros((B, H, T))))
        ref = [np.asarray(r) for r in (ro,) + tuple(rg)]

        def fwd_bwd(q, k, v, g):
            o, lse = att._flash_attention_pallas(q, k, v, True, scale,
                                                 interpret=interpret)
            return (o,) + att._flash_backward_pallas(
                q, k, v, o, lse, g, True, scale, interpret=interpret)

        rows.append(_try_kernel(
            "flash fwd+bwd", f"B{B} H{H} T{T} D{D} {dt}", fwd_bwd,
            (q, k, v, g), ref, (FLASH_FWD, FLASH_BWD), KERNEL_REL_TOL[dt]))

    # dequant decode against dequantize-then-attend
    dc = sz["decode"]
    S, H, D = dc["S"], dc["H"], dc["D"]
    scale = 1.0 / np.sqrt(D)
    for mode, TOT in [("int8", t) for t in dc["int8_tot"]] \
            + [("fp8", dc["fp8_tot"])]:
        rs = np.random.RandomState(TOT)
        q = jnp.asarray(rs.randn(S, H, D), jnp.float32)
        kd, ks = kv_quant.quantize_rows(
            jnp.asarray(rs.randn(S, H, TOT, D), jnp.float32), mode)
        vd, vs = kv_quant.quantize_rows(
            jnp.asarray(rs.randn(S, H, TOT, D), jnp.float32), mode)
        pc = jnp.asarray(rs.randint(0, TOT, S).astype(np.int32)
                         ).at[0].set(TOT - 1).at[-1].set(0)
        with jax.default_matmul_precision("highest"):
            K, V = (kv_quant.dequantize_rows(d, s)
                    for d, s in ((kd, ks), (vd, vs)))
            s_ = jnp.einsum("bhd,bhtd->bht", q, K) * scale
            mask = jnp.arange(TOT)[None, None, :] <= pc[:, None, None]
            ref = jnp.einsum("bht,bhtd->bhd", jax.nn.softmax(
                jnp.where(mask, s_, -1e30), axis=-1), V)

        def decode(q, kd, ks, vd, vs, pc):
            return qa.dequant_attention_decode(
                q, kd, ks, vd, vs, pc, scale=scale, kernel="pallas",
                interpret=interpret)

        rows.append(_try_kernel(
            f"dequant decode {mode}", f"S{S} H{H} TOT{TOT} D{D}", decode,
            (q, kd, ks, vd, vs, pc), [np.asarray(ref)], (DEQUANT_DECODE,),
            KERNEL_REL_TOL["float32"]))

    # grouped matmuls (the expert products of a sparse layer): forward, dx
    # and the per-group dw against a per-group loop at 'highest'
    from mxtpu.ops import grouped_matmul as gm
    gr = sz["grouped"]
    M, K, N, sizes = gr["M"], gr["K"], gr["N"], gr["sizes"]
    rs = np.random.RandomState(M)
    x = jnp.asarray(rs.randn(M, K), jnp.bfloat16)
    w = jnp.asarray(rs.randn(len(sizes), K, N) * 0.05, jnp.bfloat16)
    dy = jnp.asarray(rs.randn(M, N), jnp.bfloat16)
    gs = jnp.asarray(sizes, jnp.int32)
    xf, wf, dyf = (np.asarray(a, np.float32) for a in (x, w, dy))
    ref = [np.zeros((M, N), np.float32), np.zeros((M, K), np.float32),
           np.zeros(wf.shape, np.float32)]
    at = 0
    for g_, n in enumerate(sizes):
        ref[0][at:at + n] = xf[at:at + n] @ wf[g_]
        ref[1][at:at + n] = dyf[at:at + n] @ wf[g_].T
        ref[2][g_] = xf[at:at + n].T @ dyf[at:at + n]
        at += n

    def grouped(x, w, dy, gs):
        return (gm._past_the_groups(
                    gm._gmm_pallas(x, w, gs, interpret=interpret), gs),
                gm._past_the_groups(
                    gm._gmm_pallas(dy, w, gs, True, interpret=interpret), gs),
                gm._tgmm_pallas(x, dy, gs, interpret=interpret))

    rows.append(_try_kernel(
        "grouped matmul fwd+dx+dw", f"M{M} K{K} N{N} groups {sizes}",
        grouped, (x, w, dy, gs), ref, GROUPED, KERNEL_REL_TOL["bfloat16"]))

    for r in rows:
        check(r["status"] == "compiled",
              f"kernel refused: {r['kernel']} {r['shape']}: {r.get('message')}")
        check(r["rel_err"] <= r["rel_tol"],
              f"kernel wrong: {r['kernel']} {r['shape']}: rel err "
              f"{r.get('rel_err')} > {r.get('rel_tol')}")
        if on_chip:
            missing = [k for k in r["expected_mosaic"]
                       if not r["mosaic_calls"].get(k)]
            check(not missing, f"{r['kernel']} {r['shape']}: {missing} are "
                               f"not Mosaic calls in the lowered program")
    return rows


# -- leg 4b: a tiny sparse decoder's train steps ------------------------------

def leg_sparse_train(sz, on_chip: bool) -> dict:
    """A few steps of a tiny sparse ``HybridDecoderLM`` (grouped-query window
    / full attention, post-norm RMSNorm, an untied head, an expert layer that
    holds a share of its experts, a shared expert, the balancing bias)
    through ``DataParallelTrainer``: the loss falls, the bias moves, every
    (token, expert) pair is counted, and on the chip the grouped matmuls and
    the flash launches are Pallas call sites."""
    import jax
    import mxtpu as mx
    from mxtpu import nd, optimizer, profiler
    from mxtpu.gluon.model_zoo.hybrid_decoder import HybridDecoderLM
    from mxtpu.parallel import DataParallelTrainer
    from mxtpu.parallel.mesh import data_parallel_mesh
    from mxtpu.parallel.moe import _row_tile
    sp = sz["sparse"]
    profiler.reset_kernel_path_counts()
    mx.random.seed(0)
    net = HybridDecoderLM(
        sp["vocab"], ["attn_window", "attn_full", "attn_window"],
        units=sp["units"], ffn_units=sp["ffn"], num_heads=sp["heads"],
        num_kv_heads=1, head_dim=sp["head_dim"], window=sp["T"] // 4,
        attention="gqa", qk_norm=True, rope_kinds=("attn_window",),
        rope_theta=1e6, norm="rms", norm_position="post", tie_head=False,
        mlp_kinds=["mlp", "moe", "moe"],
        moe=dict(ffn_units=sp["moe_ffn"], num_experts=sp["experts"],
                 top_k=sp["top_k"], held=sp["held"],
                 shared_ffn_units=sp["moe_ffn"], routed_scale=2.5,
                 bias_update_rate=0.03))
    net.initialize()
    if on_chip:
        net.cast("bfloat16")
    dpt = DataParallelTrainer(net, seq_loss,
                              optimizer.Adam(learning_rate=1e-3),
                              data_parallel_mesh(1))
    seq = np.random.RandomState(0).randint(0, sp["vocab"], (1, sp["T"] + 1))
    x, y = nd.array(seq[:, :-1]), nd.array(seq[:, 1:].astype(np.float32))
    losses = [float(dpt.step(x, y)) for _ in range(sp["steps"])]
    check(all(np.isfinite(losses)) and losses[-1] < losses[0],
          f"sparse train: losses {losses}")
    stats = profiler.get_moe_stats(net)
    check(len(stats) == 2 and all(r["pairs"] > 0 and r["passes"] == 1
                                  for r in stats), f"sparse train: {stats}")
    # the dispatch and combine moved the pairs' row tiles, not the buffer
    check(all(0 <= r["rows_moved"] - r["pairs"] < _row_tile(r["buffer_rows"])
              for r in stats), f"sparse train: rows moved {stats}")
    bias = net.block1.moe.select_bias.data().asnumpy()
    check(float(np.abs(bias).max()) > 0, "sparse train: the bias never moved")
    paths = profiler.get_kernel_path_counts()
    if on_chip:
        for kind in ("flash", "flash_window", "grouped_matmul"):
            check(paths[kind]["pallas"] > 0 and paths[kind]["xla"] == 0,
                  f"sparse train: {kind} call sites {paths[kind]}")
    return {"losses": [round(v, 4) for v in losses], "kernel_paths": paths,
            "pairs": [r["pairs"] for r in stats],
            "rows_moved": [r["rows_moved"] for r in stats]}


# -- leg 4c: a tiny conv + all-held sparse decoder's step against XLA ---------

@contextlib.contextmanager
def xla_formulations():
    """Every kernel site of a program traced inside takes its XLA
    formulation: the ops ask ``jax.default_backend()`` and are told "cpu".
    The arrays stay where they are, so on the chip XLA compiles those
    formulations for the chip."""
    import jax
    saved = jax.default_backend
    jax.default_backend = lambda: "cpu"
    try:
        yield
    finally:
        jax.default_backend = saved


def leg_conv_sparse_train(sz, on_chip: bool) -> dict:
    """One step of a tiny ``HybridDecoderLM`` of the third family (``conv``
    and ``attn_full`` mixers, pre-norm RMSNorm, q/k norm and rotary
    positions, a tied head with float32 logits, expert layers that hold ALL
    their experts and no shared one) through ``DataParallelTrainer``, twice
    from the same weights: as it runs (on the chip the flash launch and the
    grouped matmuls are Pallas call sites) and with every kernel site on its
    XLA formulation. The first loss and every parameter's first gradient
    (Adam's first moment after one step) must agree; every token has
    ``top_k`` held pairs."""
    import mxtpu as mx
    from mxtpu import nd, optimizer, profiler
    from mxtpu.gluon.model_zoo.hybrid_decoder import HybridDecoderLM
    from mxtpu.parallel import DataParallelTrainer
    from mxtpu.parallel.mesh import data_parallel_mesh
    cs = sz["conv_sparse"]
    seq = np.random.RandomState(1).randint(0, cs["vocab"], (1, cs["T"] + 1))
    x, y = nd.array(seq[:, :-1]), nd.array(seq[:, 1:].astype(np.float32))

    def one_step():
        mx.random.seed(3)           # the same draw both times
        net = HybridDecoderLM(
            cs["vocab"], ["conv", "attn_full", "conv"], units=cs["units"],
            ffn_units=cs["ffn"], num_heads=cs["heads"],
            num_kv_heads=cs["kv_heads"], head_dim=cs["head_dim"], d_conv=3,
            attention="gqa", qk_norm=True, rope_kinds=("attn_full",),
            rope_theta=1e6, norm="rms", float32_logits=True,
            mlp_kinds=["mlp", "moe", "moe"],
            moe=dict(ffn_units=cs["moe_ffn"], num_experts=cs["experts"],
                     top_k=cs["top_k"], bias_update_rate=0.03,
                     weight_eps=1e-6))
        net.initialize()
        if on_chip:
            net.cast("bfloat16")
        dpt = DataParallelTrainer(net, seq_loss,
                                  optimizer.Adam(learning_rate=1e-3),
                                  data_parallel_mesh(1))
        loss = float(dpt.step(x, y))
        moments = {name.split("_", 1)[1]: np.asarray(slots[0], np.float32)
                   for name, slots in dpt.optimizer_state_by_param().items()}
        return loss, moments, profiler.get_moe_stats(net)

    profiler.reset_kernel_path_counts()
    loss, moments, stats = one_step()
    paths = profiler.get_kernel_path_counts()
    with xla_formulations():
        want_loss, want, _ = one_step()
    # every expert held: each token's rows summed by gathers, none added
    check(all(r["pairs"] == cs["T"] * cs["top_k"] and r["passes"] == 1
              and r["held"] == cs["experts"] and r["rows_added"] == 0
              for r in stats)
          and len(stats) == 2, f"conv sparse train: {stats}")
    tol = 3e-2 if on_chip else 1e-4     # bfloat16 against bfloat16 / float32
    check(abs(loss - want_loss) <= tol * want_loss,
          f"conv sparse train: first loss {loss} against XLA's {want_loss}")
    check(set(moments) == set(want) and any("conv" in k for k in moments),
          f"conv sparse train: parameters {sorted(moments)}")
    gaps = {k: float(np.linalg.norm(moments[k] - want[k])
                     / max(np.linalg.norm(want[k]), 1e-30)) for k in want}
    worst = max(gaps, key=gaps.get)
    check(gaps[worst] <= 3 * tol,
          f"conv sparse train: first gradient of {worst} is "
          f"{gaps[worst]:.4g} from XLA's")
    if on_chip:
        for kind in ("flash", "grouped_matmul"):
            check(paths[kind]["pallas"] > 0 and paths[kind]["xla"] == 0,
                  f"conv sparse train: {kind} call sites {paths[kind]}")
    return {"loss": round(loss, 4), "xla_loss": round(want_loss, 4),
            "worst_gradient_gap": [worst, round(gaps[worst], 5)],
            "kernel_paths": paths, "pairs": [r["pairs"] for r in stats]}


# -- leg 4d: a tiny retention decoder's step against its lax form -------------

def leg_retention_train(sz, on_chip: bool) -> dict:
    """One step of a tiny ``HybridDecoderLM`` of the fourth family (every
    mixer a power-retention layer on grouped heads with q/k norm, rotary
    positions and a decay gate; pre-norm RMSNorm, an untied head, every
    block recomputed in the backward) through ``DataParallelTrainer``,
    twice from the same weights: as it runs (on the chip the
    ``retention_fwd`` / ``retention_bwd`` launches, the state carried over
    three chunks) and with the op on its chunked ``lax`` form. The first
    loss and every parameter's first gradient must agree."""
    import mxtpu as mx
    from mxtpu import nd, optimizer, profiler
    from mxtpu.gluon.model_zoo.hybrid_decoder import HybridDecoderLM
    from mxtpu.parallel import DataParallelTrainer
    from mxtpu.parallel.mesh import data_parallel_mesh
    rs = sz["retention"]
    seq = np.random.RandomState(2).randint(0, rs["vocab"], (1, rs["T"] + 1))
    x, y = nd.array(seq[:, :-1]), nd.array(seq[:, 1:].astype(np.float32))

    def one_step():
        mx.random.seed(5)           # the same draw both times
        net = HybridDecoderLM(
            rs["vocab"], ["retention"] * 2, units=rs["units"],
            ffn_units=rs["ffn"], num_heads=rs["heads"],
            num_kv_heads=rs["kv_heads"], head_dim=rs["head_dim"],
            layer_norm_eps=1e-6, attention="gqa", qk_norm=True,
            rope_kinds=("retention",), rope_theta=1e6, norm="rms",
            tie_head=False, remat=True)
        net.initialize()
        if on_chip:
            net.cast("bfloat16")
        dpt = DataParallelTrainer(net, seq_loss,
                                  optimizer.Adam(learning_rate=1e-3),
                                  data_parallel_mesh(1))
        loss = float(dpt.step(x, y))
        return loss, {
            name.split("_", 1)[1]: np.asarray(slots[0], np.float32)
            for name, slots in dpt.optimizer_state_by_param().items()}

    profiler.reset_kernel_path_counts()
    profiler.reset_launch_stats("retention")
    loss, moments = one_step()
    paths, stats = (profiler.get_kernel_path_counts(),
                    profiler.get_retention_stats())
    with xla_formulations():
        want_loss, want = one_step()
    tol = 3e-2 if on_chip else 1e-4     # bfloat16 against bfloat16 / float32
    check(abs(loss - want_loss) <= tol * want_loss,
          f"retention train: first loss {loss} against lax's {want_loss}")
    check(set(moments) == set(want)
          and any("gate" in k for k in moments),
          f"retention train: parameters {sorted(moments)}")
    gaps = {k: float(np.linalg.norm(moments[k] - want[k])
                     / max(np.linalg.norm(want[k]), 1e-30)) for k in want}
    worst = max(gaps, key=gaps.get)
    check(gaps[worst] <= 3 * tol,
          f"retention train: first gradient of {worst} is "
          f"{gaps[worst]:.4g} from the lax form's")
    check(stats["launches"] >= 2 and stats["state_bytes_kept"] > 0,
          f"retention train: {stats}")
    if on_chip:
        check(paths["retention"]["pallas"] > 0
              and paths["retention"]["xla"] == 0,
              f"retention train: call sites {paths['retention']}")
    return {"loss": round(loss, 4), "lax_loss": round(want_loss, 4),
            "worst_gradient_gap": [worst, round(gaps[worst], 5)],
            "kernel_paths": paths["retention"], "retention": stats}


# -- leg 4e: a tiny delta-rule / latent decoder's step against its lax form ---

def leg_kda_train(sz, on_chip: bool) -> dict:
    """One step of a tiny ``HybridDecoderLM`` of the fifth family (two
    Kimi-Delta-Attention layers and a latent-attention layer, pre-norm
    RMSNorm, an untied head) through ``DataParallelTrainer``, twice from the same
    weights: as it runs (on the chip the ``kda_fwd`` / ``kda_bwd`` launches
    on raw operands, the norms, the gate and the chunks' cumulative decays
    made inside them, the state carried over three chunks, and the flash
    launches at keys of 192 and values of 128) and with every kernel site
    on its XLA formulation (the delta rule's ``lax`` form, which runs the
    same prologue). The first loss and every parameter's first gradient must
    agree (``A_log``, ``dt_bias`` and ``f_proj`` among them: on the chip the
    backward kernel makes those gradients), with dense MLPs in every layer;
    the expert layers under their group-limited router run a step of their
    own (their grouped matmuls Pallas call sites on the chip)."""
    import mxtpu as mx
    from mxtpu import nd, optimizer, profiler
    from mxtpu.gluon.model_zoo.hybrid_decoder import HybridDecoderLM
    from mxtpu.parallel import DataParallelTrainer
    from mxtpu.parallel.mesh import data_parallel_mesh
    ks = sz["kda"]
    seq = np.random.RandomState(3).randint(0, ks["vocab"], (1, ks["T"] + 1))
    x, y = nd.array(seq[:, :-1]), nd.array(seq[:, 1:].astype(np.float32))

    def one_step(mlp_kinds=("mlp", "mlp", "mlp")):
        mx.random.seed(7)           # the same draw both times
        net = HybridDecoderLM(
            ks["vocab"], ["kda", "kda", "mla"], units=ks["units"],
            ffn_units=ks["ffn"], num_heads=ks["heads"],
            num_kv_heads=ks["heads"], head_dim=ks["head_dim"], d_conv=4,
            layer_norm_eps=1e-6, rope_theta=6e6, norm="rms", tie_head=False,
            mla=dict(ks["mla"], interleave=True),
            mlp_kinds=list(mlp_kinds),
            moe=dict(ffn_units=ks["moe_ffn"], num_experts=ks["experts"],
                     top_k=ks["top_k"], held=ks["held"],
                     shared_ffn_units=ks["moe_ffn"], routed_scale=2.5,
                     bias_update_rate=0.03, n_group=ks["n_group"],
                     topk_group=ks["topk_group"]))
        net.initialize()
        if on_chip:
            net.cast("bfloat16")
        dpt = DataParallelTrainer(net, seq_loss,
                                  optimizer.Adam(learning_rate=1e-3),
                                  data_parallel_mesh(1))
        loss = float(dpt.step(x, y))
        return loss, {
            name.split("_", 1)[1]: np.asarray(slots[0], np.float32)
            for name, slots in dpt.optimizer_state_by_param().items()}, \
            profiler.get_moe_stats(net)

    profiler.reset_kernel_path_counts()
    profiler.reset_launch_stats("kda")
    loss, moments, _ = one_step()
    stats = profiler.get_kda_stats()
    # the expert layers as they run, not compared: in bfloat16 a token's
    # choice of experts turns on the last bit, and the router's gradient
    # with it
    sparse_loss, _, rows = one_step(("mlp", "moe", "moe"))
    paths = profiler.get_kernel_path_counts()
    with xla_formulations():
        want_loss, want, _ = one_step()
    tol = 3e-2 if on_chip else 1e-4     # bfloat16 against bfloat16 / float32
    check(abs(loss - want_loss) <= tol * want_loss,
          f"kda train: first loss {loss} against lax's {want_loss}")
    check(set(moments) == set(want)
          and all(any(leaf in k for k in moments)
                  for leaf in ("dt_bias", "A_log", "f_proj"))
          and any("latentattention" in k for k in moments),
          f"kda train: parameters {sorted(moments)}")
    gaps = {k: float(np.linalg.norm(moments[k] - want[k])
                     / max(np.linalg.norm(want[k]), 1e-30)) for k in want}
    worst = max(gaps, key=gaps.get)
    check(gaps[worst] <= 3 * tol,
          f"kda train: first gradient of {worst} is {gaps[worst]:.4g} from "
          f"the lax form's")
    # the kernels keep each chunk's inverse for a backward that solves
    # nothing; the lax form keeps none
    check(stats["launches"] >= 2 and stats["state_bytes_kept"] > 0
          and (stats["inverse_bytes_kept"] > 0) == on_chip,
          f"kda train: {stats}")
    check(len(rows) == 2 and all(r["pairs"] > 0 for r in rows)
          and np.isfinite(sparse_loss),
          f"kda train: sparse loss {sparse_loss}, {rows}")
    if on_chip:
        for kind in ("kda", "flash", "grouped_matmul"):
            check(paths[kind]["pallas"] > 0 and paths[kind]["xla"] == 0,
                  f"kda train: {kind} call sites {paths[kind]}")
    return {"loss": round(loss, 4), "lax_loss": round(want_loss, 4),
            "sparse_loss": round(sparse_loss, 4),
            "worst_gradient_gap": [worst, round(gaps[worst], 5)],
            "kernel_paths": {k: paths[k] for k in ("kda", "flash")},
            "kda": stats, "pairs": [r["pairs"] for r in rows]}


# -- leg 4f: a tiny latent decoder with its prediction block, trained ---------

def leg_mtp_train(sz, on_chip: bool) -> dict:
    """One step of a tiny ``HybridDecoderLM`` of the sixth family (latent
    attention with a query latent and neither q/k norms nor a head gate in
    both layers, a dense and a sparse MLP, an untied head, and a
    multi-token-prediction block: ``mtp_layers=1``) through
    ``DataParallelTrainer`` under ``NextTokenLoss``, twice from the same
    weights: as it runs (on the chip three flash launches at keys of 192
    and values of 128, the block's among them, and the grouped matmuls) and
    with every kernel site on its XLA formulation, dense MLPs in every
    layer: first loss and every parameter's first gradient must agree, the
    block's and both tables' among them; with the second loss's weight at 0
    the loss must read lower and the block's parameters get no gradient. The
    expert layers run a step of their own."""
    import mxtpu as mx
    from mxtpu import nd, optimizer, profiler
    from mxtpu.gluon.loss import NextTokenLoss
    from mxtpu.gluon.model_zoo.hybrid_decoder import HybridDecoderLM
    from mxtpu.parallel import DataParallelTrainer
    from mxtpu.parallel.mesh import data_parallel_mesh
    ms = sz["mtp"]
    seq = np.random.RandomState(4).randint(0, ms["vocab"], (1, ms["T"] + 1))
    x, y = nd.array(seq[:, :-1]), nd.array(seq[:, 1:].astype(np.float32))

    def one_step(mlp_kinds=("mlp", "mlp"), weight=0.3):
        mx.random.seed(7)           # the same draw both times
        net = HybridDecoderLM(
            ms["vocab"], ["mla", "mla"], units=ms["units"],
            ffn_units=ms["ffn"], num_heads=ms["heads"],
            num_kv_heads=ms["heads"], layer_norm_eps=1e-6, rope_theta=32e6,
            norm="rms", tie_head=False,
            mla=dict(ms["mla"], interleave=True, qk_norm=False,
                     head_gate=False),
            mlp_kinds=list(mlp_kinds),
            moe=dict(ffn_units=ms["moe_ffn"], num_experts=ms["experts"],
                     top_k=ms["top_k"], held=ms["held"],
                     shared_ffn_units=ms["moe_ffn"], routed_scale=2.5,
                     bias_update_rate=0.03),
            mtp_layers=1)
        net.initialize()
        if on_chip:
            net.cast("bfloat16")
        dpt = DataParallelTrainer(net, NextTokenLoss(weight=weight),
                                  optimizer.Adam(learning_rate=1e-3),
                                  data_parallel_mesh(1))
        loss = float(dpt.step(x, y))
        return loss, {
            name.split("_", 1)[1]: np.asarray(slots[0], np.float32)
            for name, slots in dpt.optimizer_state_by_param().items()}, \
            profiler.get_moe_stats(net)

    profiler.reset_kernel_path_counts()
    profiler.reset_launch_stats("mtp")
    loss, moments, _ = one_step()
    stats = profiler.get_launch_stats("mtp")
    sparse_loss, _, rows = one_step(("mlp", "moe"))
    paths = profiler.get_kernel_path_counts()
    main_loss, main_only, _ = one_step(weight=0.0)
    with xla_formulations():
        want_loss, want, _ = one_step()
    tol = 3e-2 if on_chip else 1e-4     # bfloat16 against bfloat16 / float32
    check(abs(loss - want_loss) <= tol * want_loss,
          f"mtp train: first loss {loss} against XLA's {want_loss}")
    block = [k for k in moments if "multitokenprediction" in k]
    check(set(moments) == set(want) and len(block) > 10
          and any("latentattention0_dense1" in k for k in block),
          f"mtp train: parameters {sorted(moments)}")
    gaps = {k: float(np.linalg.norm(moments[k] - want[k])
                     / max(np.linalg.norm(want[k]), 1e-30)) for k in want}
    worst = max(gaps, key=gaps.get)
    check(gaps[worst] <= 3 * tol,
          f"mtp train: first gradient of {worst} is {gaps[worst]:.4g} from "
          f"the XLA formulation's")
    # the second loss is a term of its own: without it the loss reads lower
    # by about 0.3 ln(vocab) and the block gets no gradient at all
    check(main_loss < loss - 0.2 * np.log(ms["vocab"])
          and all(not np.any(main_only[k]) for k in block)
          and all(np.any(moments[k]) for k in block),
          f"mtp train: loss {loss} against {main_loss} without the second "
          f"term")
    check(stats["launches"] >= 1 and stats["depth"] == 1
          and stats["positions"] == ms["T"] - 1
          and stats["logits_bytes"] == ms["T"] * ms["vocab"] * 4,
          f"mtp train: {stats}")
    check(len(rows) == 2 and all(r["pairs"] > 0 for r in rows)
          and np.isfinite(sparse_loss),
          f"mtp train: sparse loss {sparse_loss}, {rows}")
    if on_chip:
        for kind in ("flash", "grouped_matmul"):
            check(paths[kind]["pallas"] > 0 and paths[kind]["xla"] == 0,
                  f"mtp train: {kind} call sites {paths[kind]}")
    return {"loss": round(loss, 4), "xla_loss": round(want_loss, 4),
            "main_loss": round(main_loss, 4),
            "sparse_loss": round(sparse_loss, 4),
            "worst_gradient_gap": [worst, round(gaps[worst], 5)],
            "kernel_paths": {"flash": paths["flash"]}, "mtp": stats,
            "pairs": [r["pairs"] for r in rows]}



# -- leg 4g: a tiny Mamba / attention decoder's step, blocks recomputed -------

def leg_jamba_train(sz, on_chip: bool) -> dict:
    """One step of a tiny ``HybridDecoderLM`` of the seventh family (Mamba-1
    mixers with an RMSNorm on each of ``dt``, ``B`` and ``C`` around one
    grouped-query attention layer without positions, pre-norm RMSNorm, a
    tied head with float32 logits, every block but the last recomputed in
    the backward) through ``DataParallelTrainer``, twice from the same
    weights: as it runs (on the chip the ``ssm_scan_fwd`` launches, the
    recomputed blocks' a second time, ``ssm_scan_bwd`` and the flash
    launches) and with every kernel site on its XLA formulation. The first
    loss and every parameter's first gradient must agree."""
    import mxtpu as mx
    from mxtpu import nd, optimizer, profiler
    from mxtpu.gluon.model_zoo.hybrid_decoder import HybridDecoderLM
    from mxtpu.parallel import DataParallelTrainer
    from mxtpu.parallel.mesh import data_parallel_mesh
    js = sz["jamba"]
    kinds = ["mamba", "mamba", "attn_full", "mamba"]
    seq = np.random.RandomState(4).randint(0, js["vocab"], (1, js["T"] + 1))
    x, y = nd.array(seq[:, :-1]), nd.array(seq[:, 1:].astype(np.float32))

    def one_step():
        mx.random.seed(9)           # the same draw both times
        net = HybridDecoderLM(
            js["vocab"], kinds, units=js["units"], ffn_units=js["ffn"],
            num_heads=js["heads"], num_kv_heads=js["kv_heads"],
            d_state=js["d_state"], dt_rank=js["dt_rank"],
            layer_norm_eps=1e-6, attention="gqa", norm="rms", tie_head=True,
            float32_logits=True, mamba_inner_norm=True, remat=True)
        net.initialize()
        if on_chip:
            net.cast("bfloat16")
        dpt = DataParallelTrainer(net, seq_loss,
                                  optimizer.Adam(learning_rate=1e-3),
                                  data_parallel_mesh(1))
        loss = float(dpt.step(x, y))
        return loss, {
            name.split("_", 1)[1]: np.asarray(slots[0], np.float32)
            for name, slots in dpt.optimizer_state_by_param().items()}, \
            mosaic_kernels(dpt.lowered().as_text())

    profiler.reset_kernel_path_counts()
    profiler.reset_launch_stats("ssm_scan")
    profiler.reset_remat_stats()
    loss, moments, kernels = one_step()
    paths, stats, remat = (profiler.get_kernel_path_counts(),
                           profiler.get_launch_stats("ssm_scan"),
                           profiler.get_remat_stats())
    with xla_formulations():
        want_loss, want, _ = one_step()
    tol = 3e-2 if on_chip else 1e-4     # bfloat16 against bfloat16 / float32
    check(abs(loss - want_loss) <= tol * want_loss,
          f"jamba train: first loss {loss} against XLA's {want_loss}")
    check(set(moments) == set(want)
          and sum("rmsnorm" in k and "mamba" in k for k in moments) == 9,
          f"jamba train: parameters {sorted(moments)}")
    gaps = {k: float(np.linalg.norm(moments[k] - want[k])
                     / max(np.linalg.norm(want[k]), 1e-30)) for k in want}
    worst = max(gaps, key=gaps.get)
    check(gaps[worst] <= 3 * tol,
          f"jamba train: first gradient of {worst} is {gaps[worst]:.4g} "
          f"from the XLA formulation's")
    check(remat == {"blocks": 4, "recomputed": 3,
                    "kinds": {"mamba": 2, "attn_full": 1}},
          f"jamba train: recomputed {remat}")
    check(stats["launches"] >= 3 and stats["channels"] == 2 * js["units"],
          f"jamba train: {stats}")
    if on_chip:
        # three scan call sites a trace (the step is traced for the run and
        # again for the lowered text), none on the XLA formulation
        check(paths["ssm_scan"]["pallas"] >= 3
              and paths["ssm_scan"]["xla"] == 0
              and paths["flash"]["xla"] == 0 and paths["flash"]["pallas"] > 0,
              f"jamba train: call sites {paths}")
        # three scans forward and the two recomputed blocks' again
        check(kernels["ssm_scan_fwd"] == 5 and kernels["ssm_scan_bwd"] == 3
              and kernels[FLASH_FWD] == 2 and stats["chunk_start_bytes"] > 0,
              f"jamba train: launches {dict(kernels)}, {stats}")
    return {"loss": round(loss, 4), "xla_loss": round(want_loss, 4),
            "worst_gradient_gap": [worst, round(gaps[worst], 5)],
            "kernel_paths": {k: paths[k] for k in ("ssm_scan", "flash")},
            "ssm_scan": stats, "remat": remat}


# -- leg 5: four chips -------------------------------------------------------

def placement(arrays: dict, devices) -> dict:
    """Per-device bytes in use and the device set of each named array."""
    rep = {"bytes_in_use": {
        str(d.id): (d.memory_stats() or {}).get("bytes_in_use")
        for d in devices}}
    for name, a in arrays.items():
        rep[name] = {"shape": list(a.shape), "sharding": str(a.sharding.spec)
                     if hasattr(a.sharding, "spec") else str(a.sharding),
                     "devices": sorted(d.id for d in a.sharding.device_set)}
    return rep


def check_spread(rep: dict, what: str) -> None:
    """Fails if anything sits on fewer devices than it was given."""
    ids = sorted(int(i) for i in rep["bytes_in_use"])
    used = [v for v in rep["bytes_in_use"].values() if v is not None]
    # the CPU backend reports no memory_stats; the chip does
    check(len(used) in (0, len(ids)) and all(v > 0 for v in used),
          f"{what}: bytes_in_use {rep['bytes_in_use']} — not every device "
          f"holds data")
    for name, r in rep.items():
        if name != "bytes_in_use":
            check(r["devices"] == ids,
                  f"{what}: {name} sits on devices {r['devices']} of {ids}")
    say(f"  {what} placement: " + json.dumps(rep))


def kv_cache_array(net, slots: int):
    """The engine's live KV cache, found among jax's live arrays by its
    (L, 2, slots, H, TOT, D) geometry."""
    import jax
    from mxtpu.serving import kv
    L, H, D = kv.cache_dims(net)
    found = [a for a in jax.live_arrays() if a.ndim == 6
             and a.shape[:4] == (L, 2, slots, H) and a.shape[5] == D]
    check(found, "no live KV cache array")
    return max(found, key=lambda a: a.shape[4])


def leg_multichip(sz, on_chip: bool) -> dict:
    import jax
    from jax.sharding import Mesh
    rep, dpt, (x, _) = leg_train(sz, sz["multi"]["B"], on_chip)
    p0 = next(iter(dpt.block.collect_params().values())).data().data
    rep["placement"] = placement(
        {"param": p0, "optimizer_slot": dpt.optimizer_slots()[0],
         "batch": x.data}, jax.devices())
    check_spread(rep["placement"], f"train dp={rep['dp']}")
    del dpt, x, p0
    gc.collect()

    net = new_net(sz)
    four = jax.devices()[:4]
    spec = dict(sz["serve"], n=sz["multi"]["serve_n"])
    srv, _, _, eng = serve_and_check(
        sz, net, spec, 3, "serve fsdp×tp",
        mesh=Mesh(np.array(four).reshape(2, 2), ("fsdp", "tp")))
    srv["placement"] = placement(
        {"kv_cache": kv_cache_array(net, spec["slots"])}, four)
    check_spread(srv["placement"], "serve fsdp×tp")
    del eng
    return {"train": rep, "serve": srv}


# -- driver ------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="run on the CPU at the tiny preset with kernels in "
                         "interpret mode (checks this script, not the chip)")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    import jax
    if args.rehearsal:
        jax.config.update("jax_platforms", "cpu")
    platform = jax.default_backend()
    if not args.rehearsal and platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform {platform!r} "
              f"(the CPU rehearsal is --rehearsal)", file=sys.stderr)
        return 1
    on_chip = not args.rehearsal
    sz = SIZES["chip" if on_chip else "rehearsal"]

    import jaxlib
    try:
        from mxtpu import compile_cache
    except ImportError as e:
        print(f"chip_smoke: needs the mxtpu package beside it in {HERE} "
              f"({e})", file=sys.stderr)
        return 1
    cache_dir = compile_cache.place()
    counter = CompileCounter()
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:
        libtpu = None
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    report = {"ok": False, "device": device, "rehearsal": args.rehearsal,
              "versions": {"jax": jax.__version__,
                           "jaxlib": jaxlib.__version__, "libtpu": libtpu},
              "compile_cache": {"dir": cache_dir,
                                "entries_at_start": entries},
              "legs": {}}
    say(f"device {device}; versions {report['versions']}; compile cache "
        f"{cache_dir} ({entries} entries at start)")

    def leg(name, fn, *a):
        before, t0 = counter.snapshot(), time.perf_counter()
        out = fn(*a)
        rep = {"wall_s": round(time.perf_counter() - t0, 2),
               "compile": counter.delta(counter.snapshot(), before)}
        report["legs"][name] = rep
        say(f"leg {name}: {rep['wall_s']}s, {rep['compile']}")
        return rep, out

    try:
        rep, (train, dpt, batch) = leg("train", leg_train, sz,
                                       sz["train"]["B"], on_chip)
        rep.update(train, live_arrays=assert_all_on(platform, "train"))
        del dpt, batch
        gc.collect()

        net = new_net(sz)
        rep, out = leg("serve", leg_serve, sz, net)
        rep.update(out, live_arrays=assert_all_on(platform, "serve"))
        rep, out = leg("serve_int8", leg_serve_int8, sz, net, on_chip)
        rep.update(out, live_arrays=assert_all_on(platform, "serve int8"))
        del net
        gc.collect()

        rep, out = leg("kernels", leg_kernels, sz, on_chip)
        rep["rows"] = out
        rep, out = leg("sparse_train", leg_sparse_train, sz, on_chip)
        rep.update(out)
        rep, out = leg("conv_sparse_train", leg_conv_sparse_train, sz,
                       on_chip)
        rep.update(out)
        rep, out = leg("retention_train", leg_retention_train, sz, on_chip)
        rep.update(out)
        rep, out = leg("kda_train", leg_kda_train, sz, on_chip)
        rep.update(out)
        rep, out = leg("mtp_train", leg_mtp_train, sz, on_chip)
        rep.update(out)
        rep, out = leg("jamba_train", leg_jamba_train, sz, on_chip)
        rep.update(out)

        if len(devs) >= 4:
            rep, out = leg("multichip", leg_multichip, sz, on_chip)
            rep.update(out)
            report["multichip"] = f"ran on {len(devs)} devices"
        else:
            report["multichip"] = f"skipped: {len(devs)} device(s)"
        say(f"multichip: {report['multichip']}")
        report["ok"] = True
    finally:
        report["wall_s"] = round(time.perf_counter() - t_start, 2)
        report["compile"] = counter.snapshot()
        os.makedirs(OUT_DIR, exist_ok=True)
        with open(os.path.join(OUT_DIR, "report.json"), "w") as f:
            json.dump(report, f, indent=1)

    say(f"done in {report['wall_s']}s; compile {report['compile']}")
    print(json.dumps({
        "ok": True, "device": device, "rehearsal": args.rehearsal,
        "versions": report["versions"],
        "compile_cache": report["compile_cache"],
        "wall_s": report["wall_s"], "compile": report["compile"],
        "losses": report["legs"]["train"]["losses"],
        "serve_worst_sigma": report["legs"]["serve"]["worst_sigma"],
        "serve_int8_worst_sigma": report["legs"]["serve_int8"]["worst_sigma"],
        "kernels": {f"{r['kernel']} @ {r['shape']}": r["status"]
                    for r in report["legs"]["kernels"]["rows"]},
        "multichip": report["multichip"], "claim": None}))
    # the verdict: last line, these two keys and no other
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
