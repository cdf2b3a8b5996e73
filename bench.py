"""Benchmark: ResNet-50 synthetic-data training throughput through the
framework's own path (DataParallelTrainer + optimizer.SGD kernels), plus a
``benchmark_score.py``-parity inference sweep over the model zoo.

Mirrors the reference's headline harnesses (BASELINE.md):
* ``train_imagenet.py --benchmark 1`` — synthetic fwd+bwd+SGD-momentum steps.
  Baseline: 109 img/s (ResNet-50, 1x K80, batch 32).
* ``example/image-classification/benchmark_score.py:46-82`` — inference img/s
  sweep over zoo models.

Accounting: every timing here syncs by reading the loss scalar back to the
host, which waits for the device (so does ``jax.block_until_ready`` — checked
on the v5e in PR 21; the loops predate that and are left as they are).
Throughput is measured over a long pipelined run (steps chain through the
params, forcing sequential execution) with a single final readback; the
per-step "sync" distribution stops the pipeline every step and is reported
only as an upper bound. FLOPs/step come from XLA's own cost model
(compiled.cost_analysis), MFU from the documented peak of the detected chip.
fp32 convolutions on TPU execute as bf16 passes on the MXU, so the bf16 peak
is the denominator for both precisions.

Prints ONE JSON line on stdout; the detailed report goes to stderr.

Scoreboard contract (ROADMAP item 4): every scenario runs under ``run_leg``
crash containment — one retry with backoff on transient backend errors
(UNAVAILABLE / init failures), an ``{"error": ...}`` leg entry otherwise —
so the JSON line always ships with rc=0 and every healthy leg populated.
Headline metrics (img/s, MFU, steps/s) ratchet against
``BENCH_BASELINE.json`` (``apply_ratchet``: baselines only move up;
regressions beyond MXTPU_BENCH_RATCHET_TOL are reported, never fatal). The
``"mfu"`` and ``"trace"`` blocks come from ``mxtpu.observability`` — see
docs/observability.md.

Scenario-only CLI: ``bench.py resilience`` (fault-injection/supervised
resume) and ``bench.py serving`` (Poisson-arrival continuous-batching
latency/goodput — see docs/serving.md) each emit their own one-line JSON.
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Optional

# The explicit CPU harness (tier 1 drives it): MXTPU_BENCH_FALLBACK=1 pins
# the platform before jax initializes a backend. It is only ever asked for;
# a chip run whose backend does not come up fails in main().
if os.environ.get("MXTPU_BENCH_FALLBACK") == "1":
    os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np

BASELINE_IMG_S = 109.0  # ResNet-50, 1x K80, batch 32 (BASELINE.md row 5)

TRAIN_CONFIGS = [
    # (tag, dtype, batch, sync_steps, pipelined_steps, micro_batches)
    # mfu_probe (benchmark/python/mfu_probe.py, round 4): the step is
    # HBM-traffic-bound (arith intensity 57-72 flop/B vs the v5e ridge of
    # ~240), micro-batch 128 is the per-image optimum, and monolithic large
    # batches lose to HBM-capacity pressure (b512 peaks at 15.3/16 GB).
    # Gradient accumulation (micro_batches) keeps the b128 working set at any
    # global batch: b512x4 = 2519 img/s vs 2240 monolithic, monotone scaling.
    ("fp32_b32", "float32", 32, 5, 100, 1),
    ("bf16_b128", "bfloat16", 128, 5, 100, 1),
    ("bf16_b512x4", "bfloat16", 512, 3, 40, 4),
]

SCORE_MODELS = [
    # (name, image size) — benchmark_score.py model list, TPU-feasible subset
    ("alexnet", 224),
    ("resnet50_v1", 224),
    ("mobilenet1.0", 224),
    ("inceptionv3", 299),
]
SCORE_BATCHES = [1, 32]


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def _device_peak():
    """Chip kind + documented peak TFLOP/s — the canonical table now lives in
    ``mxtpu.observability.flops`` (cpu hosts get the nominal ratchet
    heuristic documented there)."""
    from mxtpu.observability import flops as flops_mod
    return flops_mod.device_peak()


# ---------------------------------------------------------------------------
# scoreboard hardening (ROADMAP item 4: a transient backend UNAVAILABLE must
# never erase the whole round again — the round-5 capture lost every leg
# to an rc=1 at backend init)
# ---------------------------------------------------------------------------

def _retry_backoff_s() -> float:
    try:
        return float(os.environ.get("MXTPU_BENCH_RETRY_BACKOFF_S", "2.0"))
    except ValueError:
        return 2.0


def _parse_fail_spec() -> dict:
    """Fault-injection seam (tests): ``MXTPU_BENCH_FAIL_LEG=leg[:n][,leg2…]``
    makes the named leg raise a simulated transient backend error — ``n``
    times (then succeed; exercises the retry path) or every time when ``n``
    is omitted (exercises the error-JSON path)."""
    spec = os.environ.get("MXTPU_BENCH_FAIL_LEG", "")
    out = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        if ":" in part:
            name, _, n = part.partition(":")
            try:
                out[name] = int(n)
            except ValueError:
                out[name] = -1
        else:
            out[part] = -1          # -1: fail every attempt
    return out


_FAIL_LEGS = _parse_fail_spec()


def _maybe_inject_failure(name: str):
    left = _FAIL_LEGS.get(name)
    if left is None or left == 0:
        return
    if left > 0:
        _FAIL_LEGS[name] = left - 1
    raise RuntimeError(
        f"UNAVAILABLE: injected transient backend error for leg {name!r} "
        "(MXTPU_BENCH_FAIL_LEG test seam)")


def run_leg(name: str, fn, *args, **kwargs):
    """Run one scoreboard scenario under the crash containment contract:
    transient backend errors are retried by THE shared policy
    (``mxtpu.resilience.retry_transient`` — bounded exponential backoff,
    ``MXTPU_RETRY_MAX`` retries, base ``MXTPU_BENCH_RETRY_BACKOFF_S``;
    replaces this harness's old ad-hoc one-retry); any failure becomes a
    ``{"error": ...}`` leg result instead of killing the process, so the
    JSON line always ships with every other leg populated (rc stays 0)."""
    from mxtpu.resilience import RetryError, retry_transient
    attempts = {"n": 0}

    def _attempt():
        attempts["n"] += 1
        _maybe_inject_failure(name)
        return fn(*args, **kwargs)

    def _note(exc, attempt):
        log(f"[bench] leg {name!r} hit a transient backend error "
            f"({type(exc).__name__}: {exc}); retrying (attempt "
            f"{attempt + 2})")

    try:
        return retry_transient(_attempt, label=f"bench.{name}",
                               base_backoff_s=_retry_backoff_s(),
                               on_retry=_note)
    except (KeyboardInterrupt, SystemExit):
        raise
    except BaseException as e:
        src = e.__cause__ if isinstance(e, RetryError) \
            and e.__cause__ is not None else e
        err = f"{type(src).__name__}: {src}"
        import traceback
        log(f"[bench] leg {name!r} FAILED "
            f"({'after retries' if attempts['n'] > 1 else 'non-transient'}):\n"
            + traceback.format_exc())
        return {"error": err, "leg": name, "retried": attempts["n"] > 1}


def _leg_ok(res) -> bool:
    return isinstance(res, dict) and "error" not in res


def bench_train(tag, dtype, batch, sync_steps, pipelined_steps,
                micro_batches=1):
    """Train ResNet-50 through DataParallelTrainer + optimizer.SGD."""
    import jax
    import jax.numpy as jnp

    from mxtpu import nd, optimizer as opt_mod
    from mxtpu.gluon.loss import SoftmaxCrossEntropyLoss
    from mxtpu.gluon.model_zoo import vision
    from mxtpu.parallel import DataParallelTrainer
    from mxtpu.parallel.mesh import data_parallel_mesh

    net = vision.resnet50_v1(classes=1000)
    net.initialize()
    if dtype != "float32":
        net.cast(dtype)

    mesh = data_parallel_mesh()
    optimizer = opt_mod.SGD(learning_rate=0.05, momentum=0.9, wd=1e-4)
    dpt = DataParallelTrainer(net, SoftmaxCrossEntropyLoss(), optimizer, mesh,
                              micro_batches=micro_batches)

    rs = np.random.RandomState(0)
    # pre-place the synthetic batch on device (reference parity:
    # train_imagenet.py --benchmark also reuses one resident batch), so the
    # host->chip transfer stays out of the step time
    from mxtpu.parallel import shard_batch
    x = shard_batch(nd.array(rs.rand(batch, 3, 224, 224).astype(dtype)), mesh)
    y = shard_batch(nd.array(rs.randint(0, 1000, batch).astype(np.int32)), mesh)

    def sync(ndarr):
        return float(ndarr.data)    # host readback: the only real barrier here

    # warmup (includes compile)
    t0 = time.perf_counter()
    for _ in range(3):
        loss = dpt.step_async(x, y)
    sync(loss)
    compile_s = time.perf_counter() - t0

    # per-step upper bound (each sample stops the pipeline for a readback)
    sync_times = []
    for _ in range(sync_steps):
        t0 = time.perf_counter()
        loss = dpt.step_async(x, y)
        sync(loss)
        sync_times.append(time.perf_counter() - t0)
    sync_times = np.array(sync_times)

    # pipelined throughput: steps chain through params, one final readback
    t0 = time.perf_counter()
    for _ in range(pipelined_steps):
        loss = dpt.step_async(x, y)
    sync(loss)
    pipelined_dt = time.perf_counter() - t0

    img_s = pipelined_steps * batch / pipelined_dt
    step_ms = 1e3 * pipelined_dt / pipelined_steps

    # FLOP accounting from XLA's own cost model
    ca = dpt.cost_analysis()
    xla_flops = float(ca.get("flops", 0.0))
    if micro_batches > 1:
        # XLA's cost model counts a scan body ONCE regardless of trip count —
        # scale by k (the update outside the scan is <0.1% of the total)
        xla_flops *= micro_batches
    # analytic cross-check: ResNet-50@224 fwd ~4.1 GFLOP/img, bwd ~2x fwd
    analytic_flops = 3 * 4.1e9 * batch

    kind, peak_tf = _device_peak()
    mfu = (xla_flops / (step_ms / 1e3)) / (peak_tf * 1e12) if peak_tf else None

    log(f"[train {tag}] batch={batch} dtype={dtype} compile+warmup={compile_s:.1f}s")
    log(f"[train {tag}] per-step incl. host-sync round-trip (upper bound): "
        f"median={np.median(sync_times)*1e3:.2f} ms "
        f"p90={np.percentile(sync_times,90)*1e3:.2f} ms")
    log(f"[train {tag}] pipelined: {step_ms:.2f} ms/step -> {img_s:.0f} img/s")
    log(f"[train {tag}] flops/step: XLA={xla_flops/1e9:.1f}G "
        f"analytic~{analytic_flops/1e9:.1f}G; chip={kind} peak={peak_tf} TF "
        f"-> MFU={100*mfu:.1f}%" if mfu is not None else
        f"[train {tag}] flops/step: XLA={xla_flops/1e9:.1f}G (unknown chip peak)")
    return {
        "img_s": round(img_s, 1),
        "step_ms": round(step_ms, 3),
        "steps_per_sec": round(1e3 / step_ms, 3),
        "sync_step_ms_median": round(float(np.median(sync_times)) * 1e3, 3),
        # per-step tail latency (sync distribution — includes one host
        # readback per sample, so an upper bound; see module docstring)
        "p50_step_ms": round(float(np.percentile(sync_times, 50)) * 1e3, 3),
        "p99_step_ms": round(float(np.percentile(sync_times, 99)) * 1e3, 3),
        "xla_gflops_per_step": round(xla_flops / 1e9, 1),
        "mfu": round(mfu, 4) if mfu is not None else None,
    }


def bench_inference():
    """benchmark_score.py parity: hybridized predict img/s over the zoo.

    Two measurements per config: the per-call loop (reference parity — pays
    one jit dispatch per forward) and a CHAINED scan of n forwards inside one
    compiled program (dispatch-independent — the chip's actual model
    throughput). The JSON reports the chained number; per-call goes to the
    log."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from mxtpu import autograd, nd
    from mxtpu.gluon.model_zoo import vision
    from mxtpu.ndarray.ndarray import NDArray

    results = {}
    for name, size in SCORE_MODELS:
        net = vision.get_model(name, classes=1000)
        net.initialize()

        # phase 1 — chained via the PUBLIC serving API
        # (mxtpu.serving.ChainedPredictor / Module.predict(chain=n)): n
        # forwards in ONE compiled scan, one dispatch per chain. Must trace
        # the PLAIN block (a hybridized CachedOp draws rng keys at its own
        # trace time — tracing it inside an outer jit leaks tracers), so ALL
        # chained measurements run before hybridize().
        from mxtpu.serving import ChainedPredictor
        for batch in SCORE_BATCHES:
            x = nd.array(np.random.rand(batch, 3, size, size).astype(np.float32))
            n = 50 if batch == 1 else 20
            with autograd.predict_mode():
                net(x)          # materialize deferred params EAGERLY (their
                                # init draws rng keys — must not happen inside
                                # the scan trace)
            cp = ChainedPredictor(net, chain=n)
            stack = NDArray(jnp.broadcast_to(x.data, (n,) + x.data.shape))
            outs = cp.predict_stack(stack)        # compile
            np.asarray(jax.device_get(outs[0].data))
            t0 = time.perf_counter()
            outs = cp.predict_stack(stack)
            # ONE D2H readback syncs the chain — no extra eager dispatches
            # inside the timed window
            r = float(np.asarray(jax.device_get(outs[0].data)).ravel()[0])
            dt_chain = time.perf_counter() - t0
            assert np.isfinite(r)
            # _chained key: NEW metric, kept separate so round-over-round
            # comparisons of the original per-call keys stay apples-to-apples
            results[f"{name}_b{batch}_chained"] = round(n * batch / dt_chain,
                                                        1)

        # phase 2 — per-call loop over the hybridized net (reference-parity
        # path; pays one dispatch per forward)
        net.hybridize(static_alloc=True)
        for batch in SCORE_BATCHES:
            x = nd.array(np.random.rand(batch, 3, size, size).astype(np.float32))
            n = 50 if batch == 1 else 20
            with autograd.predict_mode():
                out = net(x)                      # compile the per-call path
                float(jnp.sum(out.data))
                t0 = time.perf_counter()
                for _ in range(n):
                    out = net(x)
                float(jnp.sum(out.data))          # TPU queue is FIFO
                dt = time.perf_counter() - t0
            results[f"{name}_b{batch}"] = round(n * batch / dt, 1)
            log(f"[score] {name} batch={batch}: "
                f"{results[f'{name}_b{batch}_chained']:.1f} img/s chained "
                f"({results[f'{name}_b{batch}']:.1f} per-call)")
    return results


def bench_word_lm(steps: int = 30):
    """Word-language-model training throughput (BASELINE config #3:
    example/gluon/word_language_model LSTM + the cuDNN RNN path — here the
    fused lax.scan RNN). 2-layer LSTM 650/650 (the reference's --large
    config), T=35 BPTT, batch 128, synthetic token stream; reports tokens/s
    through DataParallelTrainer (fwd+bwd+update in one program)."""
    from mxtpu import nd, optimizer as opt_mod
    from mxtpu.gluon import nn, rnn
    from mxtpu.gluon.block import HybridBlock
    from mxtpu.gluon.loss import SoftmaxCrossEntropyLoss
    from mxtpu.parallel import DataParallelTrainer, shard_batch
    from mxtpu.parallel.mesh import data_parallel_mesh

    vocab, embed, hidden, layers, T, B = 10000, 650, 650, 2, 35, 128

    class LMBlock(HybridBlock):
        def __init__(self):
            super().__init__()
            self.embedding = nn.Embedding(vocab, embed)
            self.lstm = rnn.LSTM(hidden, num_layers=layers, layout="TNC",
                                 input_size=embed)
            self.decoder = nn.Dense(vocab, in_units=hidden, flatten=False)

        def forward(self, x):
            out = self.lstm(self.embedding(x))   # states=None -> out only
            return self.decoder(out)

    net = LMBlock()
    net.initialize()
    mesh = data_parallel_mesh()
    # dp shards the BATCH axis, which is axis 1 under TNC — transpose in/out
    # at the bench level instead: feed (N, T) and let the block transpose
    rs = np.random.RandomState(0)
    x_tokens = rs.randint(0, vocab, (T, B)).astype(np.int32)
    y_tokens = np.roll(x_tokens, -1, axis=0).astype(np.int32)

    class LMWrap(HybridBlock):
        def __init__(self, inner):
            super().__init__()
            self.inner = inner          # attribute assignment auto-registers

        def forward(self, x):                  # x (N, T) -> logits (N*T, V)
            from mxtpu.ndarray.ndarray import NDArray
            logits = self.inner(NDArray(x.data.T))       # (T, N, V)
            return NDArray(logits.data.reshape(-1, vocab))

    wrap = LMWrap(net)
    dpt = DataParallelTrainer(
        wrap, SoftmaxCrossEntropyLoss(),
        opt_mod.SGD(learning_rate=1.0, momentum=0.9), mesh)
    # pre-shard once like bench_train — per-step placement would change the
    # methodology vs the train legs
    x = shard_batch(nd.array(x_tokens.T), mesh)   # (N, T): dp shards axis 0
    # labels flatten T-major to pair with logits.reshape(-1, V) from (T,N,V)
    y = shard_batch(nd.array(y_tokens.reshape(-1).astype(np.float32)), mesh)

    loss = dpt.step_async(x, y)
    loss_start = float(loss.data)               # compile + first step
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = dpt.step_async(x, y)
    final = float(loss.data)
    dt = time.perf_counter() - t0
    tok_s = steps * T * B / dt
    # learning gate (round-4 verdict weak #5): memorizing the fixed batch must
    # drive the loss down — throughput from a non-learning step never enters
    # the BENCH JSON
    if not final < loss_start - 0.1:
        raise RuntimeError(
            f"word_lm learning gate FAILED: loss {loss_start:.3f} -> "
            f"{final:.3f}")
    out = {"tokens_s": round(tok_s, 1), "step_ms": round(1e3 * dt / steps, 2),
           "config": f"lstm{layers}x{hidden}_T{T}_b{B}",
           "loss_start": round(loss_start, 3), "final_loss": round(final, 3)}
    log(f"[word_lm] {out['config']}: {tok_s:.0f} tokens/s "
        f"({out['step_ms']} ms/step); loss {loss_start:.3f} -> {final:.3f}")
    return out


def bench_transformer_lm(steps: int = 24, B: int = 32, T: int = 1024,
                         micro_batches: int = 4, vocab: int = 16384,
                         preset: str = "flagship"):
    """Flagship MXU workload: decoder-transformer LM training through
    DataParallelTrainer with gradient accumulation, over the Pallas flash
    attention kernel. Presets: 'flagship' (d1024 L8 H16, ~120M params) and
    'wide' (d2048 L4, whose 2048×8192 FFN matmuls saturate the MXU).

    Unlike ResNet-50 (HBM-traffic-bound at 57-72 flop/B — benchmark/
    MFU_ANALYSIS.md), a transformer step is dominated by large matmuls, so
    this leg is the framework's MFU ceiling demonstration. Reports tokens/s,
    XLA-cost-model MFU, and a LEARNING GATE: the same batch is memorized, and
    the bench FAILS if the loss does not fall — throughput from a non-learning
    step must never enter BENCH JSON (round-4 verdict weak #5)."""
    from mxtpu import nd, optimizer as opt_mod
    from mxtpu.gluon.loss import SoftmaxCrossEntropyLoss
    from mxtpu.gluon.model_zoo import transformer_lm
    from mxtpu.gluon.model_zoo.transformer import _PRESETS
    from mxtpu.parallel import DataParallelTrainer, shard_batch
    from mxtpu.parallel.mesh import data_parallel_mesh

    import mxtpu as mx
    mx.rng.seed(0)
    net = transformer_lm(preset, vocab_size=vocab)
    net.initialize()
    net.cast("bfloat16")

    class SeqLoss:
        def __call__(self, logits, y):
            b, t, v = logits.shape
            return SoftmaxCrossEntropyLoss()(
                logits.reshape((b * t, v)), y.reshape((b * t,)))

    mesh = data_parallel_mesh()
    dpt = DataParallelTrainer(net, SeqLoss(),
                              opt_mod.Adam(learning_rate=3e-4), mesh,
                              micro_batches=micro_batches)
    rs = np.random.RandomState(0)
    x = shard_batch(nd.array(rs.randint(0, vocab, (B, T)).astype(np.int32)),
                    mesh)
    y = shard_batch(nd.array(rs.randint(0, vocab, (B, T)).astype(np.float32)),
                    mesh)

    t0 = time.perf_counter()
    loss = dpt.step_async(x, y)
    loss_start = float(loss.data)               # compile + first step
    compile_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    for _ in range(steps):
        loss = dpt.step_async(x, y)
    loss_end = float(loss.data)                 # one readback syncs the chain
    dt = time.perf_counter() - t0
    tok_s = steps * B * T / dt
    step_ms = 1e3 * dt / steps

    ca = dpt.cost_analysis()
    xla_flops = float(ca.get("flops", 0.0))
    if micro_batches > 1:
        xla_flops *= micro_batches              # scan body counted once
    # analytic cross-check: 6·P·tokens for the dense path (P excl. embeddings)
    p_dense = sum(int(np.prod(p.shape))
                  for n, p in net.collect_params().items()
                  if "embed" not in n) + vocab * net._units  # tied head matmul
    analytic_flops = 6 * p_dense * B * T

    kind, peak_tf = _device_peak()
    mfu = (xla_flops / (step_ms / 1e3)) / (peak_tf * 1e12) if peak_tf else None

    if not loss_end < loss_start - 0.3:
        raise RuntimeError(
            f"transformer_lm learning gate FAILED: loss {loss_start:.3f} -> "
            f"{loss_end:.3f} (memorizing one batch must drive it down)")

    units, layers, heads, _ = _PRESETS[preset]
    cfg = f"d{units}_L{layers}_H{heads}_b{B}_T{T}_x{micro_batches}"
    log(f"[transformer_lm] {cfg}: "
        f"compile {compile_s:.0f}s, {step_ms:.1f} ms/step -> {tok_s:.0f} tok/s")
    log(f"[transformer_lm] flops/step: XLA={xla_flops/1e9:.0f}G "
        f"analytic~{analytic_flops/1e9:.0f}G -> MFU="
        f"{100*mfu:.1f}% ({kind})" if mfu is not None else "[transformer_lm] "
        f"flops/step: XLA={xla_flops/1e9:.0f}G (unknown chip peak)")
    log(f"[transformer_lm] learning gate: loss {loss_start:.3f} -> "
        f"{loss_end:.3f} (uniform floor {np.log(vocab):.2f})")

    # KV-cache decode throughput: the whole continuation runs as ONE compiled
    # scan, so the per-TOKEN dispatch cost of naive decoding disappears; the
    # timed region is the full user-facing generate() call (scan dispatch +
    # a few fixed aux ops + the readback — a handful of dispatches total,
    # vs. one PER TOKEN for an eager decode loop)
    dec_B, dec_prompt, dec_new = 8, 32, 224
    rs2 = np.random.RandomState(1)
    dprompt = nd.array(rs2.randint(0, vocab, (dec_B, dec_prompt))
                       .astype(np.int32))
    net.generate(dprompt, dec_new).asnumpy()            # compile + warm
    t0 = time.perf_counter()
    dec = net.generate(dprompt, dec_new).asnumpy()
    dec_dt = time.perf_counter() - t0
    decode_tok_s = dec_B * dec_new / dec_dt
    assert dec.shape == (dec_B, dec_prompt + dec_new)
    log(f"[transformer_lm] KV-cache decode: {decode_tok_s:.0f} tok/s "
        f"(B{dec_B}, +{dec_new} tokens, one scan dispatch)")

    return {"tokens_s": round(tok_s, 1), "step_ms": round(step_ms, 2),
            "mfu": round(mfu, 4) if mfu is not None else None,
            "xla_gflops_per_step": round(xla_flops / 1e9, 1),
            "config": cfg,
            "decode_tok_s": round(decode_tok_s, 1),
            "loss_start": round(loss_start, 3), "loss_end": round(loss_end, 3)}


def bench_long_context(smoke: bool = False):
    """Long-context MFU probe: transformer-LM training steps at T=2048 and
    T=4096 through DataParallelTrainer over the flash-attention kernel.

    This is the hold-the-ceiling leg for PR16's tentpole (c): attention
    flops grow as T² while the matmul flops grow as T, so MFU at long T is
    where a weak flash backward shows first. No learning gate here — the
    flagship transformer_lm leg owns correctness; this leg measures only
    whether throughput holds as context stretches. ``mfu_t2048`` rides the
    BENCH_BASELINE ratchet (see apply_ratchet); docs/long_context_roofline.md
    carries the byte/flop floor analysis behind the numbers.

    Smoke mode (MXTPU_BENCH_SMOKE) shrinks to the tiny preset with the same
    T points so the geometry (max_len override, T4096 block legality) is
    exercised on CPU in seconds."""
    from mxtpu import nd, optimizer as opt_mod
    from mxtpu.gluon.loss import SoftmaxCrossEntropyLoss
    from mxtpu.gluon.model_zoo import transformer_lm
    from mxtpu.parallel import DataParallelTrainer, shard_batch
    from mxtpu.parallel.mesh import data_parallel_mesh

    import mxtpu as mx

    class SeqLoss:
        def __call__(self, logits, y):
            b, t, v = logits.shape
            return SoftmaxCrossEntropyLoss()(
                logits.reshape((b * t, v)), y.reshape((b * t,)))

    if smoke:
        preset, vocab, micro = "tiny", 256, 1
        points = ((2048, 1, 1), (4096, 1, 1))       # (T, B, steps)
    else:
        preset, vocab, micro = "flagship", 16384, 4
        points = ((2048, 8, 8), (4096, 4, 6))       # halve B as T doubles

    kind, peak_tf = _device_peak()
    doc = {"preset": preset, "device": kind}
    for T, B, steps in points:
        mx.rng.seed(0)
        # the flagship preset tops out at max_len=2048 — override so the
        # learned positional table covers the probe length
        net = transformer_lm(preset, vocab_size=vocab, max_len=T)
        net.initialize()
        if not smoke:
            net.cast("bfloat16")                    # CPU smoke stays f32
        mesh = data_parallel_mesh()
        dpt = DataParallelTrainer(net, SeqLoss(),
                                  opt_mod.Adam(learning_rate=3e-4), mesh,
                                  micro_batches=micro)
        rs = np.random.RandomState(T)
        x = shard_batch(
            nd.array(rs.randint(0, vocab, (B, T)).astype(np.int32)), mesh)
        y = shard_batch(
            nd.array(rs.randint(0, vocab, (B, T)).astype(np.float32)), mesh)

        t0 = time.perf_counter()
        float(dpt.step_async(x, y).data)            # compile + first step
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = dpt.step_async(x, y)
        float(loss.data)                            # sync the chain
        dt = time.perf_counter() - t0
        step_ms = 1e3 * dt / steps
        tok_s = steps * B * T / dt

        xla_flops = float(dpt.cost_analysis().get("flops", 0.0))
        if micro > 1:
            xla_flops *= micro                      # scan body counted once
        mfu = (xla_flops / (step_ms / 1e3)) / (peak_tf * 1e12) \
            if peak_tf else None
        doc[f"t{T}"] = {
            "step_ms": round(step_ms, 2), "tokens_s": round(tok_s, 1),
            "mfu": round(mfu, 4) if mfu is not None else None,
            "xla_gflops_per_step": round(xla_flops / 1e9, 1),
            "config": f"{preset}_b{B}_T{T}_x{micro}"}
        doc[f"mfu_t{T}"] = doc[f"t{T}"]["mfu"]
        log(f"[long_context] T{T}: {step_ms:.1f} ms/step -> {tok_s:.0f} tok/s"
            + (f", MFU {100*mfu:.1f}% ({kind})" if mfu is not None else "")
            + f" (compile {compile_s:.0f}s)")
    return doc


def bench_attention():
    """Flash-attention microbench: Pallas kernel vs XLA reference, fwd+bwd,
    at a production shape (B=4, H=16, T=2048, D=64 — the head dim that used to
    fall back), plus a T=4096 long-context point and a backward-retune sweep
    over (block size × launch shape: split vs MXTPU_FLASH_BWD=fused) so the
    fastest backward config at long T is measured, not assumed (PR16
    tentpole c)."""
    import jax
    import jax.numpy as jnp
    from mxtpu.ops.attention import attention_reference, flash_attention

    H, D = 16, 64
    rs = np.random.RandomState(0)
    results = {}
    for tag, B, T, n in (("t2048", 4, 2048, 20), ("t4096", 2, 4096, 10)):
        q, k, v = [jnp.asarray(rs.randn(B, H, T, D).astype(np.float32))
                   for _ in range(3)]
        flops = 4 * B * H * T * T * D * 3  # fwd qk+pv matmuls + bwd ~2x fwd
        point = {}
        for name, fn in (("pallas", flash_attention),
                         ("xla_ref", attention_reference)):
            step = jax.jit(jax.value_and_grad(
                lambda q_, k_, v_, f=fn: jnp.sum(f(q_, k_, v_, causal=True) ** 2),
                argnums=(0, 1, 2)))  # full backward: dq AND dk/dv kernels live
            val, _ = step(q, k, v)
            float(val)  # sync
            t0 = time.perf_counter()
            for _ in range(n):
                val, _ = step(q, k, v)
            float(val)
            dt = (time.perf_counter() - t0) / n
            point[name] = round(dt * 1e3, 3)
            log(f"[attn] {tag} {name}: {dt*1e3:.2f} ms/iter "
                f"({flops/dt/1e12:.1f} TFLOP/s incl. causal-skipped half)")
        point["speedup"] = round(point["xla_ref"] / point["pallas"], 3)
        results[tag] = point
    # headline keys stay the T2048 point (ratchet/guard continuity)
    results.update(results["t2048"])

    # backward retune sweep (direct kernel launches; TPU only — the sweep
    # times Mosaic code, and the CPU fallback would just time the reference)
    if jax.default_backend() == "tpu":
        from mxtpu.ops.attention import (_flash_attention_pallas,
                                         _flash_backward_pallas)
        B, T = 2, 4096
        scale = 1.0 / np.sqrt(D)
        q, k, v, g = [jnp.asarray(rs.randn(B, H, T, D).astype(np.float32))
                      for _ in range(4)]
        out, lse = _flash_attention_pallas(q, k, v, True, scale)
        sweep = {}
        for mode in ("split", "fused"):
            for blk in (128, 256, 512):
                os.environ["MXTPU_FLASH_BWD"] = mode
                try:
                    bwd = jax.jit(lambda *a, _b=blk: _flash_backward_pallas(
                        *a, True, scale, block_q=_b, block_k=_b))
                    jax.block_until_ready(bwd(q, k, v, out, lse, g))
                    t0 = time.perf_counter()
                    for _ in range(10):
                        r = bwd(q, k, v, out, lse, g)
                    jax.block_until_ready(r)
                    sweep[f"{mode}_b{blk}"] = round(
                        (time.perf_counter() - t0) / 10 * 1e3, 3)
                except Exception as e:   # e.g. block OOMs VMEM — record, move on
                    sweep[f"{mode}_b{blk}"] = f"error: {type(e).__name__}"
                finally:
                    os.environ.pop("MXTPU_FLASH_BWD", None)
        timed = {c: ms for c, ms in sweep.items() if isinstance(ms, float)}
        if timed:
            best = min(timed, key=timed.get)
            sweep["best"] = best
            log(f"[attn] bwd sweep @T{T}: best {best} = {timed[best]} ms "
                f"(set MXTPU_FLASH_BWD=fused to use the fused launch)")
        results["bwd_sweep_t4096"] = sweep
    return results


def bench_pipeline():
    """Host data-pipeline benchmark: .rec -> augmented NCHW batches/s, native
    libjpeg decode vs PIL (proves the host can produce batches faster than the
    chip consumes them; the reference's equivalent loop is
    iter_image_recordio_2.cc's OMP decode). Batches are materialized on the
    HOST cpu backend, so the host->device transfer is not in this number."""
    import io as pyio
    import tempfile

    import jax

    from mxtpu import image as mximage, native as mxnative, recordio
    from PIL import Image

    n_img, hw = 384, 224
    d = tempfile.mkdtemp()
    path = f"{d}/pipe.rec"
    rec = recordio.MXRecordIO(path, "w")
    rs = np.random.RandomState(0)
    for i in range(n_img):
        arr = rs.randint(0, 255, (hw, hw, 3)).astype(np.uint8)
        buf = pyio.BytesIO()
        Image.fromarray(arr).save(buf, format="JPEG", quality=90)
        rec.write(recordio.pack(recordio.IRHeader(0, float(i % 10), i, 0),
                                buf.getvalue()))
    rec.close()

    results = {}
    for tag in ("native", "pil"):
        saved = mxnative.jpeg_decode
        if tag == "pil":
            # disable the native decode entry point: the RecordIO scan and
            # the fused normalize stay native in both legs, so the delta is
            # the decode+assembly path (whole-batch C pass vs per-image PIL)
            mxnative.jpeg_decode = lambda buf: None
        try:
            it = mximage.ImageIter(batch_size=128, data_shape=(3, hw, hw),
                                   path_imgrec=path, rand_mirror=True,
                                   mean=(123.68, 116.78, 103.94),
                                   std=(58.4, 57.12, 57.38),
                                   preprocess_threads=os.cpu_count() or 8)
            if tag == "pil":
                it._nb = None   # the whole-batch C path bypasses jpeg_decode;
                                # the pil leg must run the per-image pipeline
            with jax.default_device(jax.local_devices(backend="cpu")[0]):
                next(it)  # warm
                it.reset()
                t0 = time.perf_counter()
                n = 0
                for batch in it:
                    n += batch.data[0].shape[0] - batch.pad
                dt = time.perf_counter() - t0
            results[tag] = round(n / dt, 1)
            log(f"[pipeline] {tag} decode: {n / dt:.0f} img/s host-side")
        finally:
            mxnative.jpeg_decode = saved
    results["speedup"] = round(results["native"] / results["pil"], 2)
    # decode scales with cores; report the denominator so img/s is interpretable
    # (this harness VM may expose a single core)
    results["cpu_count"] = os.cpu_count() or 1
    return results


def bench_train_e2e(synthetic_step_ms: Optional[float] = None,
                    batch: int = 128, dtype: str = "bfloat16",
                    epochs: int = 4):
    """END-TO-END data-path training: RecordIO → native decode/augment →
    async device transfer → train step, with the PrefetchingIter producer
    overlapping host decode against chip compute (the reference's whole io
    design — iter_prefetcher.h + iter_image_recordio_2.cc:50-149 — measured
    as one system instead of two halves).

    Reports e2e img/s, the chip-idle fraction (1 − compute/wall, using the
    synthetic-data step time as the compute floor), and the overlap proof:
    e2e throughput vs the host pipeline's standalone rate. The host side is
    bound by the machine's cores (cpu_count below)."""
    import io as pyio
    import tempfile

    import jax
    import jax.numpy as jnp

    from mxtpu import nd, optimizer as opt_mod, recordio
    from mxtpu import io as mxio
    from mxtpu.gluon.loss import SoftmaxCrossEntropyLoss
    from mxtpu.gluon.model_zoo import vision
    from mxtpu.parallel import DataParallelTrainer
    from mxtpu.parallel.mesh import data_parallel_mesh
    from PIL import Image

    n_img, hw = 384, 224
    d = tempfile.mkdtemp()
    path = f"{d}/e2e.rec"
    rec = recordio.MXRecordIO(path, "w")
    rs = np.random.RandomState(0)
    for i in range(n_img):
        arr = rs.randint(0, 255, (hw, hw, 3)).astype(np.uint8)
        buf = pyio.BytesIO()
        Image.fromarray(arr).save(buf, format="JPEG", quality=90)
        rec.write(recordio.pack(recordio.IRHeader(0, float(i % 10), i, 0),
                                buf.getvalue()))
    rec.close()

    net = vision.resnet50_v1(classes=1000)
    net.initialize()
    if dtype != "float32":
        net.cast(dtype)
    mesh = data_parallel_mesh()
    dpt = DataParallelTrainer(
        net, SoftmaxCrossEntropyLoss(),
        opt_mod.SGD(learning_rate=0.05, momentum=0.9, wd=1e-4), mesh)


    # the decode/augment pipeline must stay on the HOST backend: the
    # prefetcher's producer thread doesn't inherit a thread-local
    # jax.default_device context, so pin the process default to cpu for the
    # whole e2e leg — the train step's arrays are placed explicitly
    # (shard_batch -> NamedSharding on the TPU mesh), so compute still runs
    # on the chip
    cpu_dev = jax.local_devices(backend="cpu")[0]
    jax.config.update("jax_default_device", cpu_dev)

    # normalization runs ON DEVICE over the uint8 batch (one fused jit):
    # the wire carries 1 byte/px instead of 4 — the production feed layout
    # (the reference's iter normalizes on host only because its consumers
    # are host-adjacent GPUs)
    mean = jnp.array([123.68, 116.78, 103.94], jnp.float32).reshape(1, 3, 1, 1)
    std = jnp.array([58.4, 57.12, 57.38], jnp.float32).reshape(1, 3, 1, 1)
    target_dt = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    tpu_dev = jax.devices()[0]

    @jax.jit
    def normalize(u8):
        return ((u8.astype(jnp.float32) - mean) / std).astype(target_dt)

    try:
        def batches():
            # dtype='uint8': the iterator's native whole-batch path emits raw
            # NCHW u8 slabs (decode→crop→mirror→NCHW in one C pass) — no f32
            # detour, and the wire carries 1 byte/px; normalize runs on-chip
            it = mxio.ImageRecordIter(
                path_imgrec=path, data_shape=(3, hw, hw), batch_size=batch,
                rand_mirror=True, dtype="uint8",
                preprocess_threads=os.cpu_count() or 4, prefetch_buffer=2)
            for _ in range(epochs):
                it.reset()
                for b in it:
                    if b.pad:
                        continue                # steady-state batches only
                    x = np.asarray(b.data[0].asnumpy())
                    y = np.asarray(b.label[0].asnumpy(), dtype=np.int32)
                    # committed TPU placement overrides the cpu default, so
                    # the normalize jit runs on the chip
                    x_dev = jax.device_put(jnp.asarray(x), tpu_dev)
                    yield nd.NDArray(normalize(x_dev)), nd.array(y)

        # warm: compile with a first batch (cache-shared with bench_train)
        gen = batches()
        x0, y0 = next(gen)
        loss = dpt.step_async(x0, y0)
        float(loss.data)

        steps = 0
        t0 = time.perf_counter()
        for x, y in gen:
            loss = dpt.step_async(x, y)         # async: decode overlaps chip
            steps += 1
        float(loss.data)
        wall = time.perf_counter() - t0

        # feed-only: the host iterator's capacity to produce ship-ready u8
        # slabs (round-4's "5x iterator-stack gap" metric — pure host work,
        # no device ops; compare against pipeline_img_s on the same host)
        feed_steps = 0
        t0 = time.perf_counter()
        it2 = mxio.ImageRecordIter(
            path_imgrec=path, data_shape=(3, hw, hw), batch_size=batch,
            rand_mirror=True, dtype="uint8",
            preprocess_threads=os.cpu_count() or 4, prefetch_buffer=2)
        for _ in range(epochs):
            it2.reset()
            for b in it2:
                if b.pad:
                    continue
                np.asarray(b.data[0].asnumpy())
                feed_steps += 1
        feed_wall = time.perf_counter() - t0

        # feed+transfer: the same slabs THROUGH the device boundary
        # (device_put + on-chip normalize); reported separately so the host
        # iterator and the transport are not conflated.
        ft_steps = 0
        t0 = time.perf_counter()
        x = None
        for x, y in batches():
            ft_steps += 1
        if x is not None:
            # device transfers/normalizes queue FIFO — one readback of the
            # LAST image batch waits for all of them (y alone would omit the
            # in-flight image-side work)
            float(jnp.sum(x.data.astype(jnp.float32)))
        ft_wall = time.perf_counter() - t0
    finally:
        jax.config.update("jax_default_device", None)
    img_s = steps * batch / wall

    # KEY RENAME (round 5): what BENCH_r04 called feed_only_img_s (host feed
    # INCLUDING device transfer) is now feed_transfer_img_s; host_feed_img_s
    # is the pure iterator rate — renamed so round-over-round comparisons
    # don't conflate the two denominators
    out = {"img_s": round(img_s, 1), "steps": steps,
           "wall_s": round(wall, 2), "cpu_count": os.cpu_count() or 1,
           "host_feed_img_s": round(feed_steps * batch / feed_wall, 1),
           "feed_transfer_img_s": round(ft_steps * batch / ft_wall, 1)}
    out["overlap_efficiency"] = round(
        out["img_s"] / max(out["feed_transfer_img_s"], 1e-9), 3)
    if synthetic_step_ms:
        compute_s = steps * synthetic_step_ms / 1e3
        out["chip_idle_frac"] = round(max(0.0, 1 - compute_s / wall), 3)
        out["synthetic_img_s"] = round(batch * 1e3 / synthetic_step_ms, 1)
    log(f"[train_e2e] {steps} steps b{batch} {dtype}: {img_s:.0f} img/s "
        f"end-to-end; host feed {out['host_feed_img_s']:.0f} img/s, "
        f"feed+transfer {out['feed_transfer_img_s']:.0f} img/s "
        f"(overlap {out['overlap_efficiency']:.2f}, chip idle "
        f"{out.get('chip_idle_frac', '?')}, host cores={out['cpu_count']})")
    return out


def bench_int8():
    """INT8 MXU microbench (the quantization speed story): chained n x n
    matmuls, int8 codes w/ int32 accumulate + rescale vs bf16 — plus a
    quantize_net'd MLP inference vs its fp32 source."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    n, iters = 8192, 60  # long chain: the one readback amortizes
    rs = np.random.RandomState(0)
    a8 = jnp.asarray(rs.randint(-127, 127, (n, n)).astype(np.int8))
    b8 = jnp.asarray(rs.randint(-127, 127, (n, n)).astype(np.int8))
    abf, bbf = a8.astype(jnp.bfloat16), b8.astype(jnp.bfloat16)

    def sync(x):
        return float(jnp.sum(x.astype(jnp.float32)))

    f_i8 = jax.jit(lambda a, b: lax.fori_loop(0, iters, lambda i, acc: (
        lax.dot_general(acc, b, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.int32) // 1024
    ).astype(jnp.int8), a))
    f_bf = jax.jit(lambda a, b: lax.fori_loop(0, iters, lambda i, acc: (
        lax.dot_general(acc, b, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32) * 1e-3
    ).astype(jnp.bfloat16), a))
    results = {}
    for name, f, x, y in (("int8", f_i8, a8, b8), ("bf16", f_bf, abf, bbf)):
        sync(f(x, y))
        t0 = time.perf_counter()
        sync(f(x, y))
        dt = time.perf_counter() - t0
        results[f"matmul_{name}_tops"] = round(iters * 2 * n ** 3 / dt / 1e12, 1)
        log(f"[int8] matmul {name}: {results[f'matmul_{name}_tops']} TOP/s")
    results["matmul_speedup"] = round(
        results["matmul_int8_tops"] / results["matmul_bf16_tops"], 2)
    return results


def _checkpoint_probe_module():
    """A ~16 MB (params + SGD-momentum slots) MLP Module: big enough that a
    blocking save is serialize/fsync-dominated, small enough that the probe
    runs in seconds on the cpu fallback."""
    import mxtpu as mx
    from mxtpu import nd
    from mxtpu.gluon import nn
    from mxtpu.gluon.block import HybridBlock
    from mxtpu.io import DataBatch, DataDesc

    class Probe(HybridBlock):
        def __init__(self):
            super().__init__()
            self.fc1 = nn.Dense(2048, in_units=1024)
            self.fc2 = nn.Dense(10, in_units=2048)

        def forward(self, x):
            return self.fc2(self.fc1(x).relu())

    batch = 16
    rs = np.random.RandomState(0)
    x = nd.array(rs.rand(batch, 1024).astype(np.float32))
    y = nd.array(rs.randint(0, 10, batch).astype(np.float32))
    mod = mx.Module(Probe(), data_names=("data",),
                    label_names=("softmax_label",))
    mod.bind(data_shapes=[DataDesc("data", (batch, 1024))],
             label_shapes=[DataDesc("softmax_label", (batch,))])
    mod.init_params()
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05,
                                         "momentum": 0.9})
    b = DataBatch(data=[x], label=[y])
    mod.forward_backward(b)   # materialize params + momentum slots
    mod.update()
    return mod


def bench_checkpoint(module=None, iters: int = 5):
    """Checkpoint-subsystem scenario: async handoff vs blocking save wall
    time, plus committed bytes, through ``mxtpu.checkpoint.CheckpointManager``
    with the profiler counters as the source of truth. The subsystem's
    contract (docs/checkpointing.md): the training thread blocks for <10% of
    a blocking save's wall time on an async save."""
    import shutil
    import tempfile

    from mxtpu import profiler
    from mxtpu.checkpoint import CheckpointManager

    if module is None:
        module = _checkpoint_probe_module()

    d = tempfile.mkdtemp(prefix="mxtpu-bench-ckpt-")
    profiler.reset_checkpoint_stats()
    try:
        mgr = CheckpointManager(d, max_to_keep=2)
        mgr.save(0, module=module, blocking=True)   # warm: writer thread,
                                                    # first npz serialize
        blocking_ms = []
        for i in range(iters):
            t0 = time.perf_counter()
            mgr.save(2 * i + 1, module=module, blocking=True)
            blocking_ms.append((time.perf_counter() - t0) * 1e3)

        handoff_ms = []
        for i in range(iters):
            t0 = time.perf_counter()
            mgr.save(2 * i + 2, module=module, blocking=False)
            handoff_ms.append((time.perf_counter() - t0) * 1e3)
            # drain between samples: measure the handoff, not queue backlog
            mgr.wait_until_finished()
        mgr.close()
        stats = profiler.get_checkpoint_stats()
    finally:
        shutil.rmtree(d, ignore_errors=True)

    blocking = float(np.median(blocking_ms))
    handoff = float(np.median(handoff_ms))
    out = {
        "blocking_save_ms": round(blocking, 3),
        "async_handoff_ms": round(handoff, 3),
        "async_blocked_frac": round(handoff / max(blocking, 1e-9), 4),
        "committed_bytes_per_step": int(stats["committed_bytes"]
                                        / max(stats["commits"], 1)),
        "commits": stats["commits"],
        "write_ms_last": round(stats["write_ms_last"], 3),
    }
    log(f"[checkpoint] blocking={blocking:.1f} ms async-handoff={handoff:.2f} "
        f"ms (blocked frac {out['async_blocked_frac']:.3f}); "
        f"{out['committed_bytes_per_step']/1e6:.1f} MB/step committed")
    return out


def bench_comm():
    """Allreduce bandwidth block (BASELINE.json's KVStore-allreduce GB/s
    north star). Single-chip hardware here, so this reports the local/device
    tier (kvstore push-reduce loopback); under a multi-process launch the same
    harness (tools/bandwidth.py) measures the dist allreduce tier — the
    MULTICHIP dryrun separately validates the virtual-mesh collective with
    bytes-moved accounting."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    import bandwidth as bw
    rows, multi = bw.measure([4.0, 64.0], iters=6, kv_type="device")
    import jax
    out = {"tier": "dist_allreduce" if multi else "local_device",
           "world": jax.process_count(),
           "sizes": {f"{int(mb)}MB": {"ms_per_iter": round(ms, 2),
                                      "algbw_gb_s": round(alg, 2),
                                      "busbw_gb_s": round(bus, 2)}
                     for mb, ms, alg, bus in rows}}
    for mb, ms, alg, bus in rows:
        log(f"[comm] {mb:.0f}MB: {ms:.2f} ms/iter, algbw {alg:.2f} GB/s "
            f"({out['tier']})")
    out["all_to_all_probe"] = _all_to_all_probe()
    probe = out["all_to_all_probe"]
    ar64 = next((ms for mb, ms, _, _ in rows if int(mb) == 64), None)
    a2a64 = (probe.get("sizes", {}).get("64MB") or {})
    a2a_ms = a2a64.get("shard_map_ms") \
        if probe.get("default_impl") == "shard_map" \
        else a2a64.get("jit_reshard_ms")
    if ar64 and a2a_ms:
        # ratcheted up-is-good orientation: how many a2a exchanges fit in one
        # same-size allreduce (the round-5 review measured 0.12 — the 8.6x
        # anomaly, on the 8-virtual-device CPU mesh —
        # against the ≥1 expected from a2a moving half the bytes)
        out["a2a_vs_allreduce_ratio"] = round(ar64 / a2a_ms, 3)
        log(f"[comm] a2a_vs_allreduce_ratio (64MB, allreduce_ms/a2a_ms): "
            f"{out['a2a_vs_allreduce_ratio']}")
    return out


def _all_to_all_probe(sizes_mb=(1.0, 16.0, 64.0), iters: int = 6):
    """Before/after sweep for the all_to_all lowering anomaly (ISSUE 12): the
    SAME logical shard-ownership transpose timed through
    ``collectives.all_to_all_array`` under BOTH impls — the legacy
    ``shard_map``+``lax.all_to_all`` lowering and the ``jit_reshard`` default
    (GSPMD-native a2a from a spec flip) — at {1, 16, 64} MB, plus a bare
    ``jax.jit`` reshard as the floor. ``gap`` is the default path over that
    floor: the acceptance bar is gap ≤ 1.5 (the old lowering measured ~12.6×)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxtpu.parallel import collectives
    from mxtpu.parallel.mesh import data_parallel_mesh

    mesh = data_parallel_mesh()
    n = mesh.devices.size
    if n == 1:
        return {"skipped": "single device"}
    ax = mesh.axis_names[0]
    resharded = NamedSharding(mesh, P(None, ax))
    raw_reshard = jax.jit(lambda v: v, out_shardings=resharded)

    def timed(fn, x):
        fn(x).block_until_ready()                   # compile
        t0 = time.perf_counter()
        for _ in range(iters):
            r = fn(x)
        r.block_until_ready()
        return 1e3 * (time.perf_counter() - t0) / iters

    default_impl = collectives.a2a_impl()
    sizes = {}
    for mb in sizes_mb:
        rows = max(n, int(mb * 1e6 / 4 / (n * 128)) // n * n)
        x = jax.device_put(
            jnp.arange(rows * n * 128,
                       dtype=jnp.float32).reshape(rows, n * 128),
            NamedSharding(mesh, P(ax, None)))
        nbytes = x.size * 4
        shard_map_ms = timed(lambda v: collectives.all_to_all_array(
            v, mesh, split_axis=1, concat_axis=0, impl="shard_map"), x)
        jit_ms = timed(lambda v: collectives.all_to_all_array(
            v, mesh, split_axis=1, concat_axis=0, impl="jit_reshard"), x)
        floor_ms = timed(raw_reshard, x)
        default_ms = shard_map_ms if default_impl == "shard_map" else jit_ms
        entry = {"bytes": int(nbytes),
                 "shard_map_ms": round(shard_map_ms, 3),
                 "jit_reshard_ms": round(jit_ms, 3),
                 "raw_reshard_ms": round(floor_ms, 3),
                 "ratio": round(shard_map_ms / max(jit_ms, 1e-9), 2),
                 "gap": round(default_ms / max(floor_ms, 1e-9), 2)}
        sizes[f"{int(mb)}MB"] = entry
        log(f"[comm] all_to_all {mb:.0f}MB: shard_map "
            f"{shard_map_ms:.2f} ms vs jit-reshard {jit_ms:.2f} ms "
            f"(before/after {entry['ratio']}x; default gap {entry['gap']}x)")
    head = sizes[f"{int(sizes_mb[-1])}MB"]
    return {"default_impl": default_impl, "sizes": sizes,
            # headline keys (largest size) — bench-guard back-compat
            "bytes": head["bytes"], "shard_map_ms": head["shard_map_ms"],
            "jit_reshard_ms": head["jit_reshard_ms"],
            "ratio": head["ratio"], "gap": head["gap"]}


def _lenet_module(batch: int, setup: bool = True):
    """LeNet-scale Module on the fused StepExecutor path — shared by the
    cpu-fallback harness and the input_pipeline/resilience scenarios.
    ``setup=False`` returns the module unbound so ``fit`` owns bind/init
    (what the supervised-restart leg needs for a fresh per-attempt build)."""
    import mxtpu as mx
    from mxtpu.gluon import nn
    from mxtpu.gluon.block import HybridBlock
    from mxtpu.io import DataDesc

    class LeNet(HybridBlock):
        def __init__(self):
            super().__init__()
            self.c1 = nn.Conv2D(8, kernel_size=3, in_channels=1)
            self.p1 = nn.MaxPool2D(pool_size=2)
            self.c2 = nn.Conv2D(16, kernel_size=3, in_channels=8)
            self.p2 = nn.MaxPool2D(pool_size=2)
            self.flat = nn.Flatten()
            self.fc1 = nn.Dense(64, in_units=16 * 5 * 5)
            self.fc2 = nn.Dense(10, in_units=64)

        def forward(self, x):
            x = self.p1(self.c1(x).relu())
            x = self.p2(self.c2(x).relu())
            return self.fc2(self.fc1(self.flat(x)).relu())

    mod = mx.Module(LeNet(), data_names=("data",),
                    label_names=("softmax_label",))
    if setup:
        mod.bind(data_shapes=[DataDesc("data", (batch, 1, 28, 28))],
                 label_shapes=[DataDesc("softmax_label", (batch,))])
        mod.init_params()
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params={"learning_rate": 0.05,
                                             "momentum": 0.9})
    return mod


class _SyntheticDecodeIter:
    """Input-bound synthetic loader: each batch costs ``decode_ms`` of host
    work (the decode/augment stand-in) before it is placed — the workload
    whose stall the device feed exists to hide."""

    def __init__(self, n_batches: int, batch: int, decode_ms: float):
        from mxtpu.io import DataDesc
        self.batch_size = batch
        self.n_batches = n_batches
        self.decode_ms = decode_ms
        self._rs = np.random.RandomState(0)
        self._pool = [self._rs.rand(batch, 1, 28, 28).astype(np.float32)
                      for _ in range(4)]
        self._labels = self._rs.randint(0, 10, batch).astype(np.float32)
        self._i = 0
        self.provide_data = [DataDesc("data", (batch, 1, 28, 28))]
        self.provide_label = [DataDesc("softmax_label", (batch,))]

    def reset(self):
        self._i = 0

    def __iter__(self):
        return self

    def __next__(self):
        return self.next()

    def next(self):
        from mxtpu import nd
        from mxtpu.io import DataBatch
        if self._i >= self.n_batches:
            raise StopIteration
        time.sleep(self.decode_ms / 1e3)          # the emulated decode
        src = self._pool[self._i % len(self._pool)]
        self._i += 1
        return DataBatch(data=[nd.array(src)],
                         label=[nd.array(self._labels)])


def bench_input_pipeline(steps: int = 48, batch: int = 32,
                         decode_ms: float = 6.0):
    """Device-feed scenario: an input-bound synthetic loader driving the
    fused LeNet step, sync per-batch placement vs the async DeviceFeed path.
    Reports steps/sec and the input-stall fraction for both; the feed path
    must show the LOWER stall fraction (producer decode overlaps the step).
    Runs to completion on the cpu fallback — it is part of that harness."""
    from mxtpu import profiler
    from mxtpu.device_feed import DeviceFeed

    mod = _lenet_module(batch)
    loader = _SyntheticDecodeIter(steps, batch, decode_ms)
    # warm the step compile outside both timed legs — BOTH input flavors:
    # jax.jit specializes on committed-ness, so the fed (committed) batch
    # compiles a second executable the sync (uncommitted) one doesn't cover
    warm = _SyntheticDecodeIter(1, batch, 0.0)
    b0 = warm.next()
    mod.forward_backward(b0)
    mod.update()
    warm_feed = DeviceFeed(_SyntheticDecodeIter(1, batch, 0.0), depth=1)
    for b in warm_feed:
        mod.forward_backward(b)
        mod.update()

    # leg 1 — sync path (what MXTPU_DEVICE_FEED=0 training does): the step
    # loop eats the full decode+transfer latency of every batch
    loader.reset()
    input_wait = 0.0
    t0 = time.perf_counter()
    it = iter(loader)
    while True:
        t1 = time.perf_counter()
        try:
            b = next(it)
        except StopIteration:
            break
        input_wait += time.perf_counter() - t1
        mod.forward_backward(b)
        mod.update()
    sync_wall = time.perf_counter() - t0
    sync = {"steps_per_s": round(steps / sync_wall, 2),
            "stall_frac": round(input_wait / sync_wall, 3)}

    # leg 2 — device feed: producer decodes/places ahead, the loop's only
    # input cost is the (ideally empty) queue wait
    loader.reset()
    profiler.reset_feed_stats()
    feed = DeviceFeed(loader, depth=2)
    t0 = time.perf_counter()
    for b in feed:
        mod.forward_backward(b)
        mod.update()
    feed_wall = time.perf_counter() - t0
    fstats = profiler.get_feed_stats()
    dfeed = {"steps_per_s": round(steps / feed_wall, 2),
             "stall_frac": round(
                 fstats["stall_ms_total"] / 1e3 / max(feed_wall, 1e-9), 3),
             "transfer_mb": round(fstats["transfer_bytes"] / 1e6, 2),
             "transfer_ms": round(fstats["transfer_ms_total"], 1),
             "queue_depth_max": fstats["queue_depth_max"],
             "batches_prefetched": fstats["batches_prefetched"]}

    out = {"sync": sync, "device_feed": dfeed,
           "decode_ms": decode_ms, "batch": batch, "steps": steps,
           "speedup": round(dfeed["steps_per_s"] / max(sync["steps_per_s"],
                                                       1e-9), 3)}
    log(f"[input_pipeline] sync: {sync['steps_per_s']} steps/s "
        f"(stall {sync['stall_frac']:.0%}) | device-feed: "
        f"{dfeed['steps_per_s']} steps/s (stall {dfeed['stall_frac']:.0%}, "
        f"queue hw {dfeed['queue_depth_max']}) -> {out['speedup']}x")
    return out


def bench_zero_dp(steps: int = 16, batch: int = 64, hidden: int = 512):
    """ZeRO-1 vs replicated-psum data parallelism through the SAME
    DataParallelTrainer: step time, per-step gradient comm bytes
    (``profiler.get_comm_stats()`` — ring reduce-scatter + all-gather on the
    ZeRO leg vs the full all-reduce equivalent on the baseline), and the
    headline: per-device optimizer-state bytes, which ZeRO cuts ~N× on the dp
    axis (MULTICHIP_r05 motivates the collective swap: reduce_scatter 64 MB =
    464 ms vs allreduce 1117 ms)."""
    from mxtpu import nd, optimizer as opt_mod, profiler
    from mxtpu.gluon import nn
    from mxtpu.gluon.loss import SoftmaxCrossEntropyLoss
    from mxtpu.parallel import DataParallelTrainer
    from mxtpu.parallel.mesh import data_parallel_mesh

    import mxtpu as mx

    mesh = data_parallel_mesh()
    n_dev = mesh.devices.size
    rs = np.random.RandomState(0)
    X = rs.randn(batch, hidden // 2).astype(np.float32)
    y = rs.randint(0, 16, batch).astype(np.float32)

    def leg(zero: bool) -> dict:
        mx.rng.seed(0)
        net = nn.HybridSequential()
        net.add(nn.Dense(hidden, activation="relu", in_units=hidden // 2),
                nn.Dense(hidden, activation="relu", in_units=hidden),
                nn.Dense(16, in_units=hidden))
        net.initialize(init=mx.initializer.Xavier())
        dpt = DataParallelTrainer(
            net, SoftmaxCrossEntropyLoss(),
            opt_mod.SGD(learning_rate=0.05, momentum=0.9), mesh, zero=zero)
        loss = dpt.step_async(nd.array(X), nd.array(y))
        l0 = float(loss.data)                       # compile + first step
        profiler.reset_comm_stats()
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = dpt.step_async(nd.array(X), nd.array(y))
        l1 = float(loss.data)                       # one readback syncs
        dt = time.perf_counter() - t0
        c = profiler.get_comm_stats()
        comm_per_step = (c["bytes_reduced"] + c["bytes_gathered"]
                         + c["allreduce_bytes"]) / max(c["steps"], 1)
        return {
            "step_ms": round(1e3 * dt / steps, 3),
            "comm_bytes_per_step": int(comm_per_step),
            "opt_state_bytes_per_device": dpt.optimizer_state_bytes(),
            "bucket_count": c["bucket_count"],
            "loss_start": round(l0, 4), "loss_end": round(l1, 4),
        }

    repl = leg(zero=False)
    z1 = leg(zero=True)
    out = {"dp": n_dev, "replicated": repl, "zero1": z1,
           "opt_state_shrink": round(
               repl["opt_state_bytes_per_device"]
               / max(z1["opt_state_bytes_per_device"], 1), 2),
           "comm_bytes_frac": round(
               z1["comm_bytes_per_step"]
               / max(repl["comm_bytes_per_step"], 1), 3)
           if repl["comm_bytes_per_step"] else None,
           "step_speedup": round(repl["step_ms"] / max(z1["step_ms"], 1e-9),
                                 3)}
    log(f"[zero_dp] dp={n_dev}: replicated {repl['step_ms']} ms/step "
        f"({repl['opt_state_bytes_per_device']/1e3:.1f} kB opt/dev) | "
        f"ZeRO-1 {z1['step_ms']} ms/step "
        f"({z1['opt_state_bytes_per_device']/1e3:.1f} kB opt/dev, "
        f"{z1['bucket_count']} bucket(s)) -> state shrink "
        f"{out['opt_state_shrink']}x, comm frac {out['comm_bytes_frac']}")
    return out


def bench_fsdp(steps: int = 12, batch: int = 64, hidden: int = 512):
    """ZeRO stage ladder (MXTPU_ZERO_STAGE=1|2|3) through the SAME
    DataParallelTrainer and model: step time, per-step gradient comm bytes,
    and the headline — per-device resident bytes for params/grads/optimizer
    slots from ``profiler.get_memory_stats()``. Stage 3 (FSDP) holds params
    1/N on the fsdp axis with JIT per-layer all-gathers; the scoreboard
    asserts the stage-3 param+slot residency shrink and that the final loss
    stays bit-identical across stages (dim-0-only fsdp sharding keeps the
    reduction order fixed)."""
    from mxtpu import nd, optimizer as opt_mod, profiler
    from mxtpu.gluon import nn
    from mxtpu.gluon.loss import SoftmaxCrossEntropyLoss
    from mxtpu.parallel import DataParallelTrainer
    from mxtpu.parallel.mesh import data_parallel_mesh

    import mxtpu as mx

    mesh = data_parallel_mesh()
    n_dev = mesh.devices.size
    rs = np.random.RandomState(0)
    X = rs.randn(batch, hidden // 2).astype(np.float32)
    y = rs.randint(0, 16, batch).astype(np.float32)

    def leg(stage: int) -> dict:
        prev = os.environ.get("MXTPU_ZERO_STAGE")
        os.environ["MXTPU_ZERO_STAGE"] = str(stage)
        try:
            mx.rng.seed(0)
            net = nn.HybridSequential()
            net.add(nn.Dense(hidden, activation="relu",
                             in_units=hidden // 2),
                    nn.Dense(hidden, activation="relu", in_units=hidden),
                    nn.Dense(16, in_units=hidden))
            net.initialize(init=mx.initializer.Xavier())
            dpt = DataParallelTrainer(
                net, SoftmaxCrossEntropyLoss(),
                opt_mod.SGD(learning_rate=0.05, momentum=0.9), mesh,
                zero=True)
            loss = dpt.step_async(nd.array(X), nd.array(y))
            l0 = float(loss.data)                   # compile + first step
            profiler.reset_comm_stats()
            t0 = time.perf_counter()
            for _ in range(steps):
                loss = dpt.step_async(nd.array(X), nd.array(y))
            l1 = float(loss.data)                   # one readback syncs
            dt = time.perf_counter() - t0
            c = profiler.get_comm_stats()
            m = profiler.get_memory_stats()
            comm_per_step = (c["bytes_reduced"] + c["bytes_gathered"]
                             + c["allreduce_bytes"]) / max(c["steps"], 1)
            return {
                "step_ms": round(1e3 * dt / steps, 3),
                "comm_bytes_per_step": int(comm_per_step),
                "param_bytes_per_device": m["param_bytes_per_device"],
                "grad_bytes_per_device": m["grad_bytes_per_device"],
                "slot_bytes_per_device": m["slot_bytes_per_device"],
                "loss_start": l0, "loss_end": l1,
            }
        finally:
            if prev is None:
                os.environ.pop("MXTPU_ZERO_STAGE", None)
            else:
                os.environ["MXTPU_ZERO_STAGE"] = prev

    legs = {s: leg(s) for s in (1, 2, 3)}
    ps1 = (legs[1]["param_bytes_per_device"]
           + legs[1]["slot_bytes_per_device"])
    ps3 = (legs[3]["param_bytes_per_device"]
           + legs[3]["slot_bytes_per_device"])
    out = {"dp": n_dev,
           "stage1": legs[1], "stage2": legs[2], "stage3": legs[3],
           "param_slot_shrink": round(ps1 / max(ps3, 1), 2),
           "loss_bit_parity": (legs[1]["loss_end"] == legs[2]["loss_end"]
                               == legs[3]["loss_end"])}
    log(f"[fsdp] dp={n_dev}: "
        + " | ".join(f"stage{s} {legs[s]['step_ms']} ms/step, "
                     f"{(legs[s]['param_bytes_per_device'] + legs[s]['slot_bytes_per_device'])/1e3:.1f} kB "
                     f"param+slot/dev" for s in (1, 2, 3))
        + f" -> shrink {out['param_slot_shrink']}x, "
        f"loss bit-parity={out['loss_bit_parity']}")
    return out


def bench_trace(steps: Optional[int] = None, batch: int = 32):
    """Unified-tracing scenario: arms the span recorder over a fused-step
    loop fed by the DeviceFeed producer plus one async checkpoint save, dumps
    the chrome://tracing JSON, and reports what the dump contains (events,
    span categories, named thread rows) — the machine-checkable form of the
    tentpole contract. Also measures the SAME loop with tracing off, so the
    JSON carries the tracing-on overhead and the off-path throughput the
    <2%-regression acceptance compares against."""
    import tempfile

    from mxtpu import profiler
    from mxtpu.checkpoint import CheckpointManager
    from mxtpu.device_feed import DeviceFeed
    from mxtpu.observability import tracer

    smoke = os.environ.get("MXTPU_BENCH_SMOKE") == "1"
    steps = steps if steps is not None else (6 if smoke else 24)
    was_on = tracer.enabled()

    mod = _lenet_module(batch)

    def loop(traced: bool) -> float:
        feed = DeviceFeed(_SyntheticDecodeIter(steps, batch, 0.0), depth=2)
        if traced:
            tracer.start()
        try:
            t0 = time.perf_counter()
            for b in feed:
                mod.forward_backward(b)
                mod.update()
            float(mod._loss_val.mean().data)    # sync
            return time.perf_counter() - t0
        finally:
            if traced and not was_on:
                tracer.stop()

    # compile both input flavors outside the timed windows
    warm = DeviceFeed(_SyntheticDecodeIter(1, batch, 0.0), depth=1)
    for b in warm:
        mod.forward_backward(b)
        mod.update()

    # alternate off/traced legs and take each side's best: a single ordering
    # consistently charges the first timed loop with straggler warmup (feed
    # thread spin-up, allocator steady-state) on loaded hosts
    off_s = loop(traced=False)
    tracer.reset()
    on_s = loop(traced=True)
    off_s = min(off_s, loop(traced=False))
    tracer.reset()
    on_s = min(on_s, loop(traced=True))

    d = tempfile.mkdtemp(prefix="mxtpu-bench-trace-")
    try:
        # one traced async checkpoint save: ckpt/snapshot on the main thread,
        # ckpt/write + ckpt/commit on the writer's own tid row
        tracer.start()
        mgr = CheckpointManager(d)
        mgr.save(0, module=mod, blocking=True)
        mgr.close()
        if not was_on:
            tracer.stop()
        fname = os.path.join(d, "trace.json")
        saved_filename = profiler._state["config"].get("filename")
        profiler.set_config(filename=fname, xplane=False)
        try:
            profiler.dump(finished=False)   # live snapshot: no freeze
        finally:
            profiler.set_config(filename=saved_filename)
        with open(fname) as f:
            doc = json.load(f)
        dump_bytes = os.path.getsize(fname)
    finally:
        import shutil
        shutil.rmtree(d, ignore_errors=True)

    evs = doc["traceEvents"]
    spans = [e for e in evs if e.get("ph") == "X"]
    cats = sorted({e.get("cat", "") for e in evs
                   if e.get("ph") in ("X", "C")})
    threads = sorted({e["args"]["name"] for e in evs
                      if e.get("ph") == "M" and e.get("name") == "thread_name"})
    out = {"steps": steps,
           "events": len(evs),
           "spans": len(spans),
           "span_categories": cats,
           "span_names": sorted({e["name"] for e in spans}),
           "threads": threads,
           "dump_bytes": dump_bytes,
           "steps_per_s_off": round(steps / off_s, 2),
           "steps_per_s_traced": round(steps / on_s, 2),
           "overhead_frac_traced": round(on_s / max(off_s, 1e-9) - 1.0, 4)}
    if not was_on:
        profiler.reset_trace()              # leave no spans for later legs
    log(f"[trace] {out['spans']} spans / {out['events']} events, "
        f"categories={cats}, threads={threads}; traced overhead "
        f"{out['overhead_frac_traced']*100:+.1f}% "
        f"({out['steps_per_s_off']} -> {out['steps_per_s_traced']} steps/s)")
    return out


def bench_observability(smoke: bool = False):
    """Telemetry-plane scenario: the tracer + histogram record path armed
    over a fused-step loop versus the same loop with telemetry off (min of
    three alternating leg pairs), plus one real in-process
    scrape of the metrics exporter. The acceptance contract is telemetry
    overhead under a few percent — ``tests/test_bench_guard.py`` asserts
    ``overhead_frac < 0.03`` on the smoke leg, and the ratchet tracks the
    inverse so "up" stays "better"."""
    import urllib.request

    from mxtpu import profiler
    from mxtpu.device_feed import DeviceFeed
    from mxtpu.observability import exporter, histogram, tracer

    batch = 32
    steps = 8 if smoke else 32
    was_on = tracer.enabled()

    mod = _lenet_module(batch)

    def loop(telemetry: bool) -> float:
        feed = DeviceFeed(_SyntheticDecodeIter(steps, batch, 0.0), depth=2)
        if telemetry:
            tracer.start()
        try:
            t0 = time.perf_counter()
            prev = t0
            for b in feed:
                mod.forward_backward(b)
                mod.update()
                if telemetry:
                    now = time.perf_counter()
                    histogram.record_value("bench/step_ms",
                                           (now - prev) * 1e3)
                    prev = now
            float(mod._loss_val.mean().data)    # sync
            return time.perf_counter() - t0
        finally:
            if telemetry and not was_on:
                tracer.stop()

    warm = DeviceFeed(_SyntheticDecodeIter(1, batch, 0.0), depth=1)
    for b in warm:
        mod.forward_backward(b)
        mod.update()

    # min-of-three alternating pairs: each smoke leg is ~0.1 s, so a single
    # scheduler hiccup in either leg can fake a multi-percent "overhead" —
    # the min over three interleaved runs is what the <3% guard asserts on
    off_s = on_s = float("inf")
    for _ in range(3):
        off_s = min(off_s, loop(telemetry=False))
        tracer.reset()
        on_s = min(on_s, loop(telemetry=True))

    # one real scrape over HTTP (ephemeral port): Prometheus text + JSON
    ex = exporter.MetricsExporter(0).start()
    try:
        t0 = time.perf_counter()
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{ex.port}/metrics", timeout=10).read()
        scrape_ms = (time.perf_counter() - t0) * 1e3
        js = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{ex.port}/json", timeout=10).read())
    finally:
        ex.stop()
    text = body.decode()
    hist_block = js.get("histograms", {}).get("bench/step_ms", {})

    overhead = round(on_s / max(off_s, 1e-9) - 1.0, 4)
    out = {"steps": steps,
           "steps_per_s_off": round(steps / off_s, 2),
           "steps_per_s_telemetry": round(steps / on_s, 2),
           "overhead_frac": overhead,
           # ratchet coordinate: inverse overhead, floored at 1% so any run
           # in the noise band (<=1% or negative) saturates at the same 100
           # instead of ratcheting an unreachable bar from one lucky sample
           "overhead_inv": round(1.0 / max(overhead, 0.01), 2),
           "scrape_ms": round(scrape_ms, 3),
           "scrape_bytes": len(body),
           "prometheus_ok": text.count("\n") > 10
           and "mxtpu_hist_bench_step_ms_count" in text,
           "json_ok": hist_block.get("count", 0) >= steps,
           "step_ms_p50": hist_block.get("p50"),
           "step_ms_p99": hist_block.get("p99")}
    histogram.reset_histograms(prefix="bench/")
    if not was_on:
        profiler.reset_trace()
    log(f"[observability] telemetry overhead {overhead*100:+.1f}% "
        f"({out['steps_per_s_off']} -> {out['steps_per_s_telemetry']} "
        f"steps/s); scrape {out['scrape_ms']} ms / {out['scrape_bytes']} B "
        f"(prometheus_ok={out['prometheus_ok']}, json_ok={out['json_ok']})")
    return out


# ---------------------------------------------------------------------------
# MFU / steps-per-sec regression ratchet (ROADMAP item 5: "speed wins are
# ratcheted, not re-lost")
# ---------------------------------------------------------------------------


def _ratchet_path() -> str:
    return os.environ.get("MXTPU_BENCH_BASELINE_PATH") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_BASELINE.json")


def apply_ratchet(doc: dict, harness: str):
    """Compare this run's headline metrics against ``BENCH_BASELINE.json``
    and write the new baseline CANDIDATE back (per-harness key; each metric
    only ever moves UP — the ratchet). A drop beyond the tolerance
    (``MXTPU_BENCH_RATCHET_TOL``, default 10%) is reported in the
    ``"ratchet"`` JSON block and logged — never fatal: the ratchet is a
    tripwire for the reviewer, not a gate that can erase a scoreboard.
    Smoke runs ratchet under a separate ``<harness>-smoke`` key so shrunken
    iteration counts never poison the real baseline."""
    try:
        if os.environ.get("MXTPU_BENCH_SMOKE") == "1":
            harness += "-smoke"
        mfu_field = doc.get("mfu")
        block = mfu_field if isinstance(mfu_field, dict) \
            else doc.get("mfu_stats") or {}
        mfu_val = mfu_field if isinstance(mfu_field, (int, float)) \
            else block.get("mfu")
        fsdp_block = doc.get("fsdp")
        fsdp_shrink = fsdp_block.get("param_slot_shrink") \
            if isinstance(fsdp_block, dict) else None
        serving_block = doc.get("serving")
        serving_goodput = serving_block.get("goodput_tok_s") \
            if isinstance(serving_block, dict) else None
        prefix_block = serving_block.get("prefix") \
            if isinstance(serving_block, dict) else None
        if not isinstance(prefix_block, dict):
            prefix_block = {}
        # TTFT ratchets as its INVERSE (ms -> 1/s) so "up" stays "better"
        prefix_p99 = prefix_block.get("ttft_p99_ms")
        serving_ttft_inv = (1e3 / prefix_p99) \
            if isinstance(prefix_p99, (int, float)) and prefix_p99 > 0 \
            else None
        prefix_rate = prefix_block.get("hit_rate")
        spec_block = serving_block.get("spec") \
            if isinstance(serving_block, dict) else None
        if not isinstance(spec_block, dict):
            spec_block = {}
        spec_speedup = spec_block.get("spec_decode_speedup")
        accept_len = spec_block.get("accept_len_mean")
        router_block = serving_block.get("router") \
            if isinstance(serving_block, dict) else None
        if not isinstance(router_block, dict):
            router_block = {}
        router_goodput = router_block.get("goodput_tok_s")
        router_p99 = router_block.get("ttft_p99_ms")
        router_ttft_inv = (1e3 / router_p99) \
            if isinstance(router_p99, (int, float)) and router_p99 > 0 \
            else None
        comm_block = doc.get("comm")
        a2a_ratio = comm_block.get("a2a_vs_allreduce_ratio") \
            if isinstance(comm_block, dict) else None
        quant_block = doc.get("quant")
        if not isinstance(quant_block, dict):
            quant_block = {}
        kv_shrink = quant_block.get("kv_bytes_shrink")
        quant_speedup = quant_block.get("quant_decode_speedup")
        lctx_block = doc.get("long_context")
        mfu_t2048 = lctx_block.get("mfu_t2048") \
            if isinstance(lctx_block, dict) else None
        obs_block = doc.get("observability")
        telemetry_inv = obs_block.get("overhead_inv") \
            if isinstance(obs_block, dict) else None
        traffic_block = doc.get("traffic")
        goodput_slo = traffic_block.get("goodput_under_slo") \
            if isinstance(traffic_block, dict) else None
        metric_name = doc.get("metric") or ""
        img_val = doc.get("value") if metric_name.endswith("imgs_per_sec") \
            else None
        metrics = {}
        for key, val in (("img_s", img_val), ("mfu", mfu_val),
                         ("steps_per_sec", block.get("steps_per_sec")),
                         ("fsdp_param_slot_shrink", fsdp_shrink),
                         ("serving_goodput", serving_goodput),
                         ("serving_ttft_p99_inv", serving_ttft_inv),
                         ("prefix_hit_rate", prefix_rate),
                         ("spec_decode_speedup", spec_speedup),
                         ("accept_len_mean", accept_len),
                         ("router_goodput", router_goodput),
                         ("router_ttft_p99_inv", router_ttft_inv),
                         ("a2a_vs_allreduce_ratio", a2a_ratio),
                         ("kv_bytes_shrink", kv_shrink),
                         ("quant_decode_speedup", quant_speedup),
                         ("mfu_t2048", mfu_t2048),
                         ("telemetry_overhead_inv", telemetry_inv),
                         ("goodput_under_slo", goodput_slo)):
            if isinstance(val, (int, float)) and val > 0:
                metrics[key] = val
        path = _ratchet_path()
        data = {}
        if os.path.exists(path):
            try:
                with open(path) as f:
                    data = json.load(f)
            except (OSError, ValueError):
                data = {}
        if not isinstance(data, dict):
            data = {}
        prev = dict(data.get(harness) or {})
        try:
            tol = float(os.environ.get("MXTPU_BENCH_RATCHET_TOL", "0.10"))
        except ValueError:
            tol = 0.10
        regressions = {k: {"baseline": prev[k], "current": v,
                           "ratio": round(v / prev[k], 4)}
                       for k, v in metrics.items()
                       if k in prev and v < prev[k] * (1 - tol)}
        wrote = None
        if metrics and os.environ.get("MXTPU_BENCH_NO_BASELINE") != "1":
            new_base = dict(prev)
            for k, v in metrics.items():
                new_base[k] = max(prev.get(k, 0.0), v)   # only ever up
            data[harness] = new_base
            tmp = f"{path}.tmp-{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(data, f, indent=2, sort_keys=True)
            os.replace(tmp, path)
            wrote = path
        doc["ratchet"] = {"harness": harness, "tolerance": tol,
                          "current": metrics, "baseline": prev or None,
                          "regressions": regressions, "baseline_file": wrote}
        if regressions:
            log(f"[ratchet] REGRESSION (> {tol:.0%} below baseline): "
                f"{regressions}")
    except Exception as e:   # the ratchet must never kill the scoreboard
        doc["ratchet"] = {"error": f"{type(e).__name__}: {e}"}


def bench_serving(smoke: bool = False):
    """Online-serving scenario (ISSUE 10): Poisson arrivals of generation
    requests against ``ServingEngine`` (continuous batching over a fixed
    slot batch) versus a serial per-request ``generate`` baseline replaying
    the *same* trace.

    Methodology: every request's solo ``generate`` latency is measured
    first (post-compile), giving the serial server's service times. The
    serial leg is then an exact virtual-clock FIFO replay — no sleeps:
    ``end_i = max(arrival_i, end_{i-1}) + service_i`` — while the engine
    leg replays the identical arrival offsets with real sleeps against the
    live scheduler thread. Arrivals are drawn at ~2.2x the serial server's
    capacity, so the serial queue grows without bound while the slot batch
    keeps up; *goodput* counts only tokens of requests finishing inside a
    deadline of a few solo service times. Greedy decode is asserted
    bit-exact against the solo outputs (``decode_match``) so the speedup is
    never bought with drift. All compiles happen in warmup, off the clock."""
    import jax  # noqa: F401  (backend selection happens at import)

    import mxtpu as mx
    from mxtpu import nd, profiler
    from mxtpu.gluon.model_zoo import transformer_lm
    from mxtpu.serving import ServingEngine

    mx.rng.seed(0)
    vocab = 50
    net = transformer_lm("tiny", vocab_size=vocab)
    net.initialize()

    # prompt lengths all land in the first 32-token prefill bucket and every
    # total lands in ONE scan bucket, so the whole trace costs exactly one
    # generate / one prefill / one decode program (asserted via the compile
    # ratchet in tests/test_serving_guard.py). max_new is deliberately large
    # relative to the 32-token prefill bucket: prefill is a serialized B=1
    # scan (one per admission), so decode — the part the slot batch
    # parallelizes — must carry most of each request's tokens for the
    # continuous-batching win to be about batching rather than bucketing.
    n_req = 24 if smoke else 32
    max_new = 160
    slots = 8
    load_factor = 1.8          # offered load vs measured serial capacity
    deadline_factor = 6.0
    rs = np.random.RandomState(7)
    prompts = [rs.randint(1, vocab, size=int(n)).tolist()
               for n in rs.randint(8, 32, size=n_req)]

    # -- solo reference pass: warms the generate program, records the
    # per-request service time and the bit-exact greedy continuation
    # (np.asarray inside the timed region: dispatch is async, only the
    # host readback waits for the result)
    refs, t_solo = [], []
    for p in prompts:
        arr = nd.array(np.array([p], np.int32))
        np.asarray(net.generate(arr, max_new).data)    # compile, off-clock
        t0 = time.perf_counter()
        out = np.asarray(net.generate(arr, max_new).data)
        t_solo.append(time.perf_counter() - t0)
        refs.append(out[0, len(p):].tolist())
    service = float(np.mean(t_solo))
    deadline_s = deadline_factor * service

    gaps = rs.exponential(service / load_factor, size=n_req)
    gaps[0] = 0.0
    arrivals = np.cumsum(gaps)

    # -- serial baseline: virtual-clock FIFO over the measured service times
    serial_end, serial_ok_tokens, serial_lat = 0.0, 0, []
    for i in range(n_req):
        start = max(float(arrivals[i]), serial_end)
        serial_end = start + t_solo[i]
        lat = serial_end - float(arrivals[i])
        serial_lat.append(lat)           # per-request generate: all tokens
        if lat <= deadline_s:            # arrive at completion
            serial_ok_tokens += max_new
    serial_span = max(serial_end, float(arrivals[-1]))
    serial_goodput = serial_ok_tokens / serial_span if serial_span else 0.0

    # -- engine leg: same arrival offsets, real sleeps, live scheduler
    engine = ServingEngine(net, slots=slots, queue_depth=n_req + 2, chunk=16)
    engine.start()
    longest = max(prompts, key=len)
    engine.submit(longest, max_new).result(timeout=300)   # warm prefill +
    profiler.reset_serving_stats()                        # decode, off-clock
    t_base = time.monotonic()
    reqs = []
    for i in range(n_req):
        wait = float(arrivals[i]) - (time.monotonic() - t_base)
        if wait > 0:
            time.sleep(wait)
        reqs.append(engine.submit(prompts[i], max_new))
    outs = [r.result(timeout=600) for r in reqs]
    span = time.monotonic() - t_base
    stats = profiler.get_serving_stats()
    engine.stop()

    decode_match = all(o == r for o, r in zip(outs, refs))
    ttft = np.array([r.t_first_token - r.t_submit for r in reqs])
    lat = np.array([r.t_done - r.t_submit for r in reqs])
    per_tok = lat / max_new
    ok_tokens = int(sum(max_new for v in lat if v <= deadline_s))
    goodput = ok_tokens / span if span else 0.0
    doc = {
        "requests": n_req,
        "max_new": max_new,
        "slots": slots,
        "chunk": engine.chunk,
        "offered_load_vs_serial": load_factor,
        "deadline_ms": deadline_s * 1e3,
        "solo_service_ms": service * 1e3,
        "ttft_p50_ms": float(np.percentile(ttft, 50) * 1e3),
        "ttft_p99_ms": float(np.percentile(ttft, 99) * 1e3),
        "per_token_p50_ms": float(np.percentile(per_tok, 50) * 1e3),
        "per_token_p99_ms": float(np.percentile(per_tok, 99) * 1e3),
        "goodput_tok_s": goodput,
        "serial_goodput_tok_s": serial_goodput,
        "goodput_vs_serial": goodput / serial_goodput
        if serial_goodput else float("inf"),
        "serial_ttft_p50_ms": float(np.percentile(serial_lat, 50) * 1e3),
        "deadline_met": int(sum(1 for v in lat if v <= deadline_s)),
        "serial_deadline_met": int(
            sum(1 for v in serial_lat if v <= deadline_s)),
        "decode_match": bool(decode_match),
        "slot_occupancy": stats.get("slot_occupancy"),
        "decode_steps": stats.get("decode_steps"),
        "kv_promotions": stats.get("kv_promotions"),
        "completed": stats.get("completed"),
        # TTFT decomposition (ISSUE 13): where the first-token wait went
        "ttft_queue_wait_ms_mean": stats.get("queue_wait_ms_total", 0.0)
        / max(1, stats.get("admitted", 0)),
        "ttft_prefill_ms_mean": stats.get("prefill_ms_total", 0.0)
        / max(1, stats.get("admitted", 0)),
        "first_decode_ms_mean": stats.get("first_decode_ms_total", 0.0)
        / max(1, stats.get("prefills", 0)),
    }
    log(f"[serving] {n_req} reqs x {max_new} tok, {slots} slots: goodput "
        f"{goodput:.1f} tok/s vs serial {serial_goodput:.1f} "
        f"({doc['goodput_vs_serial']:.2f}x), ttft p50 "
        f"{doc['ttft_p50_ms']:.1f} ms (queue {doc['ttft_queue_wait_ms_mean']:.1f}"
        f" + prefill {doc['ttft_prefill_ms_mean']:.1f}), match={decode_match}")
    doc["prefix"] = _bench_serving_prefix(net, vocab, smoke)
    doc["spec"] = _bench_serving_spec(net, vocab, smoke)
    doc["router"] = _bench_serving_router(net, vocab, smoke)
    return doc


def _bench_serving_prefix(net, vocab: int, smoke: bool):
    """Shared-system-prompt leg (ISSUE 13): N requests extend one 64-token
    system prompt with distinct tails and arrive as a burst. The baseline
    engine is the PR9 configuration — monolithic serialized prefill
    (``prefill_chunk`` = the whole bucket), prefix cache off — so its p99
    TTFT pays N-1 redundant system-prompt prefills queued behind each
    other. The treatment engine chunks prefill between decode dispatches
    AND reuses the radix-cached prefix, so the shared 64 tokens are
    prefilled exactly once (``hit_rate == (N-1)/N``) and every later
    request scans only its suffix. Both legs replay the identical trace;
    greedy decode is asserted bit-exact against solo ``generate`` so the
    TTFT win is never bought with drift. Compiles happen in warmup with a
    NON-shared same-bucket prompt (it must not seed the prefix the trace
    shares), off the clock."""
    import numpy as np

    from mxtpu import nd, profiler
    from mxtpu.serving import ServingEngine

    n_req = 6 if smoke else 12
    max_new = 48
    rs = np.random.RandomState(11)
    sys_prompt = rs.randint(1, vocab, size=64).tolist()
    prompts = [sys_prompt + rs.randint(1, vocab, size=int(n)).tolist()
               for n in rs.randint(9, 16, size=n_req)]
    warm_prompt = rs.randint(1, vocab, size=65).tolist()   # same buckets,
    refs = []                                              # different prefix
    for p in prompts:
        out = np.asarray(net.generate(
            nd.array(np.array([p], np.int32)), max_new).data)
        refs.append(out[0, len(p):].tolist())

    def run_leg_engine(prefill_chunk, prefix_mb):
        eng = ServingEngine(net, slots=4, queue_depth=n_req + 2, chunk=8,
                            prefill_chunk=prefill_chunk,
                            prefix_cache_mb=prefix_mb)
        eng.start()
        eng.submit(warm_prompt, max_new).result(timeout=300)  # compile,
        profiler.reset_serving_stats()                        # off-clock
        t0 = time.monotonic()
        reqs = [eng.submit(p, max_new) for p in prompts]      # burst
        outs = [r.result(timeout=600) for r in reqs]
        span = time.monotonic() - t0
        stats = profiler.get_serving_stats()
        eng.stop()
        ttft = np.array([r.t_first_token - r.t_submit for r in reqs])
        return {
            "ttft_p50_ms": float(np.percentile(ttft, 50) * 1e3),
            "ttft_p99_ms": float(np.percentile(ttft, 99) * 1e3),
            "span_ms": span * 1e3,
            "decode_match": bool(outs == refs),
            "hit_rate": stats.get("prefix_hit_rate", 0.0),
            "hit_tokens": stats.get("prefix_hit_tokens", 0),
            "prefill_chunks": stats.get("prefill_chunks", 0),
            "cache_bytes": stats.get("prefix_cache_bytes", 0),
            "queue_wait_ms_mean": stats.get("queue_wait_ms_total", 0.0)
            / max(1, stats.get("admitted", 0)),
            "prefill_ms_mean": stats.get("prefill_ms_total", 0.0)
            / max(1, stats.get("admitted", 0)),
        }

    base = run_leg_engine(prefill_chunk=net._max_len, prefix_mb=0)
    chunked = run_leg_engine(prefill_chunk=32, prefix_mb=64)
    doc = {
        "requests": n_req,
        "shared_prefix_tokens": 64,
        "max_new": max_new,
        "baseline": base,                 # PR9: monolithic prefill, no reuse
        "ttft_p50_ms": chunked["ttft_p50_ms"],
        "ttft_p99_ms": chunked["ttft_p99_ms"],
        "ttft_p99_improvement": base["ttft_p99_ms"]
        / max(1e-9, chunked["ttft_p99_ms"]),
        "hit_rate": chunked["hit_rate"],
        "hit_tokens": chunked["hit_tokens"],
        "prefill_chunks": chunked["prefill_chunks"],
        "cache_bytes": chunked["cache_bytes"],
        "queue_wait_ms_mean": chunked["queue_wait_ms_mean"],
        "prefill_ms_mean": chunked["prefill_ms_mean"],
        "decode_match": chunked["decode_match"] and base["decode_match"],
    }
    log(f"[serving/prefix] {n_req} reqs sharing 64 tok: ttft p99 "
        f"{chunked['ttft_p99_ms']:.1f} ms vs serialized "
        f"{base['ttft_p99_ms']:.1f} ms "
        f"({doc['ttft_p99_improvement']:.2f}x), hit rate "
        f"{chunked['hit_rate']:.2f}, match={doc['decode_match']}")
    return doc


def _bench_serving_spec(net, vocab: int, smoke: bool):
    """Speculative-decode A/B leg (ISSUE 18): the SAME draftable burst
    trace served spec-off and spec-on (``SpecConfig(k=4)``, n-gram
    drafter). Prompts repeat a short period — the shape boilerplate-heavy
    prompts and greedy loops both have — so the drafter's self-context
    lookup actually lands multi-token accepts. Both legs run ``chunk=1``
    (incremental token-streaming decode, the mode speculation exists to
    accelerate — the chunked scan is the orthogonal latency-for-throughput
    trade). ``spec_decode_speedup`` is decode-ONLY throughput
    (``decode_tokens / decode_ms_total``) spec-on over spec-off: one
    verify dispatch emitting up to k+1 tokens per slot against one
    single-token dispatch per turn, with prefill, queueing, and scheduler
    sleeps excluded. Greedy decode is
    asserted bit-exact against solo ``generate`` in BOTH legs (the
    accept/reject contract: speculation must never buy speed with drift).
    ``accept_len_mean`` (mean emitted tokens per live slot per verify
    dispatch) rides the BENCH_BASELINE ratchet next to the speedup. All
    compiles — verify program included, the warm prompt drafts too — off
    the clock."""
    import numpy as np

    from mxtpu import nd, profiler
    from mxtpu.serving import ServingEngine, SpecConfig

    n_req = 4 if smoke else 8
    max_new = 96 if smoke else 160
    slots = 4
    k = 4
    rs = np.random.RandomState(13)
    prompts = []
    for n in rs.randint(9, 16, size=n_req):
        period = rs.randint(1, vocab, size=4).tolist()
        prompts.append((period * 8)[:int(n)])
    warm_prompt = rs.randint(1, vocab, size=15).tolist()
    refs = []
    for p in prompts:
        out = np.asarray(net.generate(
            nd.array(np.array([p], np.int32)), max_new).data)
        refs.append(out[0, len(p):].tolist())

    def leg(spec):
        eng = ServingEngine(net, slots=slots, queue_depth=n_req + 2,
                            chunk=1, spec=spec)
        eng.start()
        eng.submit(warm_prompt, max_new).result(timeout=600)  # compile,
        profiler.reset_serving_stats()                        # off-clock
        t0 = time.monotonic()
        reqs = [eng.submit(p, max_new) for p in prompts]      # burst
        outs = [r.result(timeout=600) for r in reqs]
        span = time.monotonic() - t0
        stats = profiler.get_serving_stats()
        eng.stop()
        dec_ms = stats.get("decode_ms_total", 0.0)
        return {
            "decode_match": bool(outs == refs),
            "span_ms": span * 1e3,
            "decode_only_tok_s": (stats.get("decode_tokens", 0)
                                  / (dec_ms / 1e3)) if dec_ms else 0.0,
            "decode_tokens": stats.get("decode_tokens", 0),
            "decode_steps": stats.get("decode_steps", 0),
            "spec_dispatches": stats.get("spec_dispatches", 0),
            "tokens_drafted": stats.get("tokens_drafted", 0),
            "tokens_accepted": stats.get("tokens_accepted", 0),
            "tokens_rejected": stats.get("tokens_rejected", 0),
            "accept_len_mean": stats.get("accept_len_mean", 0.0),
            "accept_len_p50": stats.get("accept_len_p50", 0.0),
            "accept_len_p99": stats.get("accept_len_p99", 0.0),
        }

    off = leg(None)
    on = leg(SpecConfig(k=k))
    # drafter A/B (ISSUE 19): the SAME trace through the draft-LM seam.
    # Self-drafting (the target as its own draft model) is the acceptance
    # UPPER BOUND — every proposal verifies, so accept_len should sit near
    # k+1; decode_match still must hold (the advisory contract is what is
    # under test, not the draft model's quality). Draft forwards run on the
    # scheduler thread between dispatches: they stretch span_ms, never
    # decode_ms, so decode_only_tok_s stays the verify-dispatch measure.
    from mxtpu.serving import ModelDrafter
    drafter = ModelDrafter(net)
    draft_lm = leg(SpecConfig(k=k, drafter=drafter))
    draft_lm.update(drafter.stats())
    doc = {
        "requests": n_req,
        "max_new": max_new,
        "slots": slots,
        "k": k,
        "off": off,
        "on": on,
        "draft_lm": draft_lm,
        "spec_decode_speedup": on["decode_only_tok_s"]
        / max(off["decode_only_tok_s"], 1e-9),
        "draft_lm_decode_speedup": draft_lm["decode_only_tok_s"]
        / max(off["decode_only_tok_s"], 1e-9),
        "accept_len_mean": on["accept_len_mean"],
        "decode_match": (off["decode_match"] and on["decode_match"]
                         and draft_lm["decode_match"]),
    }
    log(f"[serving/spec] {n_req} reqs x {max_new} tok, k={k}: decode "
        f"{on['decode_only_tok_s']:.1f} tok/s vs plain "
        f"{off['decode_only_tok_s']:.1f} "
        f"({doc['spec_decode_speedup']:.2f}x), accept_len mean "
        f"{on['accept_len_mean']:.2f} "
        f"({on['tokens_accepted']}/{on['tokens_drafted']} drafts), "
        f"draft-LM accept_len {draft_lm['accept_len_mean']:.2f} "
        f"({draft_lm['draft_lm_calls']} draft calls), "
        f"match={doc['decode_match']}")
    return doc


def _bench_serving_router(net, vocab: int, smoke: bool):
    """Multi-replica router leg (ISSUE 19): the SAME arrival trace fronted
    by a 2-replica :class:`~mxtpu.serving.router.Router` versus one
    replica-sized engine. Two measures, one real and one projected — the
    split mirrors the main leg's virtual-clock serial baseline:

    * **real** — two in-process replicas behind the real router, real
      sleeps: greedy stays bit-exact (``decode_match``), nothing drops
      (``requests_dropped``), the affinity/least-loaded/spill counters
      show the decision mix, and ``goodput_tok_s`` / TTFT percentiles
      ride the ratchet. In-process replicas share the host's cores, so
      this number tracks ROUTER overhead, not scale-out.
    * **scaleout (virtual clock)** — the replica placements the real
      router actually chose, replayed over independent slot-servers
      parameterized by the measured solo service times (each replica at
      full speed — the scale-out premise), against the identical
      single-server replay of the same trace. Offered load is ~2.5x one
      engine's slot capacity with a 1.25x-service deadline, so the single
      server's queue outgrows the deadline while two replicas keep up:
      ``scaleout_goodput_vs_single`` is the >1.5x acceptance ratio.

    The two shared-prefix populations are seeded so their first 32-token
    blocks rendezvous onto DISTINCT replicas (checked via the router's own
    hash) — the leg exercises both affinity homes instead of gambling on a
    25% both-map-same-rid draw. A sharded replica (fsdp x tp mesh) joins a
    smoke probe only when >= 8 devices are visible; on smaller hosts the
    leg degrades to plain replicas and says so (``sharded_replica``)."""
    import jax

    from mxtpu import nd, profiler
    from mxtpu.serving import Router, ServingEngine

    slots, max_new, chunk = 4, 48, 8
    n_aff = 3 if smoke else 5           # per shared-prefix population
    n_rand = 4 if smoke else 6
    rs = np.random.RandomState(17)

    def factory(rid):
        return ServingEngine(net, slots=slots, queue_depth=32, chunk=chunk,
                             engine_id=rid)

    router = Router.local(factory, 2)
    rids = router.replica_ids
    # two prefix populations pinned to DISTINCT affinity homes (see above)
    prefix_a = rs.randint(1, vocab, size=32).tolist()
    home_a = router._affinity_rid(prefix_a, True, sorted(rids))
    while True:
        prefix_b = rs.randint(1, vocab, size=32).tolist()
        if router._affinity_rid(prefix_b, True, sorted(rids)) != home_a:
            break
    prompts = [prefix_a + rs.randint(1, vocab, size=4).tolist()
               for _ in range(n_aff)]
    prompts += [prefix_b + rs.randint(1, vocab, size=4).tolist()
                for _ in range(n_aff)]
    prompts += [rs.randint(1, vocab, size=int(n)).tolist()
                for n in rs.randint(8, 24, size=n_rand)]
    order = rs.permutation(len(prompts))
    prompts = [prompts[i] for i in order]
    n_req = len(prompts)

    refs, t_solo = [], []
    for p in prompts:
        arr = nd.array(np.array([p], np.int32))
        np.asarray(net.generate(arr, max_new).data)      # compile off-clock
        t0 = time.perf_counter()
        out = np.asarray(net.generate(arr, max_new).data)
        t_solo.append(time.perf_counter() - t0)
        refs.append(out[0, len(p):].tolist())
    service = float(np.mean(t_solo))
    deadline_s = 1.25 * service
    gaps = rs.exponential(service / (slots * 2.5), size=n_req)
    gaps[0] = 0.0
    arrivals = np.cumsum(gaps)

    # -- real leg: warm both replicas off-clock, then replay the trace
    router.start()
    for rid in rids:
        eng = router._replicas[rid].engine
        eng.submit(max(prompts, key=len), max_new).result(timeout=300)
        eng.submit(min(prompts, key=len), max_new).result(timeout=300)
    profiler.reset_serving_stats()
    t_base = time.monotonic()
    handles, assign = [], []
    for i in range(n_req):
        wait = float(arrivals[i]) - (time.monotonic() - t_base)
        if wait > 0:
            time.sleep(wait)
        h = router.submit(prompts[i], max_new)
        handles.append(h)
        assign.append(next(r for r, book in router._inflight.items()
                           if h._seg.id in book))
    outs = [h.result(timeout=600) for h in handles]
    span = time.monotonic() - t_base
    rstats = profiler.get_router_stats()
    router.stop()
    decode_match = all(o == r for o, r in zip(outs, refs))
    ttft = np.array([h._seg.t_first_token - h._seg.t_submit
                     for h in handles])

    # -- virtual-clock scale-out projection over the real placements
    def goodput_virtual(assignment):
        free = {rid: [0.0] * slots for rid in set(assignment)}
        ends = []
        for i in range(n_req):
            srv = free[assignment[i]]
            j = min(range(slots), key=srv.__getitem__)
            end = max(float(arrivals[i]), srv[j]) + t_solo[i]
            srv[j] = end
            ends.append(end)
        vspan = max(ends)
        ok = sum(max_new for i, e in enumerate(ends)
                 if e - float(arrivals[i]) <= deadline_s)
        return ok / vspan if vspan else 0.0

    scale_router = goodput_virtual(assign)
    scale_single = goodput_virtual([rids[0]] * n_req)
    doc = {
        "requests": n_req,
        "max_new": max_new,
        "slots": slots,
        "replicas": 2,
        "decode_match": bool(decode_match),
        "goodput_tok_s": n_req * max_new / span if span else 0.0,
        "ttft_p50_ms": float(np.percentile(ttft, 50) * 1e3),
        "ttft_p99_ms": float(np.percentile(ttft, 99) * 1e3),
        "requests_dropped": rstats["requests_dropped"],
        "routed_affinity": rstats["routed_affinity"],
        "routed_least_loaded": rstats["routed_least_loaded"],
        "routed_spill": rstats["routed_spill"],
        "placement": {rid: assign.count(rid) for rid in rids},
        "deadline_ms": deadline_s * 1e3,
        "scaleout_router_goodput": scale_router,
        "scaleout_single_goodput": scale_single,
        "scaleout_goodput_vs_single": scale_router
        / max(scale_single, 1e-9),
    }

    # sharded-replica probe: only meaningful with a real mesh to place on
    n_dev = len(jax.devices())
    if n_dev >= 8:
        from mxtpu.parallel.mesh import make_mesh
        mesh = make_mesh((4, 2), ("fsdp", "tp"))
        probe = Router([ServingEngine(net, slots=slots, queue_depth=8,
                                      chunk=chunk, mesh=mesh,
                                      engine_id="mesh0"),
                        ServingEngine(net, slots=slots, queue_depth=8,
                                      chunk=chunk, engine_id="plain1")])
        with probe:
            got = [probe.submit(p, max_new).result(timeout=600)
                   for p in prompts[:2]]
        doc["sharded_replica"] = {"devices": n_dev,
                                  "ok": bool(got == refs[:2])}
    else:
        doc["sharded_replica"] = {"devices": n_dev, "skipped": True}

    log(f"[serving/router] {n_req} reqs x {max_new} tok, 2x{slots} slots: "
        f"goodput {doc['goodput_tok_s']:.1f} tok/s, ttft p99 "
        f"{doc['ttft_p99_ms']:.1f} ms, scale-out "
        f"{doc['scaleout_goodput_vs_single']:.2f}x vs single, placement "
        f"{doc['placement']}, dropped {doc['requests_dropped']}, "
        f"match={decode_match}")
    return doc


def bench_traffic(smoke: bool = False):
    """Multi-tenant traffic-replay scenario (ISSUE 17): the SAME seeded
    bursty arrival trace (``mxtpu.sched.replay``) — three tenants with
    shared per-tenant prefixes, a batch-tier bulk tenant flooding the burst
    windows while interactive chat requests arrive inside them — replayed
    against two engines:

    * **fifo** — the plain engine (``sched=None``): arrival order is
      admission order, so interactive requests queue behind the bulk flood;
    * **sched** — the SLO control plane on (``sched=True``, batched
      prefill): strict tier priority + weighted fair share admits the
      interactive arrivals first, preempting bulk decode slots when
      saturated (parked KV, bit-exact on resume).

    Headline is the sched leg's ``goodput_under_slo`` — tokens of requests
    that completed inside their tenant's latency budget, per second of
    replay span (the metric the BENCH_BASELINE ratchet tracks). Greedy
    decode is asserted bit-exact against solo ``generate`` in BOTH legs
    (preemption/batching must never buy latency with drift). A dry-run
    :class:`~mxtpu.sched.autoscale.Autoscaler` consumes the sched leg's
    stats snapshots on a fake clock, so the telemetry->decision loop runs
    end to end every bench run. All compiles happen in warmup with a
    non-shared prompt, off the clock."""
    import jax  # noqa: F401

    import mxtpu as mx
    from mxtpu import nd, profiler
    from mxtpu.gluon.model_zoo import transformer_lm
    from mxtpu.sched import (Autoscaler, AutoscalePolicy, TenantProfile,
                             make_trace)
    from mxtpu.serving import ServingEngine

    mx.rng.seed(0)
    vocab = 50
    net = transformer_lm("tiny", vocab_size=vocab)
    net.initialize()

    # latency budgets (measure-only: goodput accounting, not engine
    # deadlines — expiry would truncate decodes and void decode_match)
    budgets = {"chat": 2.0, "app": 4.0, "bulk": 10.0}
    tenants = (
        TenantProfile("chat", priority="interactive", share=1.0,
                      prefix_len=32, suffix_len=5, max_new=10),
        TenantProfile("app", priority="standard", share=1.0,
                      prefix_len=32, suffix_len=7, max_new=16),
        TenantProfile("bulk", priority="batch", share=2.0,
                      prefix_len=32, suffix_len=9, max_new=64),
    )
    # bulk totals (41 + 64 = 105) overflow the 64-token admission bucket, so
    # a burst of bulk requests holds BOTH decode slots for many chunks —
    # chat/app complete at admission, and an interactive arrival inside a
    # burst exercises the preempt/park/resume path instead of a no-op
    trace = make_trace("bursty", seed=5, rate=40.0 if smoke else 60.0,
                       duration_s=0.6 if smoke else 1.2, vocab=vocab,
                       tenants=tenants)
    slots, chunk = 2, 8

    # solo reference pass: bit-exact continuations + the compile warmup for
    # the generate program (off every leg's clock)
    refs = []
    for tr in trace.requests:
        out = np.asarray(net.generate(
            nd.array(np.array([list(tr.prompt)], np.int32)),
            tr.max_new).data)
        refs.append(out[0, len(tr.prompt):].tolist())

    max_total = max(len(t.prompt) + t.max_new for t in trace.requests)
    rs = np.random.RandomState(23)
    warm_prompt = rs.randint(1, vocab, size=37).tolist()  # same PB bucket,
    warm_new = max_total - len(warm_prompt)               # non-shared prefix
    warm_solo = rs.randint(1, vocab, size=37).tolist()
    warm_hit = [warm_prompt[:32] + rs.randint(1, vocab, size=5).tolist()
                for _ in range(3)]

    def leg(sched, spec=None):
        eng = ServingEngine(net, slots=slots, chunk=chunk,
                            queue_depth=len(trace) + 4,
                            sched=True if sched else None,
                            prefill_batch=2 if sched else None,
                            spec=spec)
        eng.start()

        def warm(lead, pair=None):
            base = profiler.get_serving_stats()["admitted"]
            ws = [eng.submit(*lead, tenant="warm", priority="standard")]
            if pair:
                # let the lead be admitted SOLO (scalar path); its prefill
                # program compiles on this first dispatch, and the pair
                # queues up behind it, so both land in ONE batched group
                while profiler.get_serving_stats()["admitted"] == base:
                    time.sleep(0.001)
                ws += [eng.submit(p, n, tenant="warm", priority="standard")
                       for p, n in pair]
            for w in ws:
                w.result(timeout=300)

        # warm every program variant the replay will hit, off the clock:
        #   wave 1: scalar miss (PB,PB); batched miss (N,PB,PB); decode at
        #           the max TOT bucket (the pair's totals overflow PB)
        #   wave 2: scalar prefix-hit (PB,PB-32) — the wave-1 pair seeded
        #           the warm prefix block — then the batched-hit twin
        if sched:
            warm((warm_solo, 8),
                 [(warm_prompt, warm_new), (warm_prompt, warm_new)])
            warm((warm_hit[2], 8), [(warm_hit[0], 8), (warm_hit[1], 8)])
        else:
            warm((warm_prompt, warm_new))
            warm((warm_hit[2], 8))
        profiler.reset_serving_stats()
        scaler = Autoscaler(AutoscalePolicy(breach_ticks=2, cooldown_s=5.0),
                            dry_run=True) if sched else None
        t_base = time.monotonic()
        reqs = []
        for tr in trace.requests:
            wait = tr.t - (time.monotonic() - t_base)
            if wait > 0:
                time.sleep(wait)
            reqs.append(eng.submit(list(tr.prompt), tr.max_new,
                                   tenant=tr.tenant, priority=tr.priority))
            if scaler is not None:
                scaler.step(profiler.get_serving_stats(), now=tr.t)
        outs = [r.result(timeout=600) for r in reqs]
        span = time.monotonic() - t_base
        stats = profiler.get_serving_stats()
        eng.stop()

        match = all(o == r for o, r in zip(outs, refs))
        by_tier = {}
        ok_tokens = 0
        for tr, r in zip(trace.requests, reqs):
            lat = r.t_done - r.t_submit
            if lat <= budgets[tr.tenant]:
                ok_tokens += tr.max_new
            by_tier.setdefault(tr.priority, []).append(
                (r.t_first_token - r.t_submit) * 1e3)
        tiers = {tier: {"n": len(v),
                        "ttft_p50_ms": float(np.percentile(v, 50)),
                        "ttft_p99_ms": float(np.percentile(v, 99))}
                 for tier, v in by_tier.items()}
        out = {
            "goodput_under_slo": ok_tokens / span if span else 0.0,
            "span_s": round(span, 3),
            "decode_match": bool(match),
            "ttft_by_tier": tiers,
            "slot_occupancy": stats.get("slot_occupancy"),
            "preempted": stats.get("preempted"),
            "resumed": stats.get("resumed"),
            "shed": stats.get("shed"),
            "prefill_groups": stats.get("prefill_groups"),
            "prefix_hits": stats.get("prefix_hits"),
            "prefix_partial_hits": stats.get("prefix_partial_hits"),
        }
        if spec is not None:
            out["spec_dispatches"] = stats.get("spec_dispatches", 0)
            out["tokens_drafted"] = stats.get("tokens_drafted", 0)
            out["tokens_accepted"] = stats.get("tokens_accepted", 0)
            out["accept_len_mean"] = stats.get("accept_len_mean", 0.0)
        if scaler is not None:
            table = scaler.decision_table()
            out["autoscale_dry_run"] = {
                "ticks": len(table),
                "actions": {a: sum(1 for d in table if d["action"] == a)
                            for a in ("scale_up", "scale_down", "hold")},
                "actuated": any(d["actuated"] for d in table),  # must stay
            }                                                   # False: dry
        return out

    fifo = leg(sched=False)
    sched = leg(sched=True)
    # speculative A/B on the SAME trace: the sched leg re-run with the
    # n-gram draft + batched-verify decode on (ISSUE 18) — goodput and
    # bit-exactness must survive speculation under preemption and
    # multi-tenant churn, not just in the clean serving bench
    spec = leg(sched=True, spec=4)
    inter_fifo = fifo["ttft_by_tier"].get("interactive", {})
    inter_sched = sched["ttft_by_tier"].get("interactive", {})
    doc = {
        "kind": trace.kind,
        "requests": len(trace),
        "tenants": {p.name: {"priority": p.priority, "share": p.share,
                             "budget_s": budgets[p.name]}
                    for p in tenants},
        "slots": slots,
        "chunk": chunk,
        "fifo": fifo,
        "sched": sched,
        "spec": {
            "goodput_under_slo": spec["goodput_under_slo"],
            "goodput_vs_plain_sched": spec["goodput_under_slo"]
            / max(sched["goodput_under_slo"], 1e-9),
            "decode_match": spec["decode_match"],
            "spec_dispatches": spec["spec_dispatches"],
            "tokens_drafted": spec["tokens_drafted"],
            "tokens_accepted": spec["tokens_accepted"],
            "accept_len_mean": spec["accept_len_mean"],
            "preempted": spec["preempted"],
        },
        "goodput_under_slo": sched["goodput_under_slo"],
        "goodput_vs_fifo": sched["goodput_under_slo"]
        / max(fifo["goodput_under_slo"], 1e-9),
        "interactive_ttft_p99_ms": inter_sched.get("ttft_p99_ms"),
        "interactive_ttft_p99_vs_fifo": (
            inter_fifo.get("ttft_p99_ms", 0.0)
            / max(inter_sched.get("ttft_p99_ms", 0.0), 1e-9)),
        "decode_match": fifo["decode_match"] and sched["decode_match"],
    }
    log(f"[traffic] {len(trace)} reqs ({trace.kind}): goodput under SLO "
        f"{sched['goodput_under_slo']:.1f} tok/s (fifo "
        f"{fifo['goodput_under_slo']:.1f}, "
        f"{doc['goodput_vs_fifo']:.2f}x), interactive ttft p99 "
        f"{inter_sched.get('ttft_p99_ms', 0):.1f} ms vs fifo "
        f"{inter_fifo.get('ttft_p99_ms', 0):.1f} ms, preempted "
        f"{sched['preempted']}, match={doc['decode_match']}")
    log(f"[traffic/spec] sched+spec leg: goodput "
        f"{spec['goodput_under_slo']:.1f} tok/s "
        f"({doc['spec']['goodput_vs_plain_sched']:.2f}x plain sched), "
        f"accept_len mean {spec['accept_len_mean']:.2f}, "
        f"match={spec['decode_match']}")
    return doc


def bench_quant(smoke: bool = False):
    """Low-precision execution scenario (ISSUE 14): the same burst trace
    served three ways — fp32, int8 paged-KV, and int8 KV + int8 per-channel
    weights — plus the quantized fused training step.

    Capacity is the headline: ``kv_bytes_shrink`` is the resident-KV ratio
    at IDENTICAL slot count (measured from ``kv_bytes_resident``, not
    computed), and ``resident_slots_at_budget`` re-derives how many decode
    slots each mode fits into the fp32 leg's KV footprint. Latency rides
    along (decode tok/s, p99 TTFT per mode). ``quant_decode_speedup`` =
    fp32 over int8-KV decode-PROGRAM step time (min-of-N wall of the
    compiled ``build_decode`` program at the model's full position table —
    exactly what the fused dequant-attention read changes, with prefill,
    queueing, and burst-shape noise excluded; ISSUE 16 ratchets this
    > 1.0, and the per-mode ``decode_step_ms_*`` keys ride along). Each
    engine leg also reports ``decode_only_tok_s`` (median per-token
    decode-dispatch wall from the serving stats). The int8-KV
    leg also A/Bs BOTH fused decode-kernel paths (``variants``: 'pallas'
    runs the real kernel body — interpret mode on CPU — and 'xla' the
    int8-``dot_general`` fallback; each must stay token-exact) and reports
    the active one as ``decode_kernel``. int8-KV greedy decode is asserted
    token-exact against solo ``generate``; the weight-quantized leg reports
    its logits deviation budget instead (see docs/quantization.md). One
    compiled program per (slots, bucket, chunk) per mode — asserted via the
    serving compile counters."""
    import jax  # noqa: F401

    import mxtpu as mx
    from mxtpu import nd, profiler
    from mxtpu.gluon.model_zoo import transformer_lm
    from mxtpu.serving import ServingEngine, kv as skv

    mx.rng.seed(0)
    vocab = 50
    net = transformer_lm("tiny", vocab_size=vocab)
    net.initialize()

    n_req = 6 if smoke else 16
    max_new = 24 if smoke else 96
    slots = 4
    rs = np.random.RandomState(21)
    prompts = [rs.randint(1, vocab, size=int(n)).tolist()
               for n in rs.randint(8, 32, size=n_req)]
    refs = []
    for p in prompts:
        out = np.asarray(net.generate(
            nd.array(np.array([p], np.int32)), max_new).data)
        refs.append(out[0, len(p):].tolist())

    def serve_leg(quant, decode_kernel=None, legs=None, new=None):
        if legs is None:
            reqs_in, leg_refs = prompts, refs
        else:
            # the LONGEST prompts, so prompt + new overflows the prefill
            # bucket and the burst exercises actual decode dispatches
            order = sorted(range(n_req), key=lambda i: -len(prompts[i]))
            reqs_in = [prompts[i] for i in order[:legs]]
            leg_refs = [refs[i] for i in order[:legs]]
        want = max_new if new is None else new
        eng = ServingEngine(net, slots=slots, queue_depth=n_req + 2,
                            chunk=8, quant=quant,
                            decode_kernel=decode_kernel)
        eng.start()
        eng.submit(max(reqs_in, key=len), want).result(timeout=300)
        profiler.reset_serving_stats()                       # warm off-clock
        t0 = time.monotonic()
        reqs = [eng.submit(p, want) for p in reqs_in]        # burst
        outs = [r.result(timeout=600) for r in reqs]
        span = time.monotonic() - t0
        stats = profiler.get_serving_stats()
        eng.stop()
        ttft = np.array([r.t_first_token - r.t_submit for r in reqs])
        # greedy prefixes agree: a shorter run matches the ref's head
        match = sum(o == r[:want] for o, r in zip(outs, leg_refs))
        # decode-only throughput: median per-token decode-dispatch wall
        # (one token_ms sample per dispatch), prefill/queueing/scheduler
        # time excluded — what the fused kernel actually changes (the
        # quant_decode_speedup basis); the median resists one slow dispatch
        # on a noisy host where the mean does not
        tok_ms = stats.get("token_ms_p50", 0.0)
        return {
            "decode_tok_s": len(reqs_in) * want / span if span else 0.0,
            "decode_only_tok_s": 1e3 / tok_ms if tok_ms else 0.0,
            "ttft_p50_ms": float(np.percentile(ttft, 50) * 1e3),
            "ttft_p99_ms": float(np.percentile(ttft, 99) * 1e3),
            "kv_bytes_resident": stats.get("kv_bytes_resident", 0),
            "kv_dtype": stats.get("kv_dtype"),
            "decode_kernel": stats.get("decode_kernel"),
            "decode_match": int(match),
            "decode_steps": stats.get("decode_steps"),
        }

    fp32 = serve_leg(None)
    i8kv = serve_leg("int8_kv")
    # A/B both fused decode-kernel paths at the same quant mode: the leg
    # that matches the backend-auto choice reruns tiny (it already ran
    # full-size above); the other gets its own reduced burst — on CPU that
    # exercises the REAL pallas kernel body in interpret mode
    variants = {}
    for kern in ("xla", "pallas"):
        variants[kern] = serve_leg("int8_kv", decode_kernel=kern,
                                   legs=2, new=24)
        if variants[kern]["decode_match"] != 2:
            raise AssertionError(
                f"int8-KV {kern} decode-kernel variant must stay "
                f"token-exact: {variants[kern]['decode_match']}/2")
        if not variants[kern]["decode_steps"]:
            raise AssertionError(
                f"decode-kernel variant {kern!r} never dispatched decode — "
                "the probe burst must overflow the prefill bucket")
    i8kv["variants"] = variants
    i8w = serve_leg("int8_kv,int8_w")
    if i8kv["decode_match"] != n_req:
        raise AssertionError(
            f"int8-KV greedy decode must stay token-exact: "
            f"{i8kv['decode_match']}/{n_req}")
    shrink = fp32["kv_bytes_resident"] / max(1, i8kv["kv_bytes_resident"])

    # -- decode-program speedup (the ratchet basis) -------------------------
    # min-of-N wall time of the COMPILED decode program itself, fp32 vs
    # int8-KV, at a fixed (slots, TOT, chunk): this is precisely what the
    # fused dequant-attention read changes, measured without prefill,
    # scheduling, or burst-shape noise (min-of-N is the standard stable
    # microbench estimator; the engine legs above keep the end-to-end
    # numbers). TOT is the model's full position table — the long-context
    # end of the bucket range, where the KV read actually costs something.
    def decode_program_ms(quant, TOT, reps):
        import jax
        import jax.numpy as jnp
        from mxtpu.quant.serve import parse_quant, quantize_lm
        spec = parse_quant(quant)
        params = quantize_lm(net, spec)
        caches = skv.empty_cache(net, slots, TOT, jnp.float32, spec)
        fn = skv.build_decode(net, slots, TOT, 8, quant=spec)
        args = (params, caches, jnp.zeros((slots,), jnp.int32),
                jnp.full((slots,), TOT // 2, jnp.int32),
                jnp.ones((slots,), bool), jnp.full((slots,), TOT, jnp.int32),
                jnp.zeros((slots,), jnp.float32),
                jnp.zeros((slots,), jnp.int32),
                jnp.zeros((slots,), jnp.uint32))
        jax.block_until_ready(fn(*args))                    # trace off-clock
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            best = min(best, time.perf_counter() - t0)
        return best / 8 * 1e3                               # ms per step

    dec_TOT = net._max_len
    dec_reps = 30 if smoke else 60
    dec_fp32_ms = decode_program_ms(None, dec_TOT, dec_reps)
    dec_i8kv_ms = decode_program_ms("int8_kv", dec_TOT, dec_reps)
    speedup = dec_fp32_ms / max(1e-9, dec_i8kv_ms)
    # capacity: decode slots per mode inside the fp32 leg's KV footprint
    budget = fp32["kv_bytes_resident"]
    per_slot = {tag: leg["kv_bytes_resident"] / slots
                for tag, leg in (("fp32", fp32), ("int8_kv", i8kv))}
    slots_at_budget = {tag: int(budget // b) if b else 0
                       for tag, b in per_slot.items()}
    block_shrink = skv.block_nbytes(net, "float32", None) \
        / skv.block_nbytes(net, "float32", "int8")

    # -- quantized fused training step (MXTPU_QUANT_STEP) -------------------
    def train_leg(mode, steps):
        prev = os.environ.pop("MXTPU_QUANT_STEP", None)
        if mode:
            os.environ["MXTPU_QUANT_STEP"] = mode
        try:
            mx.rng.seed(0)
            m = transformer_lm("tiny", vocab_size=vocab)
            mod = mx.Module(m, data_names=("data",),
                            label_names=("softmax_label",))
            from mxtpu.io import DataBatch, DataDesc
            mod.bind(data_shapes=[DataDesc("data", (4, 16))],
                     label_shapes=[DataDesc("softmax_label", (4, 16))])
            mod.init_params()
            mod.init_optimizer(optimizer="adam",
                               optimizer_params={"learning_rate": 3e-3})
            rs2 = np.random.RandomState(0)
            x = nd.array(rs2.randint(0, vocab, (4, 16)).astype(np.int32))
            y = nd.array(rs2.randint(0, vocab, (4, 16)).astype(np.float32))
            b = DataBatch(data=[x], label=[y])
            mod.forward_backward(b)
            mod.update()                                 # trace, off-clock
            losses, t0 = [], time.perf_counter()
            for _ in range(steps):
                mod.forward_backward(b)
                mod.update()
                losses.append(float(mod._loss_val.mean().data))
            return {"step_ms": (time.perf_counter() - t0) / steps * 1e3,
                    "loss_end": losses[-1]}
        finally:
            os.environ.pop("MXTPU_QUANT_STEP", None)
            if prev is not None:
                os.environ["MXTPU_QUANT_STEP"] = prev

    steps = 4 if smoke else 20
    tr_fp32 = train_leg(None, steps)
    tr_int8 = train_leg("int8", steps)
    qstats = profiler.get_quant_stats()
    doc = {
        "requests": n_req,
        "max_new": max_new,
        "slots": slots,
        "fp32": fp32,
        "int8_kv": i8kv,
        "int8_kv_int8_w": i8w,
        "kv_bytes_shrink": shrink,
        "kv_block_shrink": block_shrink,
        "quant_decode_speedup": speedup,
        "decode_program_tot": dec_TOT,
        "decode_step_ms_fp32": dec_fp32_ms,
        "decode_step_ms_int8_kv": dec_i8kv_ms,
        "resident_slots_at_fp32_budget": slots_at_budget,
        "weight_leg_token_agreement": i8w["decode_match"] / n_req,
        "train_step_ms_fp32": tr_fp32["step_ms"],
        "train_step_ms_int8": tr_int8["step_ms"],
        "train_loss_end_fp32": tr_fp32["loss_end"],
        "train_loss_end_int8": tr_int8["loss_end"],
        "quant_matmul_sites": qstats.get("matmuls"),
    }
    log(f"[quant] kv shrink {shrink:.2f}x at {slots} slots "
        f"({fp32['kv_bytes_resident']} -> {i8kv['kv_bytes_resident']} B), "
        f"decode step @T{dec_TOT} {dec_i8kv_ms:.3f} vs fp32 "
        f"{dec_fp32_ms:.3f} ms ({speedup:.2f}x, kernel "
        f"{i8kv['decode_kernel']}), int8-KV match "
        f"{i8kv['decode_match']}/{n_req}, quant step "
        f"{tr_int8['step_ms']:.1f} ms vs fp32 {tr_fp32['step_ms']:.1f} ms")
    return doc


def _sanitize_requested() -> bool:
    """``--sanitize`` flag (honoured by both harnesses)."""
    return "--sanitize" in sys.argv


def _resilience_only() -> bool:
    """``bench.py resilience`` — run just the fault-injection/supervised-
    resume scenario and emit a resilience-only JSON line (both harnesses
    honour it)."""
    return "resilience" in sys.argv[1:]


def _emit_resilience_only(smoke: bool) -> None:
    import jax
    resil = run_leg("resilience", bench_resilience, smoke=smoke)
    doc = {"metric": "resilience_supervised_resume",
           "value": (1.0 if isinstance(resil, dict)
                     and resil.get("params_match") else 0.0),
           "unit": "params_match",
           "platform": jax.default_backend(),
           "resilience": resil}
    print(json.dumps(doc))


def _comm_only() -> bool:
    """``bench.py comm`` — run just the comm leg (allreduce bandwidth tiers +
    the a2a before/after sweep) and emit a comm-only JSON line. On a
    single-device host the sweep runs on an 8-way virtual CPU mesh
    (``force_virtual_cpu_devices``) and ratchets under ``comm-virtual8`` so
    virtual-wire numbers never mix with real-pod baselines."""
    return "comm" in sys.argv[1:]


def _emit_comm_only() -> None:
    import jax
    harness = "comm"
    if len(jax.devices()) == 1 \
            and os.environ.get("MXTPU_BENCH_COMM_VIRTUAL") != "1":
        # the device-count flag only lands at backend init — re-exec with the
        # 8-way virtual pod (reported under its own harness name below)
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   MXTPU_BENCH_COMM_VIRTUAL="1",
                   XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                              + " --xla_force_host_platform_device_count=8"
                              ).strip())
        os.execve(sys.executable,
                  [sys.executable, os.path.abspath(__file__)]
                  + sys.argv[1:], env)
    if os.environ.get("MXTPU_BENCH_COMM_VIRTUAL") == "1":
        harness = "comm-virtual8"
    comm = run_leg("comm", bench_comm)
    probe = comm.get("all_to_all_probe", {}) if isinstance(comm, dict) else {}
    doc = {"metric": "a2a_vs_allreduce_ratio",
           "value": (comm.get("a2a_vs_allreduce_ratio", 0.0)
                     if isinstance(comm, dict) else 0.0),
           "unit": "allreduce_ms/a2a_ms (64MB)",
           "platform": jax.default_backend(),
           "a2a_gap": probe.get("gap"),
           "comm": comm}
    apply_ratchet(doc, harness)
    print(json.dumps(doc))


def _quant_only() -> bool:
    """``bench.py quant`` — run just the low-precision scenario (fp32 vs
    int8-KV vs int8-KV+int8-W serving plus the quantized fused train step)
    and emit a quant-only JSON line (both harnesses honour it)."""
    return "quant" in sys.argv[1:]


def _emit_quant_only(smoke: bool) -> None:
    import jax
    quant = run_leg("quant", bench_quant, smoke=smoke)
    doc = {"metric": "kv_bytes_shrink",
           "value": (quant.get("kv_bytes_shrink", 0.0)
                     if isinstance(quant, dict) else 0.0),
           "unit": "fp32_kv_bytes/int8_kv_bytes",
           "platform": jax.default_backend(),
           "quant": quant}
    apply_ratchet(doc, harness="quant")
    print(json.dumps(doc))


def _serving_only() -> bool:
    """``bench.py serving`` — run just the online-serving latency/goodput
    scenario and emit a serving-only JSON line (both harnesses honour
    it)."""
    return "serving" in sys.argv[1:]


def _emit_serving_only(smoke: bool) -> None:
    import jax
    serving = run_leg("serving", bench_serving, smoke=smoke)
    doc = {"metric": "serving_goodput_tok_s",
           "value": (serving.get("goodput_tok_s", 0.0)
                     if isinstance(serving, dict) else 0.0),
           "unit": "deadline-met tokens/sec",
           "platform": jax.default_backend(),
           "serving": serving}
    apply_ratchet(doc, harness="serving")
    print(json.dumps(doc))


def _traffic_only() -> bool:
    """``bench.py traffic`` — run just the multi-tenant SLO traffic-replay
    scenario (fifo vs sched on one seeded bursty trace) and emit a
    traffic-only JSON line (both harnesses honour it)."""
    return "traffic" in sys.argv[1:]


def _emit_traffic_only(smoke: bool) -> None:
    import jax
    traffic = run_leg("traffic", bench_traffic, smoke=smoke)
    doc = {"metric": "traffic_goodput_under_slo",
           "value": (traffic.get("goodput_under_slo", 0.0)
                     if isinstance(traffic, dict) else 0.0),
           "unit": "SLO-met tokens/sec (sched leg)",
           "platform": jax.default_backend(),
           "traffic": traffic}
    apply_ratchet(doc, harness="traffic")
    print(json.dumps(doc))


def _elastic_only() -> bool:
    """``bench.py elastic`` — run just the live-resize + zero-drop-handoff
    scenario and emit an elastic-only JSON line (both harnesses honour
    it)."""
    return "elastic" in sys.argv[1:]


def _observability_only() -> bool:
    """``bench.py observability`` — run just the telemetry-overhead +
    exporter-scrape scenario and emit an observability-only JSON line."""
    return "observability" in sys.argv[1:]


def _emit_observability_only(smoke: bool) -> None:
    import jax
    obs = run_leg("observability", bench_observability, smoke=smoke)
    doc = {"metric": "telemetry_overhead_frac",
           "value": (obs.get("overhead_frac", 1.0)
                     if isinstance(obs, dict) else 1.0),
           "unit": "traced/off step-time delta (lower is better)",
           "platform": jax.default_backend(),
           "observability": obs}
    apply_ratchet(doc, harness="observability")
    print(json.dumps(doc))


def _emit_elastic_only(smoke: bool) -> None:
    import jax
    elastic = run_leg("elastic", bench_elastic, smoke=smoke)
    ok = (isinstance(elastic, dict)
          and elastic.get("steps_lost") == 0
          and elastic.get("params_match_cold_resume")
          and elastic.get("serving", {}).get("requests_dropped") == 0)
    doc = {"metric": "elastic_zero_loss_resize",
           "value": 1.0 if ok else 0.0,
           "unit": "steps_lost==0 and requests_dropped==0",
           "platform": jax.default_backend(),
           "elastic": elastic}
    print(json.dumps(doc))


def bench_sanitizer(smoke: bool = False):
    """One sanitized leg per scenario (``--sanitize``): the LeNet fused-step
    train loop, the checkpoint manager, and the device-feed input pipeline
    re-run under ``MXTPU_SANITIZE=transfers,donation,retrace,threads``, with
    ``profiler.get_sanitizer_stats()`` as the source of truth. Reports the
    sanitizer's step overhead against an unsanitized twin leg and the
    violation count — the contract (docs/static_analysis.md) is zero on the
    committed tree. Runs inside the cpu-fallback harness too, so the tier-1
    bench guard can assert the sanitized leg stays exit-0."""
    from mxtpu import nd, profiler
    from mxtpu.analysis import sanitize
    from mxtpu.io import DataBatch

    batch, steps = 32, (4 if smoke else 20)
    rs = np.random.RandomState(7)
    x = nd.array(rs.rand(batch, 1, 28, 28).astype(np.float32))
    y = nd.array(rs.randint(0, 10, batch).astype(np.float32))
    b = DataBatch(data=[x], label=[y])

    def train_leg() -> float:
        mod = _lenet_module(batch)
        mod.forward_backward(b)     # compile outside the timed window
        mod.update()
        t0 = time.perf_counter()
        for _ in range(steps):
            mod.forward_backward(b)
            mod.update()
        float(mod._loss_val.mean().data)        # sync
        return (time.perf_counter() - t0) * 1e3 / steps

    plain_ms = train_leg()
    profiler.reset_sanitizer_stats()
    t0 = time.perf_counter()
    with sanitize.scope("transfers,donation,retrace,threads"):
        sanitized_ms = train_leg()
        ckpt = bench_checkpoint(iters=1 if smoke else 2)
        pipe = bench_input_pipeline(steps=4 if smoke else 16)
        # sanitizers + tracing must compose (the transfer guard wraps the
        # same dispatch the span annotates): one TRACED leg inside the
        # sanitized scope, counted into the same zero-violations contract
        from mxtpu.observability import tracer as _tracer
        from mxtpu.observability import export as _export
        was_on = _tracer.enabled()
        _tracer.start()
        try:
            traced_ms = train_leg()
        finally:
            if not was_on:
                _tracer.stop()
        traced_events = sum(len(evs) for _, _, evs, _
                            in _tracer.snapshot_buffers())
        traced_cats = sorted({e.get("cat", "") for e
                              in _export.collect_events()
                              if e.get("ph") in ("X", "C")})
        if not was_on:
            _tracer.reset()
    stats = profiler.get_sanitizer_stats()
    violations = profiler.sanitizer_violations(stats)
    out = {
        "modes": ["transfers", "donation", "retrace", "threads"],
        "scenarios": ["train", "checkpoint", "input_pipeline", "traced"],
        "step_ms_plain": round(plain_ms, 3),
        "step_ms_sanitized": round(sanitized_ms, 3),
        "overhead_frac": round(sanitized_ms / max(plain_ms, 1e-9) - 1.0, 4),
        "violations": violations,
        "stats": stats,
        "wall_s": round(time.perf_counter() - t0, 2),
        "checkpoint": {"async_blocked_frac": ckpt["async_blocked_frac"]},
        "input_pipeline": {"feed_stall_frac":
                           pipe["device_feed"]["stall_frac"]},
        "traced_leg": {"step_ms": round(traced_ms, 3),
                       "events": traced_events,
                       "span_categories": traced_cats},
    }
    log(f"[sanitizer] step {plain_ms:.2f} -> {sanitized_ms:.2f} ms "
        f"({out['overhead_frac']*100:+.1f}%), "
        f"guards={stats['transfer_guards']} "
        f"poisons={stats['donation_poisons_armed']} "
        f"ownership={stats['ownership_checks']} -> "
        f"violations={violations}")
    return out


def bench_analysis(smoke: bool = False):
    """Static-analysis leg: wall-clock for the two tier-1 gates.  (a) tpulint
    over the three committed trees (``mxtpu tests bench.py`` — the same
    invocation ``tests/test_analysis_guard.py`` guards) in-process via
    ``lint_paths``, with per-rule finding counts; (b) the jaxpr-level program
    auditor as a subprocess (``--audit --format json`` — it bootstraps its
    own 8-virtual-device re-exec), with finding and program counts.  Both
    counts are contract-zero on the committed tree, so the leg doubles as a
    scoreboard-visible drift alarm; the timings tell us when the gates get
    slow enough to hurt the edit loop."""
    import subprocess
    from mxtpu.analysis import lint_paths

    repo = os.path.dirname(os.path.abspath(__file__))
    trees = [os.path.join(repo, "mxtpu"), os.path.join(repo, "tests"),
             os.path.join(repo, "bench.py")]
    t0 = time.perf_counter()
    findings = lint_paths(trees)
    lint_s = time.perf_counter() - t0
    rule_counts: dict = {}
    for f in findings:
        rule_counts[f.rule] = rule_counts.get(f.rule, 0) + 1

    t0 = time.perf_counter()
    p = subprocess.run(
        [sys.executable, "-m", "mxtpu.analysis", "--audit",
         "--format", "json"],
        cwd=repo, capture_output=True, text=True, timeout=600)
    audit_s = time.perf_counter() - t0
    audit = {"rc": p.returncode, "findings": None, "programs": None}
    try:
        doc = json.loads(p.stdout)
        audit["findings"] = len(doc.get("findings", []))
        audit["programs"] = len(doc.get("report", {}).get("programs", {}))
        audit["counts"] = doc.get("counts", {})
    except ValueError:
        audit["stderr"] = p.stderr[-500:]

    out = {
        "lint": {"trees": ["mxtpu", "tests", "bench.py"],
                 "wall_s": round(lint_s, 3),
                 "findings": len(findings),
                 "counts": rule_counts},
        "audit": {"wall_s": round(audit_s, 2), **audit},
    }
    log(f"[analysis] lint {len(findings)} finding(s) in {lint_s:.2f}s, "
        f"audit rc={p.returncode} {audit.get('findings')} finding(s) over "
        f"{audit.get('programs')} program(s) in {audit_s:.1f}s")
    return out


def _fallback_train_leg(smoke: bool) -> dict:
    """The fallback harness's train leg: a LeNet loop through the fused
    StepExecutor, measured three ways — a sync-per-step latency distribution
    (p50/p99 via the observability step ring), a pipelined throughput run,
    and the MFU roll-up from the compiled program's FLOP estimate."""
    from mxtpu import nd
    from mxtpu.io import DataBatch
    from mxtpu.observability import flops as flops_mod

    batch, steps = 32, (4 if smoke else 20)
    rs = np.random.RandomState(0)
    x = nd.array(rs.rand(batch, 1, 28, 28).astype(np.float32))
    y = nd.array(rs.randint(0, 10, batch).astype(np.float32))
    mod = _lenet_module(batch)
    b = DataBatch(data=[x], label=[y])
    mod.forward_backward(b)       # compile + first step
    mod.update()
    loss_start = float(mod._loss_val.mean().data)

    # per-step latency distribution (each sample host-synced on the loss)
    flops_mod.reset_steps()
    for _ in range(3 if smoke else 8):
        t1 = time.perf_counter()
        mod.forward_backward(b)
        mod.update()
        float(mod._loss_val.mean().data)
        flops_mod.record_step(time.perf_counter() - t1)

    # pipelined throughput (one final readback syncs the chain)
    t0 = time.perf_counter()
    for _ in range(steps):
        mod.forward_backward(b)
        mod.update()
    loss_end = float(mod._loss_val.mean().data)
    dt = time.perf_counter() - t0
    img_s = steps * batch / dt

    pflops = mod._program_flops()
    mstats = flops_mod.get_mfu_stats(flops_per_step=pflops)
    steps_per_sec = round(steps / dt, 3)
    mfu = None
    if pflops and mstats["peak_tflops"]:
        # throughput-based MFU (the pipelined run, not the synced samples)
        mfu = round((pflops * steps / dt) / (mstats["peak_tflops"] * 1e12), 6)
    return {
        "module": mod,
        "img_s": round(img_s, 1),
        "loss_start": round(loss_start, 3),
        "loss_end": round(loss_end, 3),
        "mfu": {"mfu": mfu,
                "steps_per_sec": steps_per_sec,
                "p50_step_ms": mstats["p50_step_ms"],
                "p99_step_ms": mstats["p99_step_ms"],
                "flops_per_step": pflops,
                "device_kind": mstats["device_kind"],
                "peak_tflops": mstats["peak_tflops"],
                "source": "lenet_fused_step"},
    }


def bench_resilience(smoke: bool = False):
    """Resilience scenario (ISSUE 8): the same LeNet fit run twice — once
    fault-free, once under ``MXTPU_FAULT_PLAN`` with an injected checkpoint
    writer ``io_error`` (absorbed by the shared ``retry_transient`` policy)
    plus a mid-epoch ``crash`` on the first attempt (survived by
    ``resilience.supervise`` restarting from the last committed step).
    Reports the restart/retry/steps-lost accounting from
    ``profiler.get_resilience_stats()`` and whether the supervised run's
    final params match the fault-free baseline — the end-to-end proof that
    fault → retry → restart → resume loses no training state."""
    import shutil
    import tempfile

    from mxtpu import callback, profiler
    from mxtpu.checkpoint import CheckpointManager
    from mxtpu.io import NDArrayIter
    from mxtpu.resilience import faults, supervise

    batch = 32
    nbatch = 4 if smoke else 8
    epochs = 2 if smoke else 3
    rs = np.random.RandomState(11)
    X = rs.rand(nbatch * batch, 1, 28, 28).astype(np.float32)
    y = rs.randint(0, 10, nbatch * batch).astype(np.float32)

    def _params_np(mod):
        # positional (construction-order) list, not name-keyed: gluon name
        # counters are process-global, so a re-instantiated LeNet gets fresh
        # conv2dN_* names — restore matches positionally and so must we
        arg, aux = mod.get_params()
        return [np.asarray(v.data)
                for v in list(arg.values()) + list(aux.values())]

    def _fit(save_dir):
        # One manager drives BOTH the epoch-end saves and the resume —
        # resume_from on a fresh directory is a no-op, so baseline and
        # every supervised attempt share this exact code path. Seeding makes
        # every attempt's fresh init identical; a restore overrides both the
        # params and the RNG stream from the committed snapshot.
        import mxtpu as mx
        mx.rng.seed(20260804)
        it = NDArrayIter(X, y, batch_size=batch, shuffle=False)
        mod = _lenet_module(batch, setup=False)
        mgr = CheckpointManager(save_dir)
        try:
            mod.fit(it, num_epoch=epochs, optimizer="sgd",
                    optimizer_params={"learning_rate": 0.05,
                                      "momentum": 0.9},
                    epoch_end_callback=callback.do_checkpoint(
                        mgr, module=mod),
                    resume_from=mgr)
            mgr.wait_until_finished()
        finally:
            mgr.close()
        return _params_np(mod)

    root = tempfile.mkdtemp(prefix="mxtpu-bench-resil-")
    saved = {k: os.environ.get(k)
             for k in (faults.ENV_PLAN, faults.ENV_ATTEMPT)}
    crash_at = nbatch + 2          # two steps into the second epoch
    plan = (f"site=ckpt.write:at=1:kind=io_error,"
            f"site=step:at={crash_at}:kind=crash:attempt=1")
    t0 = time.perf_counter()
    try:
        base = _fit(os.path.join(root, "baseline"))
        profiler.reset_resilience_stats()
        faults.reset_fault_plan()
        os.environ[faults.ENV_PLAN] = plan
        faulted_dir = os.path.join(root, "faulted")
        res = supervise(lambda ctx: _fit(faulted_dir),
                        directory=faulted_dir, mode="inline")
        params = res.result
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        faults.reset_fault_plan()
        shutil.rmtree(root, ignore_errors=True)

    diffs = [float(np.max(np.abs(p - b))) if p.size else 0.0
             for p, b in zip(params, base)]
    max_diff = max(diffs) if diffs else 0.0
    match = (len(params) == len(base)
             and all(p.shape == b.shape for p, b in zip(params, base))
             and all(np.allclose(p, b, rtol=1e-5, atol=1e-6)
                     for p, b in zip(params, base)))
    stats = profiler.get_resilience_stats()
    out = {
        "fault_plan": plan,
        "nbatch": nbatch,
        "epochs": epochs,
        "attempts": res.attempts,
        "restarts": res.restarts,
        "steps_lost": res.steps_lost,
        "restart_latency_ms": stats["restart_latency_ms_last"],
        "retries": stats["retries"],
        "faults_injected": stats["faults_injected"],
        "params_match": bool(match),
        "max_abs_param_diff": max_diff,
        "wall_s": round(time.perf_counter() - t0, 2),
    }
    log(f"[resilience] {res.attempts} attempts ({res.restarts} restarts, "
        f"~{res.steps_lost} steps lost, last restart "
        f"{stats['restart_latency_ms_last']:.0f} ms), "
        f"{stats['retries']} retries / {stats['faults_injected']} faults "
        f"-> params_match={match} (max diff {max_diff:.2e})")
    if not match:
        raise AssertionError(
            f"supervised resume diverged from fault-free baseline "
            f"(max param diff {max_diff:.3e})")
    return out


def bench_elastic(smoke: bool = False):
    """Live-elasticity scenario (ISSUE 11), both halves of the contract:

    * **training** — one ZeRO fit live-shrinks dp N→N/2 mid-epoch via
      ``resilience.ElasticRun`` (no restart). Reports the in-place resize
      latency and proves ``steps_lost == 0`` (every step boundary visited
      exactly once) plus bit-exactness with a cold checkpoint-resume taken
      at the resize boundary on the survivor mesh;
    * **serving** — mid-flight requests survive a
      ``ServingEngine.drain()``/``adopt()`` handoff onto a second engine
      with ``requests_dropped == 0`` and greedy decode bit-exact vs solo
      ``generate``.
    """
    import shutil
    import tempfile

    import jax

    import mxtpu as mx
    from mxtpu import nd, parallel, profiler
    from mxtpu.checkpoint import CheckpointManager
    from mxtpu.gluon import nn
    from mxtpu.gluon.model_zoo import transformer_lm
    from mxtpu.io import NDArrayIter
    from mxtpu.resilience import ElasticRun
    from mxtpu.serving import ServingEngine

    ndev = len(jax.devices())
    from_dp, to_dp = ndev, max(1, ndev // 2)
    epochs, nbatch, batch = 2, 4, 16
    hidden = 32 if smoke else 128
    rs = np.random.RandomState(11)
    X = rs.randn(nbatch * batch, 10).astype(np.float32)
    y = rs.randint(0, 3, nbatch * batch).astype(np.float32)

    def _net():
        mx.rng.seed(11)
        net = nn.HybridSequential()
        net.add(nn.Dense(hidden, activation="tanh", in_units=10),
                nn.Dense(3, in_units=hidden))
        net.initialize(init=mx.initializer.Xavier())
        return net

    def _params(mod):
        arg, aux = mod.get_params()
        return [np.asarray(v.data)
                for v in list(arg.values()) + list(aux.values())]

    fit_kw = dict(num_epoch=epochs, kvstore="device", optimizer="sgd",
                  optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
                  eval_metric="ce")

    def _live(save_dir):
        """ElasticRun fit: commit a checkpoint at (0, 1) — the cold-resume
        anchor — then live-shrink at the SAME step boundary."""
        parallel.set_default_mesh(parallel.make_mesh((from_dp,), ("dp",)))
        mod = mx.Module(_net(), data_names=("data",),
                        label_names=("softmax_label",))
        mgr = CheckpointManager(save_dir)
        er = ElasticRun(mod)
        seen = set()

        def _cb(param):
            seen.add((param.epoch, param.nbatch))
            if (param.epoch, param.nbatch) == (0, 1):
                mgr.save(step=1, module=mod,
                         trainer=getattr(mod, "_trainer", None),
                         epoch=param.epoch, nbatch=param.nbatch,
                         blocking=True)
                er.request_resize(to_dp)
        try:
            it = NDArrayIter(X, y, batch_size=batch, shuffle=False)
            er.fit(it, batch_end_callback=_cb, **fit_kw)
            mgr.wait_until_finished()
        finally:
            mgr.close()
            parallel.set_default_mesh(None)
        return _params(mod), er, seen

    def _cold(save_dir):
        parallel.set_default_mesh(parallel.make_mesh((to_dp,), ("dp",)))
        mod = mx.Module(_net(), data_names=("data",),
                        label_names=("softmax_label",))
        try:
            it = NDArrayIter(X, y, batch_size=batch, shuffle=False)
            mod.fit(it, resume_from=save_dir, **fit_kw)
        finally:
            parallel.set_default_mesh(None)
        return _params(mod)

    root = tempfile.mkdtemp(prefix="mxtpu-bench-elastic-")
    zprev = os.environ.get("MXTPU_ZERO")
    t0 = time.perf_counter()
    try:
        os.environ["MXTPU_ZERO"] = "1"
        profiler.reset_resilience_stats()
        live, er, seen = _live(root)
        cold = _cold(root)
    finally:
        if zprev is None:
            os.environ.pop("MXTPU_ZERO", None)
        else:
            os.environ["MXTPU_ZERO"] = zprev
        shutil.rmtree(root, ignore_errors=True)
    steps_lost = epochs * nbatch - len(seen)
    match = (len(live) == len(cold)
             and all(a.shape == b.shape and np.array_equal(a, b)
                     for a, b in zip(live, cold)))
    rstats = profiler.get_resilience_stats()

    # -- serving half: drain two decoding slots + one queued request, adopt
    # them on a fresh engine, and read every result back bit-exact
    mx.rng.seed(0)
    vocab = 50
    net = transformer_lm("tiny", vocab_size=vocab)
    net.initialize()
    srs = np.random.RandomState(7)
    trace = [(srs.randint(1, vocab, size=n).tolist(), new)
             for n, new in [(3, 96), (17, 80), (9, 112)]]
    refs = [np.asarray(net.generate(
        nd.array(np.array([p], np.int32)), m).data)[0, len(p):].tolist()
        for p, m in trace]
    profiler.reset_serving_stats()
    eng = ServingEngine(net, slots=2, queue_depth=8, chunk=4).start()
    reqs = [eng.submit(p, m) for p, m in trace]
    tw = time.monotonic()
    while profiler.get_serving_stats()["prefills"] < 2:
        if time.monotonic() - tw > 120:
            raise AssertionError("serving prefill never happened")
        time.sleep(0.02)
    td = time.perf_counter()
    handoff = eng.drain()
    drain_ms = (time.perf_counter() - td) * 1e3
    eng2 = ServingEngine(net, slots=2, queue_depth=8, chunk=4)
    eng2.adopt(handoff)
    outs = [r.result(timeout=300) for r in reqs]
    eng2.stop()
    sstats = profiler.get_serving_stats()
    dropped = sstats["cancelled"] + sstats["expired"]
    decode_match = outs == refs

    out = {
        "from_dp": from_dp,
        "to_dp": to_dp,
        "resizes": er.resizes,
        "resize_latency_ms": rstats["resize_latency_ms_last"],
        "steps_lost": steps_lost,
        "restart_fallbacks": rstats["restart_fallbacks"],
        "params_match_cold_resume": bool(match),
        "serving": {
            "in_flight": handoff.in_flight,
            "drained": sstats["drained"],
            "adopted": sstats["adopted"],
            "requests_dropped": dropped,
            "drain_ms": drain_ms,
            "decode_match": bool(decode_match),
        },
        "wall_s": round(time.perf_counter() - t0, 2),
    }
    log(f"[elastic] live dp{from_dp}->dp{to_dp} in "
        f"{rstats['resize_latency_ms_last']:.1f} ms, steps lost "
        f"{steps_lost}, cold-resume match={match}; serving handoff "
        f"{sstats['drained']} drained/{sstats['adopted']} adopted, "
        f"{dropped} dropped in {drain_ms:.1f} ms, match={decode_match}")
    if er.resizes != 1 or steps_lost != 0 or not match:
        raise AssertionError(f"live resize contract violated: {out}")
    if dropped != 0 or not decode_match:
        raise AssertionError(f"zero-drop handoff contract violated: {out}")
    return out


def bench_cpu_fallback():
    """The explicit CPU harness (``MXTPU_BENCH_FALLBACK=1`` or
    ``JAX_PLATFORMS=cpu`` — never entered because a chip failed to come up).
    Emits the single-line JSON with ``"fallback": "cpu"``: a
    LeNet-scale training loop through the Module API — which also exercises
    the fused StepExecutor path — sized to finish in seconds on one core.
    Every leg runs under :func:`run_leg` crash containment (transient
    backend errors retried with backoff, ``{"error": ...}`` otherwise), so a single bad
    scenario can never erase the scoreboard again. ``MXTPU_BENCH_SMOKE=1``
    shrinks every leg's iteration counts (same code paths, same JSON keys)
    so the tier-1 bench guard can run this harness as a fast regression
    test."""
    import jax
    from mxtpu import profiler

    smoke = os.environ.get("MXTPU_BENCH_SMOKE") == "1"
    if _resilience_only():
        _emit_resilience_only(smoke)
        return
    if _serving_only():
        _emit_serving_only(smoke)
        return
    if _traffic_only():
        _emit_traffic_only(smoke)
        return
    if _elastic_only():
        _emit_elastic_only(smoke)
        return
    if _quant_only():
        _emit_quant_only(smoke)
        return
    if _observability_only():
        _emit_observability_only(smoke)
        return
    train = run_leg("train", _fallback_train_leg, smoke)
    mod = train.pop("module", None) if isinstance(train, dict) else None
    # the checkpoint + input-pipeline + zero_dp + trace scenarios reuse the
    # cpu backend — the fallback path must keep emitting the same keys as
    # the full harness
    ckpt = run_leg("checkpoint", bench_checkpoint, module=mod,
                   iters=2 if smoke else 5)
    pipe = run_leg("input_pipeline", bench_input_pipeline,
                   steps=8 if smoke else 48)
    zdp = run_leg("zero_dp", bench_zero_dp, steps=4 if smoke else 16,
                  hidden=128 if smoke else 512)
    fsdp = run_leg("fsdp", bench_fsdp, steps=4 if smoke else 12,
                   hidden=128 if smoke else 512)
    resil = run_leg("resilience", bench_resilience, smoke=smoke)
    serving = run_leg("serving", bench_serving, smoke=smoke)
    traffic = run_leg("traffic", bench_traffic, smoke=smoke)
    elastic = run_leg("elastic", bench_elastic, smoke=smoke)
    quant = run_leg("quant", bench_quant, smoke=smoke)
    lctx = run_leg("long_context", bench_long_context, smoke=smoke)
    trace = run_leg("trace", bench_trace)
    obs = run_leg("observability", bench_observability, smoke=smoke)
    analysis = run_leg("analysis", bench_analysis, smoke=smoke)
    san = run_leg("sanitizer", bench_sanitizer, smoke=smoke) \
        if _sanitize_requested() else None
    caches = profiler.get_compile_stats()
    if _leg_ok(train):
        log(f"[cpu-fallback] lenet b32: {train['img_s']:.0f} img/s, loss "
            f"{train['loss_start']:.3f} -> {train['loss_end']:.3f}, "
            f"step traces={caches.get('module_step', {}).get('traces')}")
    doc = {
        "metric": "lenet_train_imgs_per_sec",
        "value": train.get("img_s", 0.0) if isinstance(train, dict) else 0.0,
        "unit": "images/sec",
        "fallback": "cpu",
        "platform": jax.default_backend(),
        "loss_start": train.get("loss_start"),
        "loss_end": train.get("loss_end"),
        "mfu": train.get("mfu", {"error": "train leg failed"}),
        "checkpoint": ckpt,
        "input_pipeline": pipe,
        "zero_dp": zdp,
        "fsdp": fsdp,
        "resilience": resil,
        "serving": serving,
        "traffic": traffic,
        "elastic": elastic,
        "quant": quant,
        "long_context": lctx,
        "trace": trace,
        "observability": obs,
        "analysis": analysis,
        "compile_caches": caches,
    }
    if not _leg_ok(train):
        doc["error_train"] = train.get("error") if isinstance(train, dict) \
            else str(train)
    if san is not None:
        doc["sanitizer"] = san
    apply_ratchet(doc, harness="cpu-fallback")
    print(json.dumps(doc))


def main():
    import jax
    from mxtpu import compile_cache
    compile_cache.place()
    # a backend that does not come up is an error (jax raises here, rc != 0):
    # there is no re-exec onto the CPU — the CPU harness below runs only
    # where it was asked for (MXTPU_BENCH_FALLBACK=1 / JAX_PLATFORMS=cpu)
    jax.devices()
    if _comm_only():
        # comm-only runs on ANY backend: single-device/cpu hosts get the
        # 8-way virtual mesh inside _emit_comm_only
        _emit_comm_only()
        return
    if os.environ.get("MXTPU_BENCH_FALLBACK") == "1" \
            or jax.default_backend() == "cpu":
        bench_cpu_fallback()
        return
    if _resilience_only():
        _emit_resilience_only(os.environ.get("MXTPU_BENCH_SMOKE") == "1")
        return
    if _serving_only():
        _emit_serving_only(os.environ.get("MXTPU_BENCH_SMOKE") == "1")
        return
    if _traffic_only():
        _emit_traffic_only(os.environ.get("MXTPU_BENCH_SMOKE") == "1")
        return
    if _elastic_only():
        _emit_elastic_only(os.environ.get("MXTPU_BENCH_SMOKE") == "1")
        return
    if _quant_only():
        _emit_quant_only(os.environ.get("MXTPU_BENCH_SMOKE") == "1")
        return
    if _observability_only():
        _emit_observability_only(os.environ.get("MXTPU_BENCH_SMOKE") == "1")
        return
    # every scenario runs under run_leg crash containment: retries with
    # backoff on transient backend errors (UNAVAILABLE / init failures), an
    # {"error": ...} leg entry otherwise — the scoreboard always ships
    train = {}
    for cfg in TRAIN_CONFIGS:
        train[cfg[0]] = run_leg(f"train_{cfg[0]}", bench_train, *cfg)
    bf16 = train.get("bf16_b128", {})
    e2e = run_leg("train_e2e", bench_train_e2e,
                  bf16.get("step_ms") if isinstance(bf16, dict) else None)
    tlm = run_leg("transformer_lm", bench_transformer_lm)
    tlm_wide = run_leg("transformer_lm_wide", bench_transformer_lm,
                       preset="wide")
    mfus = [m.get("mfu") for m in (tlm, tlm_wide)
            if _leg_ok(m) and m.get("mfu") is not None]
    tlm = {"flagship": tlm, "wide": tlm_wide,
           "best_mfu": max(mfus) if mfus else None}
    lm = run_leg("word_lm", bench_word_lm)
    score = run_leg("inference", bench_inference)
    attn = run_leg("attention", bench_attention)
    pipe = run_leg("pipeline", bench_pipeline)
    i8 = run_leg("int8", bench_int8)
    comm = run_leg("comm", bench_comm)
    ckpt = run_leg("checkpoint", bench_checkpoint)
    feed_pipe = run_leg("input_pipeline", bench_input_pipeline)
    zdp = run_leg("zero_dp", bench_zero_dp)
    fsdp = run_leg("fsdp", bench_fsdp)
    resil = run_leg("resilience", bench_resilience)
    serving = run_leg("serving", bench_serving)
    traffic = run_leg("traffic", bench_traffic)
    elastic = run_leg("elastic", bench_elastic)
    quant = run_leg("quant", bench_quant)
    lctx = run_leg("long_context", bench_long_context)
    trace = run_leg("trace", bench_trace)
    obs = run_leg("observability", bench_observability)
    analysis = run_leg("analysis", bench_analysis)
    san = run_leg("sanitizer", bench_sanitizer) \
        if _sanitize_requested() else None

    ok_train = {t: r for t, r in train.items() if _leg_ok(r)}
    if ok_train:
        best_tag = max(ok_train, key=lambda t: ok_train[t]["img_s"])
        best = ok_train[best_tag]
    else:
        best_tag, best = None, {}
    doc = {
        "metric": "resnet50_train_imgs_per_sec",
        "value": best.get("img_s", 0.0),
        "unit": "images/sec",
        "vs_baseline": round(best.get("img_s", 0.0) / BASELINE_IMG_S, 3),
        "config": best_tag,
        "mfu": best.get("mfu"),
        "mfu_stats": {"mfu": best.get("mfu"),
                      "steps_per_sec": best.get("steps_per_sec"),
                      "p50_step_ms": best.get("p50_step_ms"),
                      "p99_step_ms": best.get("p99_step_ms"),
                      "source": f"train_{best_tag}" if best_tag else None,
                      "best_transformer_mfu": tlm["best_mfu"]},
        "train": train,
        "train_e2e": e2e,
        "transformer_lm": tlm,
        "word_lm": lm,
        "inference_img_s": score,
        "attention_ms": attn,
        "pipeline_img_s": pipe,
        "int8": i8,
        "comm": comm,
        "checkpoint": ckpt,
        "input_pipeline": feed_pipe,
        "zero_dp": zdp,
        "fsdp": fsdp,
        "resilience": resil,
        "serving": serving,
        "traffic": traffic,
        "elastic": elastic,
        "quant": quant,
        "long_context": lctx,
        "trace": trace,
        "observability": obs,
        "analysis": analysis,
        "compile_caches": _compile_caches(),
    }
    if san is not None:
        doc["sanitizer"] = san
    apply_ratchet(doc, harness="accelerator")
    print(json.dumps(doc))


def _compile_caches():
    """Framework compile-cache counters (profiler.get_compile_stats): the
    retrace-leak early-warning for every whole-step cache in the run."""
    try:
        from mxtpu import profiler
        return profiler.get_compile_stats()
    except Exception:
        return {}


if __name__ == "__main__":
    main()
