"""Job kind ``serve_open_loop``: requests sent on a schedule whether or not
earlier ones have finished, as independent users send them, at the fixed rate
the traffic file names. One thread sends what is due and watches every live
request's token count, so each request is timed on the benchmark's own clock
from when it was DUE, and generator lateness is reported.

The window is ``--seconds`` of arrivals; the run then waits until every
request sent has finished (the tails are tails of all requests), and
``serve_tokens_per_s`` counts the tokens delivered inside the window.
"""

from __future__ import annotations

import os
import random
import time

import numpy as np

import check
import stats
import system
import traffic as traffic_mod
import weights as weights_mod
from manifest import load_module

POLL_S = 0.001          # how often the client thread looks at live requests
PROFILE_CAP_S = 2.0     # the profiler is on for no more of a window than this
DRAIN_LIMIT_S = 90.0    # a request not finished this long after the window
                        # closed has failed


def bucket32(n: int, cap: int) -> int:
    return min(cap, -(-n // 32) * 32)


def drive(engine, requests: list, seconds: float, say) -> dict:
    """Send ``requests`` (sorted by ``t``) on schedule and watch them.
    Returns per-request records and the window's counts."""
    recs = [{"due": r["t"], "prompt": r["prompt"], "max_new": r["max_new"],
             "sent": None, "first": None, "last": None, "n": 0,
             "handle": None, "failed": False} for r in requests]
    live, nxt, in_window = [], 0, None
    # the longest time in which requests were live and none got a token: a
    # stall of the engine or the device shows here, whatever the tails say
    last_token, silence, silence_at = 0.0, 0.0, 0.0
    t0 = time.perf_counter()
    while nxt < len(recs) or live:
        now = time.perf_counter() - t0
        while nxt < len(recs) and recs[nxt]["due"] <= now:
            rec = recs[nxt]
            nxt += 1
            rec["sent"] = now
            try:
                rec["handle"] = engine.submit(rec["prompt"], rec["max_new"])
                live.append(rec)
            except Exception as e:     # a refusal is a failed request
                rec["failed"] = True
                say(f"request refused: {type(e).__name__}: {e}")
        now = time.perf_counter() - t0
        still, progressed = [], not live
        for rec in live:
            h = rec["handle"]
            done = h.done()
            n = len(h.tokens())
            if n > rec["n"]:
                if rec["first"] is None:
                    rec["first"] = now
                rec["n"], rec["last"] = n, now
                progressed = True
            if done:
                rec["failed"] = h.state != "done" or n != rec["max_new"]
            else:
                still.append(rec)
        live = still
        if progressed:
            last_token = now
        elif now - last_token > silence:
            silence, silence_at = now - last_token, last_token
        if in_window is None and now >= seconds:
            in_window = sum(r["n"] for r in recs)
        if now > seconds + DRAIN_LIMIT_S:
            for rec in live:
                rec["failed"] = True
            say(f"{len(live)} requests unfinished {DRAIN_LIMIT_S:.0f}s after "
                f"the window closed: failed")
            break
        time.sleep(POLL_S)
    total_s = time.perf_counter() - t0
    if in_window is None:
        in_window = sum(r["n"] for r in recs)
    return {"records": recs, "tokens_in_window": in_window,
            "drain_s": max(0.0, total_s - seconds), "total_s": total_s,
            "silence_s": silence, "silence_at_s": silence_at}


def summarise(out: dict, seconds: float, say) -> dict:
    recs = out["records"]
    ok = [r for r in recs if not r["failed"] and r["first"] is not None]
    late = [r["sent"] - r["due"] for r in recs if r["sent"] is not None]
    ttft = [(r["first"] - r["due"]) * 1e3 for r in ok]
    tpot = [(r["last"] - r["first"]) / (r["n"] - 1) * 1e3
            for r in ok if r["n"] > 1]
    failed = len(recs) - len(ok)
    say(f"requests: {len(recs)} sent, {len(ok)} finished, {failed} failed; "
        f"generator lateness p50 {stats.median(late) * 1e3:.3f}ms max "
        f"{max(late) * 1e3:.3f}ms; drained {out['drain_s']:.2f}s after the "
        f"window; longest time with live requests and no token "
        f"{out['silence_s'] * 1e3:.0f}ms, from {out['silence_at_s']:.2f}s")
    e2e = {}
    if ttft and tpot:
        # the 90th percentile: of the window's 158 requests it has 16 beyond
        # it, the 95th only 8 (ten is the least a tail should stand on)
        e2e = {"ttft_p90_ms": stats.percentile(ttft, 90),
               "tpot_p90_ms": stats.percentile(tpot, 90),
               "serve_tokens_per_s": out["tokens_in_window"] / seconds}
        say(f"ttft ms: p50 {stats.median(ttft):.2f} p90 "
            f"{e2e['ttft_p90_ms']:.2f} p95 {stats.percentile(ttft, 95):.2f} "
            f"max {max(ttft):.2f} (n={len(ttft)}); tpot ms: p50 "
            f"{stats.median(tpot):.3f} p90 {e2e['tpot_p90_ms']:.3f} p95 "
            f"{stats.percentile(tpot, 95):.3f} (n={len(tpot)}); tokens in "
            f"window {out['tokens_in_window']}")
    return {"attempted": len(recs), "failed": failed, "end_to_end": e2e}


def warm_up(engine, traffic: dict, cfg: dict, seed: int, run) -> None:
    """Every shape the traffic mix can draw, and no other, whatever the
    seed paired with what: first one request of the largest total the mix
    allows, so that the cache is allocated once at its top bucket (it only
    grows), then one request for each 32-token prompt bucket between the
    mix's shortest and longest prompt (which runs every chunk size of that
    bucket). Prompts are fresh random tokens, so nothing of them is found in
    the prefix cache later. Each request is let go after its first decode
    turn."""
    cap = cfg["n_positions"]
    rng = random.Random(f"{seed}|warm-up")
    pt, ot = traffic["prompt_tokens"], traffic["output_tokens"]
    buckets = list(range(bucket32(pt["min"], cap),
                         bucket32(pt["max"], cap) + 1, 32))
    plan = [(pt["max"], min(ot["max"], cap - pt["max"]))] + \
        [(pb, 40) for pb in buckets]
    for plen, max_new in plan:
        before, t = run.counter.snapshot(), time.perf_counter()
        prompt = [rng.randrange(1, cfg["vocab_size"]) for _ in range(plen)]
        h = engine.submit(prompt, max_new)
        want = min(max_new, bucket32(plen, cap) - plen + 9)
        while len(h.tokens()) < want and not h.done():
            if time.perf_counter() - t > 600:
                raise SystemExit("benchmark: a warm-up request hung")
            time.sleep(0.005)
        h.cancel()
        while not h.done():
            time.sleep(0.002)
        d = run.counter.delta(run.counter.snapshot(), before)
        run.say(f"warm-up prompt {plen} + {max_new}: "
                f"{time.perf_counter() - t:.2f}s, compiled {d['compiled']} "
                f"in {d['compile_s']}s, cache hits {d['cache_hits']}")


def served_sample(recs: list, seed: int, n: int) -> list:
    """``n`` finished requests drawn from the seed, the longest among them."""
    done = [r for r in recs if not r["failed"] and r["n"] == r["max_new"]]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r["prompt"]) + r["n"])
    rest = [r for r in done if r is not longest]
    random.Random(f"{seed}|sample").shuffle(rest)
    return [longest] + rest[:n - 1]


def run(run) -> dict:
    import jax
    import jax.numpy as jnp

    cell, cfg, spec = run.cell, run.cell.config, run.cell.spec
    job = spec["job_params"]
    gpt2 = load_module(os.path.join(cell.suite, "reference", "gpt2.py"),
                       "suite_reference_gpt2")

    with run.phase("inputs"):
        requests = traffic_mod.requests(cell.traffic, run.seed, run.seconds,
                                        cfg["vocab_size"])
    with run.phase("weights"):
        weights = weights_mod.make_weights(cfg, run.seed, job["dtype"])
        jax.block_until_ready(weights)
    with run.phase("build"):
        net = system.build_net(cfg, weights, job["dtype"])
        engine = system.make_engine(net, job["engine"])
        engine.start()
    with run.phase("warm-up"):
        warm_up(engine, cell.traffic, cfg, run.seed, run)
        system.reset_serving_stats()
        run.note_memory("after warm-up")

    if run.trace:
        system.record_program_spans(True)
    run.window_opens()
    if run.trace:
        # the profiler covers the first seconds of steady load, not the ramp
        out = drive_traced(run, engine, requests)
    else:
        out = drive(engine, requests, run.seconds, run.say)
    run.window_closes()
    if run.trace:
        system.record_program_spans(False)
    stats_now = system.serving_stats()
    engine.stop()
    summary = summarise(out, run.seconds, run.say)
    run.say(f"engine: kv promotions in the window "
            f"{stats_now.get('kv_promotions')}, prefix hits "
            f"{stats_now.get('prefix_hits')}, kv bytes resident "
            f"{stats_now.get('kv_bytes_resident')}")

    run.keep_memory_peak()
    with run.phase("reference"):
        del engine, net
        sample = served_sample(out["records"], run.seed, job["checked_requests"])
        w = jax.jit(gpt2.stack_layers)(weights)
        # the weights ride as an argument: closed over, they would be a
        # constant of the program, compiled for a minute on every run
        fwd = jax.jit(lambda w, toks: gpt2.forward(cfg, w, toks))
        ref_fwd = lambda toks: np.asarray(fwd(w, jnp.asarray(toks)))
        numbers = check.serving_numbers(sample, ref_fwd, cfg["n_positions"])
        if run.control:
            low = jax.jit(lambda w, toks: gpt2.forward(
                cfg, w, toks, check.CONTROL_PRECISION))
            check.judge(check.serving_numbers(
                sample, ref_fwd, cfg["n_positions"],
                lambda toks: np.asarray(low(w, jnp.asarray(toks)))),
                spec["limits"], run.say, what="control")
    correct = bool(sample) and check.judge(numbers, spec["limits"], run.say) \
        and summary["failed"] == 0

    obs = {"records": [dict({k: r[k] for k in ("due", "sent", "first", "last",
                                               "n")},
                            prompt_len=len(r["prompt"]))
                       for r in out["records"]],
           "window_s": run.seconds, "chips": cell.chips,
           "engine_args": job["engine"], "dtype": job["dtype"]}
    if "profiled" in out:
        obs["profiled"] = out["profiled"]
    return {"correct": correct, "attempted": summary["attempted"],
            "failed": summary["failed"], "end_to_end": summary["end_to_end"],
            "observations": obs}


def drive_traced(run, engine, requests: list) -> dict:
    """As :func:`drive`, with ``jax.profiler`` on for ``profile_s`` seconds
    from ``profile_at_s`` into the window; host spans run throughout."""
    import threading
    job = run.cell.spec["job_params"]
    at, span = job["profile_at_s"], min(job["profile_s"], PROFILE_CAP_S)
    if at + span > run.seconds:
        at = max(0.0, run.seconds - span)

    def profile():
        time.sleep(at)
        with run.profile():
            time.sleep(span)

    th = threading.Thread(target=profile, name="bench-profile")
    th.start()
    try:
        return dict(drive(engine, requests, run.seconds, run.say),
                    profiled=(at, span))
    finally:
        th.join()
