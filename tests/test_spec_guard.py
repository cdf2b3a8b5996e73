"""Speculative-decode guard (ISSUE 18): draft-and-verify decode must be
BIT-EXACT with the non-speculative engine (and with solo ``generate``) no
matter what the n-gram drafter proposes — across KV bucket promotions,
prefix-cache hits, int8 KV, greedy/sampled slot mixes, preemption
park/resume, and a drain/adopt landing between verify turns — while a
full run compiles at most ONE verify program per (slots, KV bucket, k).

The speedup side has no measurement on the chip yet (the benchmark has no
serving cell); here the stats contract is pinned structurally: drafted ==
accepted + rejected, the accept-length histogram mean exceeds 1.0 on
draftable (repetitive) streams, and a spec-less engine never dispatches a
verify program at all.

Engines are deliberately scarce (each owns fresh jit wrappers and pays
its own XLA compiles), so every test asserts several contracts at once.
"""

import threading
import time

import numpy as np
import pytest

import mxtpu as mx
from mxtpu import nd, profiler
from mxtpu.gluon.model_zoo import transformer_lm
from mxtpu.serving import (Drafter, ModelDrafter, SamplingParams,
                           ServingEngine, ServingHandoff, SpecConfig)

VOCAB = 50


@pytest.fixture(scope="module")
def net():
    mx.rng.seed(0)
    model = transformer_lm("tiny", vocab_size=VOCAB)
    model.initialize()
    return model


def _solo(model, prompt, max_new):
    out = model.generate(nd.array(np.array([prompt], np.int32)), max_new)
    return np.asarray(out.data)[0, len(prompt):].tolist()


def _rep_prompt(rs, period, n):
    """A prompt built from a repeated period — the shape the n-gram
    drafter is exact on, so accept lengths actually exercise > 1."""
    base = rs.randint(1, VOCAB, size=period).tolist()
    return (base * (n // period + 1))[:n]


def _verify_traces():
    return profiler.get_compile_stats().get("serving_verify",
                                            {}).get("traces", 0)


def test_spec_decode_bit_exact_across_buckets_trace_once(net):
    """The tentpole contract: spec-on greedy decode is bit-exact with solo
    ``generate`` while a mid-flight KV bucket promotion retraces the
    verify program exactly once per bucket — and a second same-shaped
    wave retraces NOTHING (mixed accept lengths ride data, not shape)."""
    profiler.reset_serving_stats()
    rs = np.random.RandomState(18)
    p1 = _rep_prompt(rs, 4, 13)      # total 53  -> decode bucket 64
    p2 = _rep_prompt(rs, 5, 9)       # total 109 -> promotes to bucket 128
    ref1, ref2 = _solo(net, p1, 40), _solo(net, p2, 100)
    base = _verify_traces()

    eng = ServingEngine(net, slots=2, queue_depth=8, chunk=4,
                        spec=SpecConfig(k=4)).start()
    r1 = eng.submit(p1, 40)
    t0 = time.monotonic()
    while not r1.tokens():                    # decoding in bucket 64
        assert time.monotonic() - t0 < 300, "decode never started"
        time.sleep(0.001)
    r2 = eng.submit(p2, 100)                  # joins mid-flight, promotes
    assert r1.result(timeout=300) == ref1
    assert r2.result(timeout=300) == ref2
    wave1 = _verify_traces() - base
    assert 1 <= wave1 <= 2                    # at most one per KV bucket

    # same shapes again: every verify dispatch is a cache hit
    r3 = eng.submit(p1, 40)
    r4 = eng.submit(p2, 100)
    assert r3.result(timeout=300) == ref1
    assert r4.result(timeout=300) == ref2
    stats = profiler.get_serving_stats()
    eng.stop()
    assert _verify_traces() - base == wave1   # zero new traces

    # stats contract: speculation engaged and the ledger balances
    assert stats["spec_dispatches"] > 0
    assert stats["tokens_drafted"] > 0
    assert stats["tokens_accepted"] + stats["tokens_rejected"] \
        == stats["tokens_drafted"]
    assert stats["accept_len_mean"] > 1.0     # drafts actually landed
    assert stats["accept_len_count"] > 0


def test_spec_default_off_is_byte_identical_and_verify_free(net):
    """Without ``spec=`` the engine must be the PR 10 engine byte-for-byte:
    no draft buffers, no verify program ever built, no spec counters."""
    profiler.reset_serving_stats()
    rs = np.random.RandomState(21)
    prompt = _rep_prompt(rs, 3, 11)
    ref = _solo(net, prompt, 40)
    base = _verify_traces()
    with ServingEngine(net, slots=2, queue_depth=8, chunk=4) as eng:
        assert eng._spec is None
        assert eng.submit(prompt, 40).result(timeout=300) == ref
        stats = profiler.get_serving_stats()
    assert _verify_traces() == base
    assert stats["spec_dispatches"] == 0
    assert stats["tokens_drafted"] == 0 and stats["accept_len_count"] == 0


def test_spec_greedy_sampled_mix_degrades_sampled_slot_only(net):
    """A sampled request sharing the batch with a greedy one degrades to
    per-slot plain decode (dlen = 0) WITHOUT retracing: its stream must
    equal the non-spec engine's deterministic (seed, position) stream,
    the greedy neighbour must equal solo, and both engines together
    compile at most one verify program."""
    profiler.reset_serving_stats()
    rs = np.random.RandomState(23)
    p_greedy = _rep_prompt(rs, 4, 12)
    p_sampled = rs.randint(1, VOCAB, size=10).tolist()
    sampling = SamplingParams(temperature=0.8, top_k=5, seed=7)
    ref_g = _solo(net, p_greedy, 40)

    with ServingEngine(net, slots=2, queue_depth=8, chunk=4) as plain:
        ref_s = plain.submit(p_sampled, 40,
                             sampling=sampling).result(timeout=300)

    base = _verify_traces()
    with ServingEngine(net, slots=2, queue_depth=8, chunk=4,
                       spec=SpecConfig(k=4)) as eng:
        rg = eng.submit(p_greedy, 40)
        rsamp = eng.submit(p_sampled, 40, sampling=sampling)
        assert rg.result(timeout=300) == ref_g
        assert rsamp.result(timeout=300) == ref_s
        stats = profiler.get_serving_stats()
    assert _verify_traces() - base <= 1
    # every drafted token belongs to the greedy slot; the ledger balances
    assert stats["tokens_accepted"] + stats["tokens_rejected"] \
        == stats["tokens_drafted"]


def test_spec_int8_kv_and_prefix_hit_stay_greedy_exact(net):
    """Quantized KV under speculation: per-row int8 scales are written and
    rolled back congruently with the data rows (a rejection leaves garbage
    that the next dispatch overwrites before anything attends it), and a
    radix prefix-cache hit feeds both the KV reuse AND the drafter's
    n-gram side index — all of it greedy-exact vs solo."""
    profiler.reset_serving_stats()
    rs = np.random.RandomState(27)
    pfx = _rep_prompt(rs, 6, 40)              # > 1 cache block
    p_random = rs.randint(1, VOCAB, size=9).tolist()   # drafts mostly wrong
    ref_pfx = _solo(net, pfx, 40)
    ref_rand = _solo(net, p_random, 40)

    with ServingEngine(net, slots=2, queue_depth=8, chunk=4,
                       quant="int8_kv", prefix_cache_mb=1.0,
                       spec=SpecConfig(k=4)) as eng:
        assert eng.submit(pfx, 40).result(timeout=300) == ref_pfx
        hit = eng.submit(pfx, 40)             # radix hit + tree n-grams
        rand = eng.submit(p_random, 40)       # rejection/rollback exercise
        assert hit.result(timeout=300) == ref_pfx
        assert rand.result(timeout=300) == ref_rand
        stats = profiler.get_serving_stats()
    assert stats["kv_dtype"] == "int8"
    assert stats["prefix_hits"] >= 1
    assert stats["spec_dispatches"] > 0
    assert stats["ngram_hits"] + stats["ngram_misses"] > 0


def test_spec_park_resume_preemption_bit_exact(net):
    """SLO preemption under speculation: the parked slot's in-flight draft
    rides the park entry and is restored on resume — both the preempted
    batch request and the interactive preemptor finish bit-exact, and
    fair share billed accepted tokens (pass advances past the prompt)."""
    profiler.reset_serving_stats()
    rs = np.random.RandomState(29)
    p_batch = _rep_prompt(rs, 4, 11)
    p_inter = _rep_prompt(rs, 5, 7)
    ref_b = _solo(net, p_batch, 48)
    ref_i = _solo(net, p_inter, 40)

    eng = ServingEngine(net, slots=1, queue_depth=8, chunk=4, sched=True,
                        spec=SpecConfig(k=4)).start()
    rb = eng.submit(p_batch, 48, tenant="bulk", priority="batch")
    t0 = time.monotonic()
    while len(rb.tokens()) < 24:              # mid-decode, past the bucket
        assert time.monotonic() - t0 < 300, "batch decode never started"
        time.sleep(0.001)
    ri = eng.submit(p_inter, 40, tenant="chat", priority="interactive")
    assert ri.result(timeout=300) == ref_i
    assert rb.result(timeout=300) == ref_b    # park + resume, bit-exact
    stats = profiler.get_serving_stats()
    passes = eng._sched.export_state()["pass"]
    eng.stop()
    assert stats["preempted"] >= 1 and stats["resumed"] >= 1
    assert stats["spec_dispatches"] > 0
    # charge_tokens billed the decode stream, not one unit per turn:
    # bulk's pass covers its prompt plus every delivered token
    assert passes["bulk"] >= len(p_batch) + 48


class _GatedOracle(Drafter):
    """Proposes the reference continuation, so EVERY turn ends with a live
    draft, and parks the scheduler thread inside the proposal that ends the
    first turn ``gate`` or more tokens deep until ``release`` is set: the
    request can neither finish nor start another turn before the test has
    called ``drain()``."""

    def __init__(self, n_prompt, ref, gate):
        self._n_prompt, self._ref, self._gate = n_prompt, ref, gate
        self.reached = threading.Event()
        self.release = threading.Event()

    def propose(self, context, k):
        g = len(context) - self._n_prompt
        if g >= self._gate and not self.reached.is_set():
            self.reached.set()
            self.release.wait(timeout=60)
        return self._ref[g:g + k]


class _NoDraft(Drafter):
    def propose(self, context, k):
        return []


def test_spec_drain_adopt_mid_verify_and_specless_refusal(net):
    """Elastic handoff between verify turns: the handoff carries the spec
    schema ({'k'}) and each slot's un-verified draft, a spec-less
    successor REFUSES it (mirror of the parked-slots rule), and a spec
    successor resumes bit-exact — the draft proposed on the old engine is
    verified on the new one.

    What ``drain()`` guarantees: it stops the scheduler BETWEEN turns, and a
    turn ends with ``_propose_drafts``, so an entry's ``dlen`` is whatever
    the drafter proposed for the stream as it then stood: a live draft when
    it proposed one, 0 when it missed. This test used to poll with
    ``time.sleep(0.001)`` for 24 tokens under the default n-gram drafter
    and assert ``dlen > 0``; on jax / jaxlib 0.9.0 the tiny model's greedy
    stream stands at a tail with no earlier occurrence (``... 36, 4``) at
    the turn the poll lands on, the drafter misses, and ``dlen`` was 0 on
    every run. A draft CAN be in flight, so the state is reached by
    construction instead: ``_GatedOracle`` always proposes and holds the
    scheduler inside the proposal until ``drain()`` is under way."""
    profiler.reset_serving_stats()
    rs = np.random.RandomState(31)
    prompt = _rep_prompt(rs, 4, 13)
    ref = _solo(net, prompt, 60)

    drafter = _GatedOracle(len(prompt), ref, gate=24)
    eng = ServingEngine(net, slots=2, queue_depth=8, chunk=4,
                        spec=SpecConfig(k=4, drafter=drafter)).start()
    req = eng.submit(prompt, 60)
    assert drafter.reached.wait(timeout=300), "decode never got 24 deep"
    # the timer only opens the gate once drain() has asked the loop to
    # stop; were it early, the loop would run further turns, each of which
    # ends with a live draft too: no assertion below rests on the 0.2 s
    threading.Timer(0.2, drafter.release.set).start()
    handoff = eng.drain()
    assert handoff.spec == {"k": 4}
    assert handoff.in_flight == 1
    entry = handoff.entries[0]
    done = len(req.tokens())
    assert 24 <= done < 60
    assert entry["dlen"] == 4                 # genuine in-flight draft
    assert entry["draft"] == ref[done:done + 4]

    # spec-less successor refuses BEFORE touching any state, so the same
    # handoff still adopts cleanly afterwards
    bare = ServingEngine(net, slots=2, queue_depth=8, chunk=4)
    with pytest.raises(ValueError, match="draft"):
        bare.adopt(handoff)

    # the successor proposes nothing of its own, so whatever it accepts is
    # the draft that rode the handoff
    before = profiler.get_serving_stats()
    eng2 = ServingEngine(net, slots=2, queue_depth=8, chunk=4,
                         spec=SpecConfig(k=4, drafter=_NoDraft()))
    eng2.adopt(handoff)
    assert req.result(timeout=300) == ref     # hop mid-verify, bit-exact
    eng2.stop()
    stats = profiler.get_serving_stats()
    assert stats["tokens_accepted"] - before["tokens_accepted"] == 4
    assert stats["spec_dispatches"] - before["spec_dispatches"] == 1
    assert stats["drained"] == 1 and stats["adopted"] == 1
    assert stats["cancelled"] == 0 and stats["expired"] == 0
    assert stats["accept_len_mean"] > 1.0


def test_model_drafter_self_draft_bit_exact(net):
    """The draft-LM seam (``ModelDrafter``) under the same advisory
    contract: the target drafts for itself from a context cut to the
    drafter's buckets, so some proposals are wrong and are rejected, and
    the stream is still bit-exact with solo; the drafter's own counters
    agree with the serving stats' ledger. (Until PR 28 only a leg of the
    deleted ``bench`` harness drove this class.)"""
    profiler.reset_serving_stats()
    rs = np.random.RandomState(37)
    prompt = rs.randint(1, VOCAB, size=11).tolist()   # no n-gram to lean on
    ref = _solo(net, prompt, 60)
    drafter = ModelDrafter(net)
    with ServingEngine(net, slots=2, queue_depth=8, chunk=4,
                       spec=SpecConfig(k=4, drafter=drafter)) as eng:
        assert eng.submit(prompt, 60).result(timeout=300) == ref
        stats = profiler.get_serving_stats()
    d = drafter.stats()
    assert d["draft_lm_calls"] > 0
    assert d["draft_lm_tokens"] >= stats["tokens_drafted"] > 0
    assert stats["tokens_accepted"] > 0 and stats["spec_dispatches"] > 0
    assert stats["tokens_accepted"] + stats["tokens_rejected"] \
        == stats["tokens_drafted"]
