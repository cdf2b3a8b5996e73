"""Tier-1 guards for the mxtpu.sched SLO control plane (ISSUE 17).

Policy side (no engine, no jax): stride fair share cannot be starved by a
flooding tenant, latency tiers admit strictly by rank, a COLD scheduler
never sheds while a warm one sheds exactly the doomed request, and the
preemption victim table matches the tier spec. Autoscaler side: the
dry-run decision table against synthetic histograms — breach streaks,
cooldown dead time, asymmetric scale-down, and the never-actuate
contract. Engine side (tiny transformer, CPU): preempt → park → resume is
BIT-EXACT vs solo ``generate`` (the paged-KV block plus cursors IS the
decode chain), two saturated tenants interleave instead of running FIFO,
and a deadline the rates prove unmeetable sheds before it is missed.
"""

import itertools
import time

import numpy as np
import pytest

import mxtpu as mx
from mxtpu import nd, profiler
from mxtpu.sched import replay
from mxtpu.sched.autoscale import AutoscalePolicy, Autoscaler
from mxtpu.sched.policy import (DEFAULT_TIERS, SLOPolicy, SLOScheduler,
                                TierSpec)
from mxtpu.serving import ShedError

VOCAB = 50


# ---------------------------------------------------------------------------
# policy: fake requests (the scheduler touches no engine internals)
# ---------------------------------------------------------------------------

_ids = itertools.count(1)


class _Req:
    def __init__(self, tenant="a", priority="standard", t_submit=0.0,
                 prompt_len=8, max_new=8, deadline=None):
        self.id = next(_ids)
        self.tenant = tenant
        self.priority = priority
        self.t_submit = t_submit
        self.prompt = [1] * prompt_len
        self.max_new = max_new
        self.total = prompt_len + max_new
        self.deadline = deadline


def _drain_order(sched, pending, now=10.0):
    """Run select()+charge() to exhaustion; returns the pick order (no
    shedding expected — asserts none happened)."""
    order = []
    pending = list(pending)
    while pending:
        choice, shed = sched.select(pending, now)
        assert shed == []
        assert choice is not None
        sched.charge(choice)
        order.append(choice)
        pending.remove(choice)
    return order


def test_select_without_charge_is_stateless():
    """A saturated engine re-selects every scheduler turn until a slot
    frees; only charge() advances fair-share state, so repeated selection
    must be idempotent — charging on selection would inflate the waiting
    tenant's pass exactly when contention makes fairness matter."""
    sched = SLOScheduler()
    a = _Req(tenant="a", t_submit=0.0)
    b = _Req(tenant="b", t_submit=1.0)
    c1, _ = sched.select([a, b], now=2.0)
    c2, _ = sched.select([a, b], now=2.0)
    assert c1 is c2 is a
    assert sched.stats()["picks"] == 0
    assert sched.stats()["tenants_seen"] == 0
    sched.charge(a)
    assert sched.stats()["picks"] == 1
    choice, _ = sched.select([b], now=2.0)
    assert choice is b


def test_fair_share_interleaves_instead_of_fifo():
    """A flooding tenant's backlog cannot serialize ahead of another
    tenant: stride passes alternate the two queues (plain FIFO would run
    all four flood requests first)."""
    sched = SLOScheduler()
    flood = [_Req(tenant="flood", t_submit=float(i)) for i in range(4)]
    light = [_Req(tenant="light", t_submit=0.5 + 2 * i) for i in range(2)]
    order = _drain_order(sched, flood + light)
    tenants = [r.tenant for r in order]
    assert tenants != ["flood"] * 4 + ["light"] * 2     # not FIFO
    # each light request is picked before the flood requests submitted
    # after it have all drained: last light pick is never last overall
    assert tenants.index("light") <= 1
    last_light = max(i for i, t in enumerate(tenants) if t == "light")
    assert last_light < len(tenants) - 1
    assert sched.stats()["picks"] == 6
    assert sched.stats()["tenants_seen"] == 2


def test_fair_share_weights_apportion_picks():
    """weight-2 tenant draws twice the picks of a weight-1 tenant under
    contention (pass advances by total/weight)."""
    pol = SLOPolicy(tenant_weights={"heavy": 2.0, "lite": 1.0})
    sched = SLOScheduler(pol)
    pending = ([_Req(tenant="heavy", t_submit=float(i)) for i in range(6)]
               + [_Req(tenant="lite", t_submit=0.5 + float(i))
                  for i in range(6)])
    order = _drain_order(sched, pending)
    first9 = [r.tenant for r in order[:9]]
    assert first9.count("heavy") == 6
    assert first9.count("lite") == 3


def test_tier_rank_admits_strictly_before_fair_share():
    """interactive > standard > batch regardless of submit order or
    accumulated stride passes."""
    sched = SLOScheduler()
    batch = _Req(priority="batch", t_submit=0.0)
    std = _Req(priority="standard", t_submit=1.0)
    inter = _Req(priority="interactive", t_submit=2.0)
    order = _drain_order(sched, [batch, std, inter])
    assert [r.priority for r in order] == ["interactive", "standard",
                                           "batch"]


def test_cold_scheduler_never_sheds():
    """No rate observations → no service estimate → an 'impossible'
    deadline is still admitted, never shed on a guess."""
    sched = SLOScheduler()
    doomed = _Req(max_new=10_000, deadline=10.001)   # 1 ms of budget
    choice, shed = sched.select([doomed], now=10.0)
    assert shed == [] and choice is doomed
    assert sched.estimate_service_s(doomed) is None
    assert sched.stats()["sheds"] == 0


def test_warm_scheduler_sheds_exactly_the_doomed_request():
    sched = SLOScheduler()
    sched.observe_prefill(100, 1.0)     # 10 ms / prefilled token
    sched.observe_decode(10, 1.0)       # 100 ms / generated token
    est = sched.estimate_service_s(_Req(prompt_len=8, max_new=100))
    assert est == pytest.approx(8 * 0.01 + 100 * 0.1)
    doomed = _Req(prompt_len=8, max_new=100, deadline=11.0)   # 1s budget
    fine = _Req(prompt_len=8, max_new=100, deadline=10.0 + 60.0)
    nodl = _Req(prompt_len=8, max_new=100)
    choice, shed = sched.select([doomed, fine, nodl], now=10.0)
    assert shed == [doomed]
    assert choice in (fine, nodl)
    err = sched.shed_error(doomed, now=10.0)
    assert isinstance(err, ShedError)
    assert str(doomed.id) in str(err) and "shed" in str(err)
    assert sched.stats()["sheds"] == 1


def test_shed_margin_is_applied():
    """margin 1.2 sheds a deadline the raw estimate would just meet."""
    sched = SLOScheduler()
    sched.observe_decode(1, 0.1)
    sched.observe_prefill(1, 0.0001)
    # est ~= 1.0008s; deadline budget 1.1s: raw fits, *1.2 margin does not
    r = _Req(prompt_len=8, max_new=10, deadline=1.1)
    choice, shed = sched.select([r], now=0.0)
    assert shed == [r] and choice is None


def test_pick_victim_decision_table():
    running_batch = _Req(priority="batch", t_submit=1.0)
    running_batch2 = _Req(priority="batch", t_submit=2.0)
    running_std = _Req(priority="standard", t_submit=0.0)
    running_inter = _Req(priority="interactive", t_submit=0.0)
    inter = _Req(priority="interactive", t_submit=5.0)
    std = _Req(priority="standard", t_submit=5.0)

    sched = SLOScheduler()
    # standard does not preempt
    assert sched.pick_victim([running_batch], std) is None
    # interactive cannot evict interactive (preemptible=False)
    assert sched.pick_victim([running_inter], inter) is None
    # lowest tier goes first, then the YOUNGEST (least sunk work)
    assert sched.pick_victim([running_std, running_batch], inter) \
        is running_batch
    assert sched.pick_victim([running_batch, running_batch2], inter) \
        is running_batch2
    # nobody below the incoming rank → None
    assert sched.pick_victim([], inter) is None
    # the global preemption gate wins over everything
    off = SLOScheduler(SLOPolicy(preemption=False))
    assert off.pick_victim([running_batch], inter) is None


def test_inflight_map_is_bounded_by_forget():
    """The R008 contract done right: register grows req.id -> tenant,
    forget pops it (idempotently) — nothing leaks per request."""
    sched = SLOScheduler()
    reqs = [_Req(tenant=f"t{i % 3}") for i in range(50)]
    for r in reqs:
        sched.register(r)
    assert sched.stats()["inflight"] == 50
    for r in reqs:
        sched.forget(r)
        sched.forget(r)           # idempotent
    assert sched.stats()["inflight"] == 0


def test_export_load_state_roundtrip():
    src = SLOScheduler()
    src.observe_prefill(10, 0.5)
    src.observe_decode(10, 1.0)
    src.charge(src.select([_Req(tenant="bulk")], now=0.0)[0])
    state = src.export_state()
    assert state["pass"]["bulk"] > 0
    dst = SLOScheduler()
    dst.load_state(state)
    assert dst.export_state() == state
    # the successor's estimator is warm: it can shed immediately
    assert dst.estimate_service_s(_Req(prompt_len=8, max_new=8)) \
        == pytest.approx(src.estimate_service_s(_Req(prompt_len=8,
                                                     max_new=8)))
    # loading an EMPTY state must not clobber warm EWMAs with None
    dst.load_state({"pass": {}, "ewma_decode_s": None,
                    "ewma_prefill_s": None})
    assert dst.estimate_service_s(_Req(prompt_len=8, max_new=8)) is not None


# ---------------------------------------------------------------------------
# autoscaler: dry-run decision table on synthetic histograms + a fake clock
# ---------------------------------------------------------------------------

BREACH = {"ttft_ms_p99": 400.0, "queue_wait_ms_p99": 20.0,
          "slot_occupancy": 0.6}
CALM = {"ttft_ms_p99": 50.0, "queue_wait_ms_p99": 5.0,
        "slot_occupancy": 0.1}


def _scaler(**kw):
    kw.setdefault("breach_ticks", 2)
    kw.setdefault("relax_ticks", 3)
    kw.setdefault("cooldown_s", 10.0)
    kw.setdefault("max_replicas", 4)
    return Autoscaler(AutoscalePolicy(**kw), dry_run=True)


def test_autoscaler_dry_run_scale_up_needs_consecutive_breaches():
    sc = _scaler()
    assert sc.step(BREACH, now=0.0)["action"] == "hold"     # streak 1
    d = sc.step(BREACH, now=1.0)                            # streak 2
    assert d["action"] == "scale_up" and d["target"] == 2
    assert d["dry_run"] is True and d["actuated"] is False  # never actuates
    assert "consecutive SLO breaches" in d["reason"]
    assert sc.replicas == 2


def test_autoscaler_interrupted_breach_streak_resets():
    sc = _scaler()
    sc.step(BREACH, now=0.0)
    sc.step({}, now=1.0)                     # no signal → streak resets
    d = sc.step(BREACH, now=2.0)
    assert d["action"] == "hold"             # back to streak 1
    assert d["reason"] == "breach"


def test_autoscaler_cooldown_suppresses_actions():
    sc = _scaler()
    sc.step(BREACH, now=0.0)
    assert sc.step(BREACH, now=1.0)["action"] == "scale_up"
    d = sc.step(BREACH, now=2.0)             # 9s of cooldown left
    assert d["action"] == "hold" and "cooldown" in d["reason"]
    d = sc.step(BREACH, now=3.0)
    assert d["action"] == "hold"
    # the streak kept accumulating through the dead time, so the first
    # post-cooldown tick fires immediately
    d = sc.step(BREACH, now=12.0)
    assert d["action"] == "scale_up" and d["target"] == 3


def test_autoscaler_scale_down_is_reluctant_and_floored():
    sc = _scaler()
    sc.replicas = 2
    for i in range(2):
        assert sc.step(CALM, now=float(i))["action"] == "hold"
    d = sc.step(CALM, now=2.0)               # relax_ticks = 3
    assert d["action"] == "scale_down" and d["target"] == 1
    # at min_replicas calm never goes below the floor
    for i in range(10):
        d = sc.step(CALM, now=20.0 + i)
    assert d["action"] == "hold" and sc.replicas == 1


def test_autoscaler_signal_extraction_and_breach_causes():
    sc = _scaler()
    # full collect_snapshot() documents and bare serving dicts both parse
    sig = sc.signals({"serving": BREACH})
    assert sig == {"ttft_p99_ms": 400.0, "queue_wait_p99_ms": 20.0,
                   "occupancy": 0.6}
    assert sc.signals(BREACH) == sig
    assert sc.signals({})["occupancy"] is None
    # each signal alone can breach; occupancy between the marks is no-signal
    assert sc._classify(sc.signals({"slot_occupancy": 0.95})) == "breach"
    assert sc._classify(sc.signals({"queue_wait_ms_p99": 500.0})) == "breach"
    assert sc._classify(sc.signals({"slot_occupancy": 0.5})) is None
    # calm needs POSITIVE occupancy headroom, not merely absent breach
    assert sc._classify(sc.signals({"ttft_ms_p99": 10.0})) is None
    assert sc._classify(sc.signals(CALM)) == "calm"


def test_autoscaler_actuates_elastic_without_stacking_resizes():
    class FakeElastic:
        def __init__(self):
            self.calls = []
            self.pending_resize = False

        def request_resize(self, n):
            self.calls.append(n)

    el = FakeElastic()
    spawned = []
    sc = Autoscaler(AutoscalePolicy(breach_ticks=1, cooldown_s=0.0,
                                    max_replicas=4),
                    elastic=el, respawn=spawned.append)
    d = sc.step(BREACH, now=0.0)
    assert d["action"] == "scale_up" and d["actuated"] is True
    assert el.calls == [2] and spawned == [2]
    # an unserved resize must not be stacked; respawn still actuates
    el.pending_resize = True
    d = sc.step(BREACH, now=1.0)
    assert d["action"] == "scale_up" and d["actuated"] is True
    assert el.calls == [2] and spawned == [2, 3]


# ---------------------------------------------------------------------------
# engine integration: park/resume bit-exactness, saturation fairness, shed
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def net():
    mx.rng.seed(0)
    from mxtpu.gluon.model_zoo import transformer_lm
    model = transformer_lm("tiny", vocab_size=VOCAB)
    model.initialize()
    return model


def _solo(model, prompt, max_new):
    out = model.generate(nd.array(np.array([prompt], np.int32)), max_new)
    return np.asarray(out.data)[0, len(prompt):].tolist()


def _spin(cond, what, timeout=300):
    t0 = time.monotonic()
    while not cond():
        assert time.monotonic() - t0 < timeout, f"{what} never happened"
        time.sleep(0.001)


def test_preempt_park_resume_is_bit_exact_vs_solo(net):
    """slots=1: an interactive arrival evicts the decoding batch request
    mid-stream; the batch request resumes after the interactive one
    retires and BOTH outputs equal uninterrupted solo ``generate`` — the
    parked page + (tok, p, limit) cursors are the whole decode chain."""
    from mxtpu.serving import ServingEngine
    profiler.reset_serving_stats()
    rs = np.random.RandomState(41)
    p_batch = rs.randint(1, VOCAB, size=11).tolist()
    p_inter = rs.randint(1, VOCAB, size=7).tolist()
    ref_b = _solo(net, p_batch, 48)
    ref_i = _solo(net, p_inter, 8)

    eng = ServingEngine(net, slots=1, queue_depth=8, chunk=4,
                        sched=True).start()
    rb = eng.submit(p_batch, 48, tenant="bulk", priority="batch")
    _spin(lambda: len(rb.tokens()) >= 4, "batch decode")   # mid-decode
    ri = eng.submit(p_inter, 8, tenant="chat", priority="interactive")
    assert ri.result(timeout=300) == ref_i
    assert rb.result(timeout=300) == ref_b                 # park survived
    eng.stop()
    stats = profiler.get_serving_stats()
    assert stats["preempted"] == 1 and stats["resumed"] == 1
    assert stats["completed"] == 2
    sstats = profiler.get_sched_stats()
    assert sstats["preemptions"] == 1 and sstats["resumes"] == 1
    assert sstats["inflight"] == 0          # both forgotten on retire
    # the tenant-keyed plane recorded the preemption where it happened
    assert stats["tenants"]["bulk"]["preempted"] == 1
    assert stats["tenants"]["chat"]["completed"] == 1


def test_two_tenant_saturation_interleaves_not_fifo(net):
    """slots=1, six standard-tier requests from two tenants: stride fair
    share interleaves the backlog (light's last request retires before
    flood's last), and every output stays bit-exact under the contention."""
    from mxtpu.serving import ServingEngine
    profiler.reset_serving_stats()
    rs = np.random.RandomState(43)
    mk = lambda: rs.randint(1, VOCAB, size=int(rs.randint(5, 14))).tolist()
    flood = [mk() for _ in range(4)]
    light = [mk() for _ in range(2)]
    refs = {id(p): _solo(net, p, 24) for p in flood + light}

    eng = ServingEngine(net, slots=1, queue_depth=8, chunk=4,
                        sched=True).start()
    # interleaved submit order: f0 l0 f1 f2 f3 l1 — a FIFO engine would
    # still finish them in this order; fair share must pull l1 ahead of f3
    rf, rl = [], []
    for p, bucket, tenant in ((flood[0], rf, "flood"), (light[0], rl,
                                                        "light"),
                              (flood[1], rf, "flood"), (flood[2], rf,
                                                        "flood"),
                              (flood[3], rf, "flood"), (light[1], rl,
                                                        "light")):
        bucket.append((p, eng.submit(p, 24, tenant=tenant)))
    for p, r in rf + rl:
        assert r.result(timeout=300) == refs[id(p)]
    eng.stop()
    assert max(r.t_done for _, r in rl) < max(r.t_done for _, r in rf)
    stats = profiler.get_serving_stats()
    assert stats["completed"] == 6
    assert stats["tenants"]["light"]["completed"] == 2
    assert stats["tenants"]["flood"]["completed"] == 4
    assert profiler.get_sched_stats()["picks"] == 6


def test_unmeetable_deadline_sheds_before_it_is_missed(net):
    """A warm scheduler rejects a request whose measured service rates
    prove the deadline unmeetable — promptly, with ShedError, long before
    the deadline itself; requests without deadlines ride along untouched."""
    from mxtpu.serving import ServingEngine
    profiler.reset_serving_stats()
    sched = SLOScheduler()
    # warm the estimator deterministically: 50 ms/decode token means a
    # 240-token request needs >= 12 s of slot time
    sched.observe_prefill(64, 0.064)
    sched.observe_decode(20, 1.0)
    rs = np.random.RandomState(47)
    prompt = rs.randint(1, VOCAB, size=9).tolist()
    ref = _solo(net, prompt, 8)

    eng = ServingEngine(net, slots=1, queue_depth=8, chunk=4,
                        sched=sched).start()
    doomed = eng.submit(prompt, 240, deadline_s=5.0, tenant="chat",
                        priority="interactive")
    t0 = time.monotonic()
    with pytest.raises(ShedError) as exc:
        doomed.result(timeout=300)
    assert time.monotonic() - t0 < 5.0       # shed BEFORE the deadline
    assert "shed" in str(exc.value) and "chat" in str(exc.value)
    ok = eng.submit(prompt, 8, tenant="chat")
    assert ok.result(timeout=300) == ref
    eng.stop()
    stats = profiler.get_serving_stats()
    assert stats["shed"] == 1 and stats["expired"] == 0
    assert stats["tenants"]["chat"]["shed"] == 1
    assert profiler.get_sched_stats()["sheds"] == 1


def test_scalar_prefill_warms_the_shed_estimator(net):
    """prefill_batch=1 sched engines feed observe_prefill from the scalar
    chunk path too — otherwise the estimator never warms and shedding is
    silently dead in the default configuration."""
    from mxtpu.serving import ServingEngine
    profiler.reset_serving_stats()
    sched = SLOScheduler()
    eng = ServingEngine(net, slots=1, queue_depth=8, chunk=4,
                        sched=sched).start()
    # total must overflow the 64-token admission bucket or the request
    # completes at admission and never exercises the decode estimator
    r = eng.submit([3, 1, 4, 1, 5], 68, tenant="warm")
    r.result(timeout=300)
    eng.stop()
    st = sched.stats()
    assert st["prefill_ms_per_token"] is not None \
        and st["prefill_ms_per_token"] > 0
    assert st["decode_ms_per_token"] is not None


# -- traffic replay traces (mxtpu.sched.replay) ------------------------------
# Until PR 28 only a leg of the deleted ``bench`` harness and the router guard
# built a trace; what a load test leans on is pinned here, a case per kind.


@pytest.mark.parametrize("kind", replay.KINDS)
def test_replay_trace_is_seeded_sorted_and_shares_tenant_prefixes(kind):
    tenants = (replay.TenantProfile("chat", priority="interactive",
                                    prefix_len=6, suffix_len=3, max_new=5,
                                    deadline_s=9.0),
               replay.TenantProfile("bulk", priority="batch", share=2.0,
                                    prefix_len=4, suffix_len=2, max_new=7))
    kw = dict(rate=20.0, duration_s=2.0, vocab=VOCAB, tenants=tenants)
    a = replay.make_trace(kind, seed=3, **kw)
    assert a == replay.make_trace(kind, seed=3, **kw)      # same seed, same trace
    assert a.requests != replay.make_trace(kind, seed=4, **kw).requests
    assert a.kind == kind and len(a) >= 4
    times = [r.t for r in a.requests]
    assert times == sorted(times) and 0.0 <= times[0] and times[-1] < 2.0
    by_name = {t.name: t for t in tenants}
    for r in a.requests:
        t = by_name[r.tenant]
        prefix = a.prefixes[r.tenant]
        assert len(prefix) == t.prefix_len
        assert r.prompt[:t.prefix_len] == prefix           # cache-hittable
        assert len(r.prompt) > t.prefix_len
        assert all(1 <= tok < VOCAB for tok in r.prompt)   # 0 is reserved
        assert r.priority == t.priority and r.deadline_s == t.deadline_s
        assert r.max_new >= 1
    assert {r.tenant for r in a.requests} == {"chat", "bulk"}
    with pytest.raises(ValueError, match="unknown trace kind"):
        replay.make_trace("square", seed=3)
