"""The jaxpr-level program auditor (``python -m mxtpu.analysis --audit``).

tpulint (``lint.py``) reads source; the auditor reads PROGRAMS.  It builds
the framework's canonical compiled programs — the fused training step
(``step_cache.StepExecutor``), the ZeRO bucketed update
(``parallel/zero.py``), and the serving decode/verify/prefill family
(``serving/kv.py``), including the sharded fsdp×tp decode — abstractly, on
a virtual 8-device CPU mesh, and statically verifies the invariants the
incident history says drift silently:

* **shardcheck** (A101/A102/A103/A104) — the SpecLayout/ServingLayout
  tables against the mesh and the canonical parameter geometry: an axis a
  spec names must exist (A101), a sharded probe dim must divide cleanly
  instead of silently degrading to replicated (A102), ``compose_spec`` may
  only ever insert the fsdp axis on dim 0 — contraction-dim sharding
  reorders float reductions, the PR 8 ban (A103) — and the serving
  row-parallel pair must replicate, the PR 19 bit-exactness precondition
  (A104);
* **collective / transfer budgets** (A201/A202) — compiled-HLO collective
  counts against per-program budgets (the sharded decode compiles with
  ZERO all-reduce or greedy token parity is already gone; the ZeRO update
  must gather, never all-reduce) and a jaxpr walk proving no host
  callback/transfer primitive rides a hot program;
* **retrace closure** (A301) — the engine's ProgramCache key functions
  (``serving/engine.py::audit_key_specs``) evaluated over the whole
  admissible request domain: every key component must take a bounded set
  of values, so the program count is provably finite (the trace-once
  contract as a theorem instead of a counter assertion).

``--expect-fail`` seeds one violation per invariant class and requires its
detection — the auditor proves it can still see each failure mode, not
just that today's tree is clean.  Findings reuse :class:`lint.Finding`
with ``<audit:...>`` paths so ``--select``/``--ignore``/``--format json``
work unchanged.
"""

from __future__ import annotations

import os
import re
import sys
from typing import Dict, List, Optional, Sequence, Tuple

from .lint import Finding

# -- rule catalog -----------------------------------------------------------

_CATALOG = [
    ("A101", "spec-axis-missing",
     "layout spec names a mesh axis the audit mesh does not have"),
    ("A102", "spec-dim-indivisible",
     "sharded table dim does not divide by its mesh axes (silent degrade)"),
    ("A103", "contraction-dim-shard",
     "spec composition shards a contraction (non-0) dim — PR 8 ban"),
    ("A104", "row-parallel-not-replicated",
     "serving row-parallel pair must be P() for bit-exactness — PR 19"),
    ("A201", "collective-budget-exceeded",
     "compiled program's collective counts violate its budget"),
    ("A202", "host-transfer-in-program",
     "host callback/transfer primitive inside a compiled program"),
    ("A301", "open-program-key-set",
     "program-cache key component unbounded over the request domain"),
]


def rule_catalog():
    return list(_CATALOG)


# seed name -> (rule it must trip, which legs to run)
_SEEDS: List[Tuple[str, str, Tuple[str, ...]]] = [
    ("spec_axis", "A101", ("shardcheck",)),
    ("contraction_shard", "A103", ("shardcheck",)),
    ("row_parallel", "A104", ("shardcheck",)),
    ("extra_collective", "A201", ("serving",)),
    ("host_transfer", "A202", ("serving",)),
    ("open_keys", "A301", ("keys",)),
]

_MIN_DEVICES = 8
_LEGS = ("shardcheck", "serving", "zero", "fused_step", "keys")

# canonical audit geometry: tiny transformer with a DIVISIBLE vocab (the
# guard tests use vocab 50 to exercise filter_spec degradation; the audit
# wants the clean-shard case so A102 is meaningful), 4 slots on a (4, 2)
# fsdp×tp mesh
_VOCAB, _SLOTS, _TOT, _CHUNK, _K = 64, 4, 64, 4, 4
_MAX_LEN, _PREFILL_CHUNK = 256, 16


def _finding(program: str, rule: str, message: str) -> Finding:
    return Finding(f"<audit:{program}>", 0, 0, rule, message)


# -- jaxpr / HLO counters ---------------------------------------------------

# primitives that cross the device/host boundary inside a program
_CALLBACK_PRIMS = {"pure_callback", "io_callback", "debug_callback",
                   "infeed", "outfeed"}

_HLO_COLLECTIVE_RE = re.compile(
    r"=\s+\S+\s+(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(")


def jaxpr_prim_counts(jaxpr, counts: Optional[Dict[str, int]] = None):
    """Primitive histogram of a jaxpr, recursing into every sub-jaxpr
    (scan/while/cond bodies, custom_vjp branches, pjit calls)."""
    counts = counts if counts is not None else {}
    for eqn in jaxpr.eqns:
        counts[eqn.primitive.name] = counts.get(eqn.primitive.name, 0) + 1
        for v in eqn.params.values():
            _sub_counts(v, counts)
    return counts


def _sub_counts(v, counts):
    if hasattr(v, "eqns"):                      # open Jaxpr
        jaxpr_prim_counts(v, counts)
    elif hasattr(v, "jaxpr"):                   # ClosedJaxpr
        jaxpr_prim_counts(v.jaxpr, counts)
    elif isinstance(v, (list, tuple)):
        for e in v:
            _sub_counts(e, counts)


def hlo_collective_counts(hlo_text: str) -> Dict[str, int]:
    """Collective-op histogram of a compiled module's HLO text.  Async
    pairs count once (the ``-start`` carries the op; ``-done`` has no
    parenthesized operand list in the matched position)."""
    counts: Dict[str, int] = {}
    for op in _HLO_COLLECTIVE_RE.findall(hlo_text):
        counts[op] = counts.get(op, 0) + 1
    return counts


def _check_budget(findings, program: str, counts: Dict[str, int],
                  budget: Dict[str, Tuple[int, Optional[int]]],
                  why: str) -> None:
    for op, (lo, hi) in budget.items():
        n = counts.get(op, 0)
        if hi is not None and n > hi:
            findings.append(_finding(program, "A201", (
                f"collective-budget-exceeded: {program} compiles to {n} "
                f"{op} op(s), budget {hi} — {why}")))
        elif n < lo:
            findings.append(_finding(program, "A201", (
                f"collective-budget-exceeded: {program} compiles to {n} "
                f"{op} op(s), expected at least {lo} — {why}")))


def _check_transfers(findings, program: str,
                     counts: Dict[str, int]) -> None:
    hits = {p: n for p, n in counts.items() if p in _CALLBACK_PRIMS}
    for prim, n in sorted(hits.items()):
        findings.append(_finding(program, "A202", (
            f"host-transfer-in-program: {program} traces {n} '{prim}' "
            f"primitive(s) — every dispatch pays a device->host round trip "
            f"and stalls the device behind it; land results with the "
            f"program's returns, never a callback")))


# -- axis helpers -----------------------------------------------------------

def _axes_of(entry) -> set:
    if entry is None:
        return set()
    if isinstance(entry, (tuple, list)):
        return set(entry)
    return {entry}


def _pad_spec(spec, rank: int) -> list:
    entries = list(tuple(spec)) if spec is not None else []
    return entries + [None] * (rank - len(entries))


# -- leg 1: shardcheck ------------------------------------------------------

def _leg_shardcheck(findings, report, mesh, seed: Optional[str]) -> None:
    from jax.sharding import PartitionSpec as P
    from ..parallel import fsdp
    from ..serving import sharded

    serving_layout = sharded.ServingLayout()
    if seed == "spec_axis":
        serving_layout = sharded.ServingLayout(tp_axis="model")
    elif seed == "row_parallel":
        class _RowParallelSeed(sharded.ServingLayout):
            def attn_out(self):
                return P(None, self.tp_axis)
        serving_layout = _RowParallelSeed()

    mesh_axes = {str(a) for a in mesh.axis_names}
    checked = 0
    for label, layout in (("SpecLayout", fsdp.SpecLayout()),
                          ("ServingLayout", serving_layout)):
        for role, shape, spec in fsdp.audit_spec_table(layout):
            checked += 1
            entries = _pad_spec(spec, len(shape))
            for d, entry in enumerate(entries):
                for ax in sorted(_axes_of(entry)):
                    if ax not in mesh_axes:
                        findings.append(_finding("shardcheck", "A101", (
                            f"spec-axis-missing: {label}.{role} dim {d} "
                            f"names mesh axis '{ax}' but the mesh only has "
                            f"{sorted(mesh_axes)} — the spec can never "
                            f"apply; every leaf silently replicates")))
                        continue
                axes = [a for a in _axes_of(entry) if a in mesh_axes]
                if not axes:
                    continue
                degree = 1
                for ax in axes:
                    degree *= int(mesh.shape[ax])
                if shape[d] % degree != 0:
                    findings.append(_finding("shardcheck", "A102", (
                        f"spec-dim-indivisible: {label}.{role} shards dim "
                        f"{d} (size {shape[d]}) over {tuple(axes)} (degree "
                        f"{degree}) but {shape[d]} % {degree} != 0 — "
                        f"filter_spec degrades this leaf to replicated on "
                        f"the canonical geometry, a silent 1/{degree} "
                        f"memory and bandwidth loss")))

        # A104: the bit-exactness precondition only binds serving layouts
        if isinstance(layout, sharded.ServingLayout):
            for entry_name, spec in sharded.audit_layout_invariants(layout):
                findings.append(_finding("shardcheck", "A104", (
                    f"row-parallel-not-replicated: {label}.{entry_name}() "
                    f"is {spec}, must be P() — sharding a row-parallel "
                    f"contraction dim turns the matmul into per-device "
                    f"partial sums + psum, reordering the float reduction "
                    f"and breaking greedy token parity with solo generate "
                    f"(PR 19)")))

    # A103: compose_spec may only insert the fsdp axis on dim 0
    compose = fsdp.compose_spec
    if seed == "contraction_shard":
        ax, n = fsdp.fsdp_axis_name(mesh), fsdp.fsdp_size(mesh)

        def compose(shape, base, mesh_):
            if len(shape) >= 2 and shape[1] % n == 0:
                entries = _pad_spec(base, len(shape))
                if entries[1] is None:
                    entries[1] = ax
                    return P(*entries)
            return fsdp.compose_spec(shape, base, mesh_)

    for role, shape, base in fsdp.audit_spec_table(fsdp.SpecLayout()):
        if len(shape) < 2 or role == "kv_cache":
            continue
        composed = compose(shape, base, mesh)
        if composed is None:
            continue
        base_entries = _pad_spec(base, len(shape))
        comp_entries = _pad_spec(composed, len(shape))
        for d in range(1, len(shape)):
            added = _axes_of(comp_entries[d]) - _axes_of(base_entries[d])
            if added:
                findings.append(_finding("shardcheck", "A103", (
                    f"contraction-dim-shard: composing {role} {shape} adds "
                    f"axis {sorted(added)} on dim {d} — only dim 0 (the "
                    f"output dim) may take the fsdp axis; sharding a "
                    f"contraction dim makes XLA compute partial sums + "
                    f"psum, changing the reduction order that stages 1/2 "
                    f"bit-parity depends on (PR 8)")))
    report["legs"].append({"leg": "shardcheck", "rows": checked})


# -- leg 2: serving programs (trace + sharded compile) ----------------------

def _audit_model():
    import numpy as np
    import mxtpu as mx
    from .. import autograd
    from ..gluon.model_zoo.transformer import transformer_lm
    from ..ndarray.ndarray import NDArray
    mx.rng.seed(0)
    model = transformer_lm("tiny", vocab_size=_VOCAB)
    model.initialize()
    # one (1, 1) forward completes the deferred shapes (the engine's
    # _materialize_params does the same before its first dispatch)
    with autograd.predict_mode():
        model(NDArray(np.zeros((1, 1), np.int32)))
    return model


def _leg_serving(findings, report, mesh, seed: Optional[str]) -> None:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ..serving import kv, sharded

    model = _audit_model()
    programs = kv.audit_programs(model, _SLOTS, _TOT, _CHUNK, _K)

    for name, fn, args in programs:
        traced_fn = fn
        if seed == "host_transfer" and name == "serving_decode":
            base = fn

            def traced_fn(*a):
                out = base(*a)
                jax.debug.callback(lambda x: None, out[3])
                return out

        jaxpr = jax.make_jaxpr(traced_fn)(*args)
        counts = jaxpr_prim_counts(jaxpr.jaxpr)
        _check_transfers(findings, name, counts)
        report["programs"][name] = {
            "eqns": sum(counts.values()),
            "callbacks": sum(counts.get(p, 0) for p in _CALLBACK_PRIMS),
        }

    # sharded fsdp×tp decode: compile on the virtual mesh — under
    # layout_scope, exactly as the engine's dispatch traces — and hold the
    # compiled module to its collective budget.  The canonical geometry
    # compiles with exactly TWO all-reduces, both order-exact integer/max
    # reductions (the one-hot embedding lookup over the vocab-sharded
    # table sums exact zeros; the greedy argmax over vocab shards is an
    # associative max).  Any all-reduce beyond those is a float-dot
    # partial-sum psum — a sharded row-parallel contraction — which
    # reorders the reduction and breaks greedy token parity with solo
    # generate (PR 19).
    from ..parallel import fsdp
    layout = sharded.ServingLayout()
    if seed == "extra_collective":
        class _RowParallelSeed(sharded.ServingLayout):
            def attn_out(self):
                return P(None, self.tp_axis)
        layout = _RowParallelSeed()

    # a FRESH decode builder: jax.jit caches its traced jaxpr by avals, so
    # the instance make_jaxpr traced above would hand the scoped lower its
    # unscoped trace (no activation constraints) and the budget would
    # measure the wrong program
    fn = kv.build_decode(model, _SLOTS, _TOT, _CHUNK)
    args = programs[0][2]
    repl = NamedSharding(mesh, P())
    placed = (sharded.place_params(args[0], mesh, layout),
              sharded.place_cache(args[1], mesh, layout),
              *(jax.device_put(a, repl) for a in args[2:]))
    with fsdp.layout_scope(layout, mesh):
        hlo = fn.lower(*placed).compile().as_text()
    counts = hlo_collective_counts(hlo)
    prog = f"serving_decode[fsdp={mesh.shape['fsdp']},tp={mesh.shape['tp']}]"
    _check_budget(findings, prog, counts,
                  {"all-reduce": (0, 2), "all-to-all": (0, 0)},
                  "the canonical sharded decode's only all-reduces are the "
                  "two exact reductions (one-hot embedding lookup, vocab "
                  "argmax); a count beyond 2 means a float contraction got "
                  "sharded and greedy token parity with solo generate is "
                  "gone (PR 19)")
    report["programs"][prog] = {"collectives": counts}
    report["legs"].append(
        {"leg": "serving",
         "programs": [name for name, _fn, _args in programs] + [prog]})


# -- leg 3: ZeRO bucketed update --------------------------------------------

def _leg_zero(findings, report, seed: Optional[str]) -> None:
    import jax
    import jax.numpy as jnp
    from ..parallel import zero as zero_mod
    from ..parallel.mesh import make_mesh
    from .. import optimizer as opt_mod

    mesh = make_mesh((_MIN_DEVICES,), ("dp",))
    opt = opt_mod.create("sgd", learning_rate=0.05, momentum=0.9)
    params = [jnp.ones((64, 8), jnp.float32),
              jnp.zeros((128,), jnp.float32),
              jnp.ones((16,), jnp.float32)]
    n = len(params)
    layout = zero_mod.ZeroLayout(params, [1.0] * n, [1.0] * n,
                                 _MIN_DEVICES)
    states, residuals = zero_mod.init_zero_states(opt, layout, params, mesh)
    zero_update = zero_mod.build_zero_update(opt, layout, mesh)
    grads = [jnp.full_like(p, 0.5) for p in params]
    scalars = (jnp.float32(0.05), jnp.float32(0.0), jnp.float32(1.0),
               jnp.float32(0.0), jnp.int32(1))
    hlo = jax.jit(zero_update).lower(
        params, grads, states, residuals, *scalars).compile().as_text()
    counts = hlo_collective_counts(hlo)
    prog = f"zero_update[dp={_MIN_DEVICES}]"
    _check_budget(findings, prog, counts,
                  {"all-reduce": (0, 0), "all-gather": (1, None)},
                  "the ZeRO update is reduce-scatter -> shard-update -> "
                  "all-gather by construction; an all-reduce means the "
                  "update fell back to replicated math (the pre-PR-4 "
                  "monolithic step) and the 1/N state residency is fiction")
    report["programs"][prog] = {"collectives": counts}
    report["legs"].append({"leg": "zero", "programs": [prog]})


# -- leg 4: fused training step ---------------------------------------------

def _leg_fused_step(findings, report, seed: Optional[str]) -> None:
    import numpy as np
    import jax
    import mxtpu as mx
    from mxtpu import nd
    from mxtpu.gluon import nn
    from mxtpu.gluon.block import HybridBlock
    from mxtpu.io import DataBatch, DataDesc

    class _AuditNet(HybridBlock):
        def __init__(self):
            super().__init__()
            self.fc1 = nn.Dense(16, in_units=12)
            self.fc2 = nn.Dense(10, in_units=16)

        def forward(self, x):
            return self.fc2(self.fc1(x).relu())

    mx.rng.seed(0)
    mod = mx.Module(_AuditNet(), data_names=("data",),
                    label_names=("softmax_label",))
    mod.bind(data_shapes=[DataDesc("data", (8, 12))],
             label_shapes=[DataDesc("softmax_label", (8,))])
    mod.init_params()
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.05,
                                         "momentum": 0.9})
    rs = np.random.RandomState(0)
    batch = DataBatch(data=[nd.array(rs.rand(8, 12).astype(np.float32))],
                      label=[nd.array(rs.randint(0, 10, 8)
                                      .astype(np.float32))])
    mod.forward_backward(batch)
    mod.update()
    jitted, avals = mod._step_exec.audit_entry()
    jaxpr = jax.make_jaxpr(jitted)(*avals)
    counts = jaxpr_prim_counts(jaxpr.jaxpr)
    _check_transfers(findings, "module_step", counts)
    report["programs"]["module_step"] = {
        "eqns": sum(counts.values()),
        "callbacks": sum(counts.get(p, 0) for p in _CALLBACK_PRIMS),
    }
    report["legs"].append({"leg": "fused_step", "programs": ["module_step"]})


# -- leg 5: retrace-closure proof -------------------------------------------

def _leg_keys(findings, report, seed: Optional[str]) -> None:
    from ..serving import engine as engine_mod

    bucket = (lambda n: n) if seed == "open_keys" else None
    specs = engine_mod.audit_key_specs(_MAX_LEN, _SLOTS, _CHUNK,
                                       _PREFILL_CHUNK, _K, bucket=bucket)
    # the admissible request domain: every prompt length x a spread of
    # generation lengths, totals clamped to the model window
    domain = [(plen, min(plen + new, _MAX_LEN))
              for plen in range(1, _MAX_LEN + 1)
              for new in (1, 7, 33)]
    audited = {}
    for name, keys_of, bounds in specs:
        keys = set()
        comp_vals = [set() for _ in bounds]
        for plen, total in domain:
            for key in keys_of(plen, total):
                keys.add(key)
                for i, c in enumerate(key):
                    comp_vals[i].add(c)
        audited[name] = {"distinct_keys": len(keys),
                         "bound": 1}
        for b in bounds:
            audited[name]["bound"] *= b
        for i, (vals, bound) in enumerate(zip(comp_vals, bounds)):
            if len(vals) > bound:
                findings.append(_finding(name, "A301", (
                    f"open-program-key-set: {name} key component {i} takes "
                    f"{len(vals)} distinct values over the admissible "
                    f"request domain, bound {bound} — an unbucketed "
                    f"quantity leaked into the program key; every new "
                    f"value mints a full recompile (the trace-once "
                    f"contract requires bucket32 at the key site)")))
    report["legs"].append({"leg": "keys", "programs": audited})


# -- driver -----------------------------------------------------------------

def run_audit(seed: Optional[str] = None,
              legs: Optional[Sequence[str]] = None):
    """Run the audit legs (all by default), optionally with one seeded
    violation.  Returns ``(findings, report)``."""
    from ..parallel.mesh import make_mesh

    active = tuple(legs) if legs else _LEGS
    findings: List[Finding] = []
    report = {"programs": {}, "legs": []}
    mesh = None
    if "shardcheck" in active or "serving" in active:
        mesh = make_mesh((4, 2), ("fsdp", "tp"))
    if "shardcheck" in active:
        _leg_shardcheck(findings, report, mesh, seed)
    if "serving" in active:
        _leg_serving(findings, report, mesh, seed)
    if "zero" in active:
        _leg_zero(findings, report, seed)
    if "fused_step" in active:
        _leg_fused_step(findings, report, seed)
    if "keys" in active:
        _leg_keys(findings, report, seed)
    return findings, report


def _filter(findings: List[Finding], select, ignore) -> List[Finding]:
    if select:
        findings = [f for f in findings if f.rule in set(select)]
    if ignore:
        findings = [f for f in findings if f.rule not in set(ignore)]
    return findings


def _respawn(expect_fail: bool, fmt: str, select, ignore) -> int:
    """Child re-exec with enough virtual CPU devices.  The audit needs the
    8-device mesh; a bare CLI invocation starts with 1 CPU device and the
    backend cannot be re-initialized in-process, so re-run ourselves with
    the forced device count (same shape the tier-1 guards use)."""
    import subprocess
    argv = [sys.executable, "-m", "mxtpu.analysis", "--audit"]
    if expect_fail:
        argv.append("--expect-fail")
    if fmt != "text":
        argv += ["--format", fmt]
    for r in select or ():
        argv += ["--select", r]
    for r in ignore or ():
        argv += ["--ignore", r]
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    env["XLA_FLAGS"] = (flags +
                        " --xla_force_host_platform_device_count=8").strip()
    env["JAX_PLATFORMS"] = "cpu"
    env["MXTPU_AUDIT_CHILD"] = "1"
    return subprocess.run(argv, env=env).returncode


def main_audit(expect_fail: bool = False, fmt: str = "text",
               select=None, ignore=None) -> int:
    import jax
    if len(jax.devices()) < _MIN_DEVICES:
        if os.environ.get("MXTPU_AUDIT_CHILD") == "1":
            print(f"audit: needs >= {_MIN_DEVICES} devices, have "
                  f"{len(jax.devices())} even after re-exec", file=sys.stderr)
            return 2
        return _respawn(expect_fail, fmt, select, ignore)

    if expect_fail:
        return _main_expect_fail(select, ignore)

    findings, report = run_audit()
    findings = _filter(findings, select, ignore)
    if fmt == "json":
        import json
        counts: Dict[str, int] = {}
        for f in findings:
            counts[f.rule] = counts.get(f.rule, 0) + 1
        print(json.dumps(
            {"version": 2, "audit": True,
             "findings": [{"path": f.path, "line": f.line, "col": f.col,
                           "rule": f.rule, "message": f.message}
                          for f in findings],
             "counts": counts, "report": report},
            indent=1, sort_keys=True, default=str))
    else:
        for f in findings:
            print(f.format())
        for prog, info in sorted(report["programs"].items()):
            print(f"audit: {prog}: {info}")
        print(f"audit: {len(report['programs'])} program(s), "
              f"{len(findings)} finding(s)")
    return 1 if findings else 0


def _main_expect_fail(select, ignore) -> int:
    """Prove detection: each seeded violation must surface its rule."""
    missed = []
    for seed, rule, legs in _SEEDS:
        findings, _ = run_audit(seed=seed, legs=legs)
        findings = _filter(findings, select, ignore)
        hits = [f for f in findings if f.rule == rule]
        status = "DETECTED" if hits else "MISSED"
        print(f"audit --expect-fail: seed '{seed}' -> {rule}: {status} "
              f"({len(hits)} finding(s))")
        if not hits:
            missed.append((seed, rule))
    if missed:
        print(f"audit --expect-fail: {len(missed)} seeded violation(s) "
              f"NOT detected: {missed}", file=sys.stderr)
        return 1
    print(f"audit --expect-fail: all {len(_SEEDS)} seeded violations "
          f"detected")
    return 0
