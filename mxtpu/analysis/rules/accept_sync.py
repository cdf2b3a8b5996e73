"""R009 per-token-host-sync: accept-count readback inside a scheduler loop.

The speculative-decode contract (``mxtpu.serving.spec``) is ONE sanctioned
host readback per verify dispatch: the engine lands ``(outs, lives)`` with
a single ``np.asarray`` pair and every per-slot decision — how many tokens
were accepted, what to emit, where the cursor moved — runs on that host
copy.  The tempting alternative is a per-slot (or worse, per-token) loop
that calls ``.item()`` / ``int()`` / ``np.asarray()`` on the DEVICE
accept-count array each iteration; each such call is a device→host round
trip with the device idle behind it, so a k=4 verify over 8 slots pays up to
32 syncs for a dispatch whose entire point was to cost one.
The win silently inverts: speculation *slows decode down* while every
bit-exactness test stays green.

Flagged: a host-materializing call (``.item()`` / ``.tolist()`` /
``int()`` / ``float()`` / ``np.asarray()``-family) **inside a ``for`` /
``while`` loop** whose receiver/argument names an accept/verify-family
value (``accept``/``accepted``/``accept_len``/``lives``/``verify_out``
substrings).  The blessed shape — the one readback outside the loop,
host-side indexing inside — never trips: names carrying a host-copy
suffix (``lives_np`` / ``accepts_host`` / ``*_cpu``) are exempt, as are
static quantities (``int(x.shape[0])``, ``len(...)``), mirroring R001.
"""

from __future__ import annotations

import ast

from ..lint import Finding, dotted_name

RULE_ID = "R009"
TITLE = "per-token-host-sync"

# substrings marking an accept/verify-family value (the arrays the verify
# program returns and the per-slot accept accounting derives from)
_ACCEPT_HINTS = ("accept", "lives", "verify_out")

_SYNC_METHODS = {"item", "asscalar", "tolist", "asnumpy"}
_SYNC_FUNCS = {"np.asarray", "numpy.asarray", "np.array", "numpy.array",
               "jax.device_get", "device_get"}
_CONCRETIZERS = {"int", "float", "bool"}
# static (python-int) quantities: int(acc.shape[0]) is not a host sync
_STATIC_HINTS = {"shape", "ndim", "size", "len", "range", "dtype", "dims"}


# suffixes declaring "already landed on the host" — the blessed readback
# names its numpy copies this way (outs_np / lives_np), and touching those
# in a loop is exactly the pattern the rule steers toward
_HOST_SUFFIXES = ("_np", "_host", "_cpu")


def _mentions_accept(node) -> bool:
    for n in ast.walk(node):
        name = None
        if isinstance(n, ast.Name):
            name = n.id
        elif isinstance(n, ast.Attribute):
            name = n.attr
        if name is not None:
            low = name.lower()
            if any(low.endswith(s) for s in _HOST_SUFFIXES):
                continue
            if any(h in low for h in _ACCEPT_HINTS):
                return True
    return False


def _mentions_static(node) -> bool:
    for n in ast.walk(node):
        if isinstance(n, ast.Name) and n.id in _STATIC_HINTS:
            return True
        if isinstance(n, ast.Attribute) and n.attr in _STATIC_HINTS:
            return True
        if isinstance(n, ast.Call) and (dotted_name(n.func) or "") == "len":
            return True
    return False


def _in_loop(ctx, node) -> bool:
    return any(isinstance(a, (ast.For, ast.AsyncFor, ast.While))
               for a in ctx.ancestors(node))


def _sync_target(node):
    """(synced expression, display form) of a host-materializing call."""
    if isinstance(node.func, ast.Attribute) \
            and node.func.attr in _SYNC_METHODS:
        return node.func.value, f".{node.func.attr}()"
    name = dotted_name(node.func)
    if name in _SYNC_FUNCS and node.args:
        return node.args[0], f"{name}()"
    if name in _CONCRETIZERS and len(node.args) == 1:
        return node.args[0], f"{name}()"
    return None, None


def _param_index(fn, name: str):
    a = fn.args
    params = [p.arg for p in list(a.posonlyargs) + list(a.args)]
    return params.index(name) if name in params else None


def _root_name(node):
    while isinstance(node, (ast.Subscript, ast.Attribute)):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


def _is_hostcopy(node) -> bool:
    """The argument is a declared host copy (``lives_np`` etc.) — syncing it
    again is free, so the cross-function form must not fire."""
    root = _root_name(node)
    return root is not None and \
        any(root.lower().endswith(s) for s in _HOST_SUFFIXES)


def _loop_sites_of(ctx, fn):
    """Loop call sites targeting ``fn`` across the module's call graph."""
    out = []
    for pairs in ctx.callgraph.edges.values():
        for callee, site in pairs:
            if callee is fn and _in_loop(ctx, site):
                out.append(site)
    return out


def _accept_at_site(ctx, fn, idx, depth=0):
    """Does some loop call site of ``fn`` pass an accept-family value at
    positional ``idx``?  Follows one parameter hop per level (helper one or
    two frames below the loop), bounded."""
    if idx is None or depth > 3:
        return None
    for site in _loop_sites_of(ctx, fn):
        if idx >= len(site.args):
            continue
        arg = site.args[idx]
        if _mentions_accept(arg) and not _mentions_static(arg):
            return site
        root = _root_name(arg)
        if root is not None:
            caller = ctx.enclosing_scope(site)
            if isinstance(caller, (ast.FunctionDef, ast.AsyncFunctionDef)):
                up = _accept_at_site(ctx, caller, _param_index(caller, root),
                                     depth + 1)
                if up is not None:
                    return site
    return None


def check(ctx):
    flagged = set()
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        target, hit = _sync_target(node)
        if target is None or not _mentions_accept(target) \
                or _mentions_static(target) or not _in_loop(ctx, node):
            continue
        flagged.add((node.lineno, node.col_offset))
        yield Finding(
            ctx.path, node.lineno, node.col_offset, RULE_ID,
            f"{TITLE}: {hit} on an accept/verify-family array inside a "
            f"loop syncs the host once per iteration — land (outs, lives) "
            f"with ONE np.asarray per verify dispatch outside the loop and "
            f"index the host copy inside it")

    # v2 cross-function form: a helper that syncs one of its parameters,
    # called from inside a for/while loop with an accept-family argument —
    # the helper body runs (and syncs) once per iteration even though no
    # loop is lexically visible around the sync itself
    for fn in ast.walk(ctx.tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if id(fn) not in ctx.callgraph.loop_called:
            continue
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            key = (node.lineno, node.col_offset)
            if key in flagged:
                continue
            target, hit = _sync_target(node)
            if target is None or _mentions_static(target):
                continue
            # the hazard is a value flowing IN from the loop: the synced
            # root must be a parameter of the helper.  A local produced by
            # the helper itself (the engine landing (toks, lives) once per
            # decode dispatch) is the sanctioned readback, never flagged.
            root = _root_name(target)
            idx = _param_index(fn, root) if root is not None else None
            if idx is None:
                continue
            site = None
            if _mentions_accept(target):
                for s in _loop_sites_of(ctx, fn):
                    if idx < len(s.args) and not _is_hostcopy(s.args[idx]):
                        site = s
                        break
            else:
                site = _accept_at_site(ctx, fn, idx)
            if site is None:
                continue
            flagged.add(key)
            yield Finding(
                ctx.path, node.lineno, node.col_offset, RULE_ID,
                f"{TITLE}: {hit} in '{fn.name}' syncs an accept/verify-"
                f"family value once per iteration of the loop calling it "
                f"(line {site.lineno}) — land (outs, lives) with ONE "
                f"np.asarray per verify dispatch outside the loop and pass "
                f"the host copy in")

