"""R001 host-sync-in-step: a host synchronization inside a jit-traced step.

``.asnumpy()`` / ``np.asarray`` / ``float()`` / ``.item()`` on a traced value
either fails at trace time (TracerArrayConversionError) or — worse, via a
shape-dependent path that concretizes — forces a device→host round trip
every step.  Each readback drains the device queue and leaves the chip idle
until the host dispatches again, so a single stray sync erases the win of
the fused step executor.  The runtime twin of this
rule is ``MXTPU_SANITIZE=transfers`` (``jax.transfer_guard`` around the
fused step).
"""

from __future__ import annotations

import ast

from ..lint import Finding, dotted_name

RULE_ID = "R001"
TITLE = "host-sync-in-step"

# attribute calls that synchronize with the host
_SYNC_METHODS = {"asnumpy", "asscalar", "item", "tolist", "block_until_ready",
                 "wait_to_read", "wait_to_write"}
# module functions that materialize on the host
_SYNC_FUNCS = {"np.asarray", "numpy.asarray", "np.array", "numpy.array",
               "np.copy", "numpy.copy", "jax.device_get", "device_get"}
# builtins that concretize a traced value
_CONCRETIZERS = {"float", "int", "bool"}
# names whose presence in the argument marks a static (python-int) quantity:
# int(x.shape[0]) / float(len(xs)) trace fine and are not host syncs
_STATIC_HINTS = {"shape", "ndim", "size", "len", "range", "dtype", "dims"}


def _mentions_static(node) -> bool:
    for n in ast.walk(node):
        if isinstance(n, ast.Name) and n.id in _STATIC_HINTS:
            return True
        if isinstance(n, ast.Attribute) and n.attr in _STATIC_HINTS:
            return True
        if isinstance(n, ast.Call) and (dotted_name(n.func) or "") == "len":
            return True
    return False


def check(ctx):
    seen = set()
    for fn in ctx.step_functions:
        # v2: step_functions is closed over the call graph (self-method,
        # alias, lax HOF edges); name the drag-in chain for transitive hits
        path = ctx.callgraph.trace_path(fn)
        via = f" (traced via {' -> '.join(path)})" if len(path) > 1 else ""
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            key = (node.lineno, node.col_offset)
            if key in seen:
                continue
            name = dotted_name(node.func)
            hit = None
            if isinstance(node.func, ast.Attribute) \
                    and node.func.attr in _SYNC_METHODS:
                hit = f".{node.func.attr}()"
            elif name in _SYNC_FUNCS:
                if node.args and not isinstance(node.args[0], ast.Constant):
                    hit = f"{name}()"
            elif name in _CONCRETIZERS and len(node.args) == 1 \
                    and not isinstance(node.args[0], ast.Constant) \
                    and not _mentions_static(node.args[0]):
                hit = f"{name}()"
            if hit:
                seen.add(key)
                yield Finding(
                    ctx.path, node.lineno, node.col_offset, RULE_ID,
                    f"{TITLE}: {hit} inside a function that flows into a jax "
                    f"trace (jit/grad) forces a host sync every step — read "
                    f"results outside the step, or keep the value traced"
                    f"{via}")
