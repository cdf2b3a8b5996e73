"""R010 blocking-call-in-decode-loop: network/transport I/O inside a
scheduler decode loop.

The multi-replica serving contract (``mxtpu.serving.router``) is that
routing reads are LOCK-FREE SNAPSHOTS: a router polls ``engine.load()``
(or scrapes a remote exporter) from its own thread, and the engine's
scheduler loop never waits on anything slower than its own dispatch. The
tempting inversion — the scheduler loop itself phoning a peer, scraping a
metrics endpoint, or rendezvousing over the ``mxtpu.dist`` transport once
per decode turn — couples every slot's inter-token latency to network
tail latency: one 200 ms scrape stall is a 200 ms token stall for the
whole batch, and the decode program sits idle while the socket blocks. The failure is invisible to bit-exactness tests;
only p99 inter-token latency shows it.

Flagged: a blocking network/transport call — ``urlopen``/``requests.*``
fetches, ``socket`` connects, ``recv``/``sendall``/``getresponse``, or a
connect/barrier/scrape-family method on a transport-named receiver
(``transport``/``sock``/``conn``/``http``/``channel``/``session``) —
**inside a ``for``/``while`` loop** of a scheduler-family function (name
containing ``sched``/``decode``/``serve``/``dispatch``/``turn``). The
blessed shapes never trip: the router's own polling loops live outside the
engine (no scheduler-family enclosing function), drain/adopt transport
use sits outside the decode loop, and an exporter scrape runs on its own
daemon thread.
"""

from __future__ import annotations

import ast

from ..lint import Finding, dotted_name

RULE_ID = "R010"
TITLE = "blocking-call-in-decode-loop"

# unambiguous blocking network calls, by dotted name
_NET_FUNCS = {"urllib.request.urlopen", "urlopen", "requests.get",
              "requests.post", "requests.put", "requests.request",
              "socket.create_connection", "http.client.HTTPConnection"}
# unambiguous blocking socket/HTTP methods, any receiver
_NET_METHODS = {"recv", "recv_into", "recvfrom", "sendall", "getresponse",
                "urlopen"}
# connect/sync-family methods that block only when the receiver is a
# network/transport object — gated on the receiver's name
_TRANSPORT_METHODS = {"connect", "disconnect", "barrier", "scrape",
                      "fetch", "request", "get", "post", "send",
                      "rendezvous", "wait"}
_TRANSPORT_HINTS = ("transport", "socket", "sock", "conn", "http",
                    "channel", "session", "client", "peer")

# a scheduler-family function: the engine's decode/dispatch path, where a
# blocking call inside a loop stalls every slot's next token
_SCHED_HINTS = ("sched", "decode", "serve", "dispatch", "turn")


def _names_transport(node) -> bool:
    for n in ast.walk(node):
        name = None
        if isinstance(n, ast.Name):
            name = n.id
        elif isinstance(n, ast.Attribute):
            name = n.attr
        if name is not None:
            low = name.lower()
            if any(h in low for h in _TRANSPORT_HINTS):
                return True
    return False


def _sched_loop(ctx, node) -> bool:
    """In a for/while loop AND under a scheduler-family function."""
    in_loop = in_sched_fn = False
    for a in ctx.ancestors(node):
        if isinstance(a, (ast.For, ast.AsyncFor, ast.While)):
            in_loop = True
        if isinstance(a, (ast.FunctionDef, ast.AsyncFunctionDef)):
            low = a.name.lower()
            if any(h in low for h in _SCHED_HINTS):
                in_sched_fn = True
    return in_loop and in_sched_fn


def check(ctx):
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        hit = None
        name = dotted_name(node.func)
        if name is not None and name in _NET_FUNCS:
            hit = f"{name}()"
        elif isinstance(node.func, ast.Attribute):
            attr = node.func.attr
            if attr in _NET_METHODS:
                hit = f".{attr}()"
            elif attr in _TRANSPORT_METHODS \
                    and _names_transport(node.func.value):
                hit = f".{attr}()"
        if hit is None or not _sched_loop(ctx, node):
            continue
        yield Finding(
            ctx.path, node.lineno, node.col_offset, RULE_ID,
            f"{TITLE}: {hit} blocks the scheduler decode loop on network "
            f"I/O — every slot's next token now waits on tail latency. "
            f"Routing reads must be lock-free snapshots (engine.load()); "
            f"move the call to the router/exporter thread or outside the "
            f"per-turn loop")
