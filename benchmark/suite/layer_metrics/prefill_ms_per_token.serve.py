"""Model step (serve), prefill: host time of the ``serving/prefill_chunk``
spans over the positions they covered, dispatch and readback included."""


def read(view):
    spans = [e for e in view.get("spans") or []
             if e.get("name") == "serving/prefill_chunk" and e.get("ph") == "X"]
    positions = sum(e["args"]["chunk"] for e in spans)
    if not positions:
        return None
    return sum(e["dur"] for e in spans) / 1e3 / positions
