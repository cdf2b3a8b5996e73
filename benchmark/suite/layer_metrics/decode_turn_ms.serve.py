"""Model step (serve), decode: median host time of one ``serving/decode``
span, which is one dispatch of ``chunk`` decode steps over all slots with
its readback."""
import stats


def read(view):
    durs = [e["dur"] / 1e3 for e in view.get("spans") or []
            if e.get("name") == "serving/decode" and e.get("ph") == "X"]
    return stats.median(durs) if durs else None
