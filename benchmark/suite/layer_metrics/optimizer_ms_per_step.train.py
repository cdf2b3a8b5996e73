"""Optimizer update: device time per profiled step under the scope
``optimizer`` (``optimizer/zero``: the ZeRO bucket update; the per-parameter
update of what is not bucketed)."""
import scopes


def read(view):
    return scopes.ms_per_step(view, "optimizer")
