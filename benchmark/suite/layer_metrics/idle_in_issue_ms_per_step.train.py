"""Device: the part of the trainer's four issuing spans (``train/place``,
``prepare``, ``dispatch``, ``adopt``) during which device 0 runs nothing,
per profiled step: the idle time that the host's issuing explains."""
import scopes
import xplane


def read(view):
    spans, step = scopes.issue_spans(view), scopes.step_scopes(view)
    if spans is None or step is None:
        return None
    idle = xplane.subtract(xplane.merge(spans), step["busy"])
    return xplane.covered(idle) / view["profiled_steps"] / 1e6
