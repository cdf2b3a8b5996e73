"""Model step (train): device time per profiled step under
``block<i>/moe/route/groups``: the group-limited choice (each group's two
best scores, the best groups, the mask), a part of
``moe_route_ms_per_step.train``."""
import ling


def read(view):
    return ling.scope_ms(view, "groups")
