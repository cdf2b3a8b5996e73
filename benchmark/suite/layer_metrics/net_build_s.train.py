"""Set-up: seconds the model's parameters took before a trainer exists: the
outermost ``Block.initialize`` (``net/initialize``) and ``Block.cast``
(``net/cast``) calls and every ``Parameter.set_data`` (``param/set_data``:
the seed's weights installed)."""
import phases


def read(view):
    return phases.span_seconds(
        view, ("net/initialize", "net/cast", "param/set_data"))
