"""Device: device time per profiled step of the operations under none of
the scopes ``embed``, ``embedding``, ``block<i>``, ``ln_f``, ``head``,
``loss``, ``optimizer`` that are no Mosaic call: what the names do not
cover."""
import scopes


def read(view):
    return scopes.ms_per_step(view, scopes.UNATTRIBUTED)
