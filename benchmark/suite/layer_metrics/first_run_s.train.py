"""Set-up: seconds of ``train/first_readback``, the wait for the loss of a
step whose call traced: the executable goes onto the device and runs for the
first time (the compile or cache load is ``compile_or_load_s.train``'s)."""
import phases


def read(view):
    return phases.span_seconds(view, ("train/first_readback",))
