"""Device: GB the fullest device has left at the newest memory mark: its
``bytes_limit`` less ``bytes_in_use`` less ``bytes_reserved``. Near 0 the
compiler rematerialises to fit whatever is added to the resident state."""
import phases


def read(view):
    return phases.mark_gb(phases.newest_mark(view), ("bytes_limit",),
                          minus=("bytes_in_use", "bytes_reserved"))
