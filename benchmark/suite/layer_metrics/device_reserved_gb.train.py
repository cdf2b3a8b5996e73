"""Device: GB the fullest device holds RESERVED at the newest memory mark
(``bytes_reserved``: what loaded programs keep for their temporaries, which
``memory_peak_bytes`` leaves out)."""
import phases


def read(view):
    return phases.mark_gb(phases.newest_mark(view), ("bytes_reserved",))
