"""Set-up: GB of the process's peak resident set (``getrusage``) at the
newest memory mark taken before the window's last step ended, so that the
profiler's own buffers and the reference, which come later, are not in it."""
import phases


def read(view):
    rows = phases.window_rows(view)
    if rows is None:
        return None
    end = rows[-1]["start_ns"] + rows[-1]["step_s"] * 1e9
    return phases.mark_gb(phases.newest_mark(view, before_ns=end),
                          ("host_peak_rss_bytes",))
