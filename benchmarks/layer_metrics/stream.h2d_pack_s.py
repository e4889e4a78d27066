"""Seconds inside `ooc::h2d_pack` spans per solve over the window, summed
over the staging threads (thread-seconds, like `stream.h2d_span_s`,
which contains it): the host-side contiguous copy, against the
hand-over to the runtime."""


def compute(run):
    s, n = run["spans"].get("ooc::h2d_pack"), run["records"].get("solves")
    if not s or not n:
        return None
    return s / n
