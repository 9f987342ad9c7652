"""Host milliseconds per die in `run_mc_detector`: its own `host_s` timer
(per-chunk fetch and mAP scoring) over the dies of the window."""


def read(view):
    c = view["counters"]
    if not c.get("dies"):
        return None
    return 1e3 * c["host_s"] / c["dies"]
