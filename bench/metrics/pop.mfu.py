"""Whole chunk program's share of the chip's bf16 peak: the FLOPs every
die must do (`flops.die_flops`) times the window's dies per second."""
import harness


def read(view):
    c = view["counters"]
    if not c.get("dies"):
        return None
    rate = c["die_flops"] * c["dies"] / c["window_s"]
    return 100.0 * rate / harness.peak(view, "bf16_flops_per_s")
