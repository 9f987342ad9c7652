"""Whole QAT step's share of the chip's bf16 peak: the FLOPs every step
must do (`flops.qat_step_flops`) times the window's steps per second."""
import harness


def read(view):
    c = view["counters"]
    if not c.get("steps"):
        return None
    rate = c["step_flops"] * c["steps"] / c["window_s"]
    return 100.0 * rate / harness.peak(view, "bf16_flops_per_s")
