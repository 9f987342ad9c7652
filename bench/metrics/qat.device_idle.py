"""Share of the QAT window in which the chip ran no op (trace)."""
import harness


def read(view):
    return harness.idle_share(view)
