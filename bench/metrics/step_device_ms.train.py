"""Device busy milliseconds per step in the traced window (union of the
device's operations, averaged over the chips)."""
import trace_reduce as TR


def read(run):
    busy = TR.busy_s(run.trace)
    return busy / run.steps * 1e3 if busy > 0 and run.steps else None
