"""Percent of the traced window in which no operation ran on the device,
averaged over the chips."""
import trace_reduce as TR


def read(run):
    if not run.trace.ops:
        return None
    return 100.0 * (1.0 - TR.busy_s(run.trace) / TR.window_s(run.trace))
