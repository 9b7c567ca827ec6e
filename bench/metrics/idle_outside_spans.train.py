"""Percent of the device's idle time in the traced window that falls under
no program span (the driver's ``bench.*`` spans do not count): the idle
time the program's spans leave unexplained."""
import program_spans as PS
import trace_reduce as TR


def read(run):
    outside = PS.idle_outside_s(run.trace)
    idle = TR.window_s(run.trace) - TR.busy_s(run.trace)
    if outside is None or idle <= 0:
        return None
    return 100.0 * outside / idle
