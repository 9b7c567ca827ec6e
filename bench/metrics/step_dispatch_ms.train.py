"""Host milliseconds per step of the ``train.step`` span's self time (its
span less its child ``train.wait``, in ``graphs/experiment.py::run_step``):
the dispatch of the jitted step, over the traced window's steps."""
import program_spans as PS


def read(run):
    step = PS.span_s(run.trace, "train.step")
    wait = PS.span_s(run.trace, "train.wait")
    if step is None or wait is None:
        return None
    return PS.per_step_ms(run, step - wait)
