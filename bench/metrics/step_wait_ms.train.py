"""Host milliseconds per step in the program's ``train.wait`` spans
(``graphs/experiment.py::run_step``: ``block_until_ready`` on the step's
loss), over the traced window's steps."""
import program_spans as PS


def read(run):
    return PS.per_step_ms(run, PS.span_s(run.trace, "train.wait"))
