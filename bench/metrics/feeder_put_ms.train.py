"""Host milliseconds per step in the program's ``feeder.put`` spans
(``graphs/experiment.py::_to_batch``: the batch's host-to-device puts),
over the traced window's steps."""
import program_spans as PS


def read(run):
    return PS.per_step_ms(run, PS.span_s(run.trace, "feeder.put"))
