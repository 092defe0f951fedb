"""Host wall time of the epoch loop's ``train.eval`` spans in the traced
window per traced epoch (``bench/program_trace.py``)."""
from bench import program_trace


def read(ctx):
    return program_trace.eval_ms_per_epoch(ctx)
