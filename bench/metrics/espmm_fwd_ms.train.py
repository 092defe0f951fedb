"""Device time of the step program's ops under the ``espmm_fwd`` scope
(``kernels/ops._espmm_core``) per step, averaged over the chips, as
``step_ms.train`` divides (``bench/program_trace.py``)."""
from bench import program_trace


def read(ctx):
    return program_trace.pass_ms(ctx, "espmm_fwd")
