"""Host time of the routed-expert gates (top-k routing and scaling of the
up-projections' messages), the program's span ``sim.moe.gate``, per
request completed, in milliseconds."""

from bench import program_spans


def read(run):
    return program_spans.per_request_ms(run, "sim.moe.gate")
