"""Host time of each layer's synaptic forward, the program's span
``sim.synaptic`` (device copies and kernels included), per request
completed, in milliseconds."""

from bench import program_spans


def read(run):
    return program_spans.per_request_ms(run, "sim.synaptic")
