"""Host time of each layer's neuron update, the program's span
``sim.neuron``, per request completed, in milliseconds."""

from bench import program_spans


def read(run):
    return program_spans.per_request_ms(run, "sim.neuron")
