"""Host time of the reduction of the functional run's counters to
per-layer cumulative sums, the program's span ``price.cumsum``, per
request completed, in milliseconds."""

from bench import program_spans


def read(run):
    return program_spans.per_request_ms(run, "price.cumsum")
