"""Host time of pricing one partition and placement from the cumulative
sums (segment gathers, core times and energies, NoC routing), the
program's span ``price.candidate``, per request completed, in
milliseconds."""

from bench import program_spans


def read(run):
    return program_spans.per_request_ms(run, "price.candidate")
