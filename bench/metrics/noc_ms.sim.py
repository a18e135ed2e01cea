"""Host time of routing a candidate's messages over the NoC (router loads,
hops and injections), the program's span ``price.route``, per request
completed, in milliseconds."""

from bench import program_spans


def read(run):
    return program_spans.per_request_ms(run, "price.route")
