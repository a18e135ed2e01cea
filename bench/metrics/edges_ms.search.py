"""Host time around the generations of a search: seeding the population
(``search.seed``) and pricing the answer (``search.finish``), the
program's spans, per search completed, in milliseconds."""

from bench import program_spans


def read(run):
    return program_spans.per_request_ms(run, "search.seed", "search.finish")
