"""Host time of each generation's archive update and statistics record,
the program's span ``search.archive``, per generation completed, in
milliseconds."""

from bench import program_spans


def read(run):
    return program_spans.per_generation_ms(run, "search.archive")
