"""Host time spent fetching each generation's offspring and statistics
from the device, the program's span ``search.sync``, per generation
completed, in milliseconds."""

from bench import program_spans


def read(run):
    return program_spans.per_generation_ms(run, "search.sync")
