"""The program's own spans (``repro.tracing``) in a traced run, for the
per-layer metrics that read them.

The program records its spans while a profiler trace runs, so a traced run
holds those of its window and an untraced one none.  The first reader of a
run drains them once and keeps them on ``run.state`` for the others.  Each
reading is None where the run is untraced, the program has no tracer (an
older commit), the tracer dropped any span, or no span of the names read
lies inside the window.
"""

from __future__ import annotations

KEY = "program_spans"


def window_spans(run) -> list | None:
    """The program's spans that lie inside the window, from the start of
    its first request to the end of its last."""
    if not run.traced or not run.requests:
        return None
    if KEY not in run.state:
        try:
            from repro import tracing
        except ImportError:
            run.state[KEY] = None
        else:
            spans, dropped = tracing.drain()
            run.state[KEY] = None if dropped else spans
    spans = run.state[KEY]
    if spans is None:
        return None
    lo, hi = run.requests[0].start, run.requests[-1].end
    return [s for s in spans if lo <= s.start and s.end <= hi]


def named(run, *names) -> list | None:
    """The window's spans named one of ``names``, or None where none is."""
    spans = window_spans(run)
    picked = [s for s in spans or () if s.name in names]
    return picked or None


def total_s(run, *names) -> float | None:
    """Seconds inside the window's spans named one of ``names``."""
    picked = named(run, *names)
    return None if picked is None else sum(s.end - s.start for s in picked)


def per_request_ms(run, *names) -> float | None:
    """:func:`total_s` in milliseconds over the requests completed."""
    total, done = total_s(run, *names), len(run.done)
    return None if total is None or done == 0 else 1e3 * total / done


def per_generation_ms(run, *names) -> float | None:
    """:func:`total_s` in milliseconds over the search generations
    completed."""
    total = total_s(run, *names)
    gens = sum(r.work["generations"] for r in run.done)
    return None if total is None or gens == 0 else 1e3 * total / gens
