"""Bytes the program copied from host arrays to the device, its counter
``h2d_bytes`` on the spans ``kernel.put``, per request completed, in
megabytes (1e6 bytes)."""

from bench import program_spans


def read(run):
    puts, done = program_spans.named(run, "kernel.put"), len(run.done)
    if puts is None or done == 0:
        return None
    return sum(s.counts.get("h2d_bytes", 0) for s in puts) / done / 1e6
