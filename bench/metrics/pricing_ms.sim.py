"""Mean host time of the pricing, ``simulate(net, xs, chip,
precomputed=run)``, per request: the benchmark's span ``bench.pricing``
of a traced run."""


def read(run):
    spans = run.span_s("bench.pricing")
    return 1e3 * sum(spans) / len(spans) if spans else None
