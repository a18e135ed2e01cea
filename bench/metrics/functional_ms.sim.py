"""Mean host time of the functional run, ``net.run_batch(xs)``, per
request: the benchmark's span ``bench.run_batch`` of a traced run."""


def read(run):
    spans = run.span_s("bench.run_batch")
    return 1e3 * sum(spans) / len(spans) if spans else None
