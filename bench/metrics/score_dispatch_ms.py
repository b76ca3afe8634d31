"""Mean time of one fused fallback dispatch, from the call to
``score_query_batch`` until ``device_get`` returns
(``serve_score_dispatch_seconds``)."""
from bench import program


def read(run):
    return program.mean_ms(run, "serve_score_dispatch_seconds")
