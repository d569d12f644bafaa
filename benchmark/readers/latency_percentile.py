"""A percentile of shown-minus-due over all changes due in the window: the
same arithmetic as ``latency_p50_ms``, further out in the tail. The tail is a
per-layer metric and not an end-to-end one because no bound fits it: on one
code it spread by 0.8% in one set of six runs and by 39% in the next, when
the machine's host had a noisy spell (PERF.md)."""

from ..metrics import percentile


def read(run, args):
    if not run.latencies_ms:
        return None
    return percentile(run.latencies_ms, float(args["percentile"]))
