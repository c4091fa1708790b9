"""Median milliseconds a served query waited from the time it was due
to the ``answer_batch`` call that took it (the harness's clock)."""
import statistics


def read(run):
    w = run.spans.get("wait_s")
    return statistics.median(w) * 1e3 if w else None
