"""Median milliseconds of one ``answer_batch`` call, from the call to the
moment its answers are ready on the host's clock."""
import statistics


def read(run):
    c = run.spans.get("call_s")
    return statistics.median(c) * 1e3 if c else None
