"""95th percentile of request latency: scheduled send to final answer,
over every request due in the window (a failed one counts as infinitely
late)."""
import measure


def read(run):
    return measure.percentile(measure.latencies(run), 95)
