"""Median, over answered window requests, of the client's latency from its
actual send less the engine's own ``latency_s`` (submit to retire): the
time in HTTP/JSON/SSE, the bridge's queue and the event hand-back."""
import measure


def read(run):
    v = [(r["done_t"] - r["send_t"] - r["latency_s"]) * 1e3
         for r in measure.due(run) if r.get("ok") and r.get("latency_s")
         is not None]
    return measure.percentile(v, 50)
