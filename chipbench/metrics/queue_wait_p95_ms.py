"""95th percentile of the engine's ``queue_wait_s`` (submit to slot
admission) over answered window requests."""
import measure


def read(run):
    v = [r["queue_wait_s"] * 1e3 for r in measure.due(run)
         if r.get("ok") and r.get("queue_wait_s") is not None]
    return measure.percentile(v, 95)
