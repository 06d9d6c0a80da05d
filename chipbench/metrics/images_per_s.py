"""Images of sampling work done in the window, per second of window.

Each answered request with a finite x0 counts the share of its service
(the engine's ``service_s``, admission to retirement, ending at its answer)
that fell inside the window: a sample served wholly inside counts 1, one
that straddles an edge counts its part. Every trajectory takes one step per
tick, so that share is the share of its steps. Counting whole completions
instead would count the closed loop's waves (every resident trajectory of
one length retires on the same tick) and swing by a wave with the window's
phase."""
import measure


def read(run):
    t0, t1 = run.window
    done = 0.0
    for r in run.records:
        if not (r.get("ok") and r.get("finite")):
            continue
        end = r["done_t"]
        start = end - max(float(r.get("service_s") or 0.0), 0.0)
        if end <= start:
            done += 1.0 if t0 <= end <= t1 else 0.0
            continue
        done += max(0.0, min(end, t1) - max(start, t0)) / (end - start)
    return done / measure.window_s(run)
