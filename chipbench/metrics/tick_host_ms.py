"""Host milliseconds per engine tick: the window's wall time less the time
inside the jitted tick call (dispatch to ``block_until_ready``), over the
ticks; the mean over pools. Sound where the engine never waits for work."""
import measure


def read(run):
    w = measure.window_s(run)
    v = [(w - wall) / ticks * 1e3 for wall, ticks in
         zip(measure.delta(run, "tick_wall_s"), measure.delta(run, "ticks"))
         if ticks > 0]
    return sum(v) / len(v) if v else None
