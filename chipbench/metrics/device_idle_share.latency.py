"""Share of the traced window, in %, in which no op ran on the device
(``measure.idle_share``)."""
import measure


def read(run):
    return measure.idle_share(run)
