"""Share of the traced window, in %, in the supervisor's checkpoint sweeps
(``spans.checkpoint_share``)."""
import spans


def read(run):
    return spans.checkpoint_share(run)
