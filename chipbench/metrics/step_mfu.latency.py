"""The whole tick's share of the chips' peak, in % (``measure.step_mfu``)."""
import measure


def read(run):
    return measure.step_mfu(run)
