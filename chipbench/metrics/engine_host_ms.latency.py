"""Host milliseconds per engine tick, from the program's tick and block
spans (``spans.engine_host_ms``)."""
import spans


def read(run):
    return spans.engine_host_ms(run)
