"""Milliseconds of HTTP body encoding per answered request, from the
program's ``repro/http/encode`` spans (``spans.http_encode_ms``)."""
import spans


def read(run):
    return spans.http_encode_ms(run)
