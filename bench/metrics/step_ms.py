"""Window milliseconds per decode step: the mean gap between a decoding
request's tokens, admissions and host work included."""
UNIT = "ms"


def read(run):
    steps = run.report["decode_steps"]
    return 1000.0 * run.window_s / steps if steps else None
