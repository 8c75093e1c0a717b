"""Output tokens of every request served in the window over the window's
seconds (the window ends when the last of them completes)."""
UNIT = "tokens/s"


def read(run):
    return run.work["output_tokens"] / run.window_s
