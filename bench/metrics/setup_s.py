"""Set-up: process start to the first request of the window — weights
made, every shape the window uses warmed up (compiled, or loaded from the
compile cache)."""
UNIT = "s"


def read(run):
    return run.setup_s
