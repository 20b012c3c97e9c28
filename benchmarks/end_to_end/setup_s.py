"""Process start to the first timed step: interpreter, imports, the kernel
sweep's child on a cold checkout, weights and optimizer state from the
seed, compilation (or its load from the cache), the checked first steps and
the warm-up. The plain reference runs after the window and is not in it."""

NAME, UNIT = "setup_s", "s"


def read(run):
    return run.setup_s
