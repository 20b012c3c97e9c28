"""Tokens of the steps completed in the window / (last completion - first
completion) / chips. Host clock around ``block_until_ready`` of each
step's loss."""

NAME, UNIT = "tokens_per_s_per_chip", "tokens/s/chip"


def read(run):
    return run.tokens_per_s_per_chip()
