"""Seconds from the start of the process to the opening of the window:
inputs made, store filled and encoded, mirrors uploaded, every program
of the window compiled or loaded and run once."""


def read(run):
    return run.setup_s
