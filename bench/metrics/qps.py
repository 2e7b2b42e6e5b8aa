"""Exact answers per second: the requests submitted in the window and
answered, over the time from the window's opening to its last answer.

A dispatch can take seconds, so answers come in lumps; counting only the
answers that land before the window closes would swing by a lump from
run to run.  Counting every request of the window, and all the time until
the last of them is answered, takes all the work and all the time."""


def read(run):
    return run.answered / (run.t_drained - run.t_start)
