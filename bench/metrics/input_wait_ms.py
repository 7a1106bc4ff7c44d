"""Device-idle time under the training loop's ``stl.input`` span (the
next batch taken from the input stream), in ms per local step, mean over
the cell's chips."""
from bench.metrics._spans import idle_ms_per


def read(ctx):
    return idle_ms_per(("input",), ctx.steps)
