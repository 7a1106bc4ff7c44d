"""Device-idle time under the training loop's ``stl.dispatch`` spans (a
local step or a round launched), in ms per program launched (local steps
plus rounds), mean over the cell's chips."""
from bench.metrics._spans import idle_ms_per


def read(ctx):
    return idle_ms_per(("dispatch",), ctx.steps + ctx.rounds)
