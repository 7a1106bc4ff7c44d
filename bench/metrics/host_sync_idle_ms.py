"""Device-idle time under the training loop's host syncs, its
``stl.wait`` (blocked on the new state) and ``stl.loss_read`` (a loss
read back) spans, in ms per local step, mean over the cell's chips."""
from bench.metrics._spans import idle_ms_per


def read(ctx):
    return idle_ms_per(("wait", "loss_read"), ctx.steps)
