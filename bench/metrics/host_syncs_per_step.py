"""Host syncs per local step: the training loop's ``stl.wait`` and
``stl.loss_read`` spans in the traced window over the local steps in it.
Each marks one place the loop blocks on the device or reads a value back
from it."""
from bench.metrics._spans import cycle


def read(ctx):
    c = cycle()
    if not c or not ctx.steps:
        return None
    return (c["counts"]["stl.wait"] + c["counts"]["stl.loss_read"]) / ctx.steps
