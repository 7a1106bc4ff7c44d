"""Device time of one ``bench_local_step`` program, in ms, on the slowest
device of the traced window."""
from bench.metrics._programs import per_call_ms


def read(ctx):
    return per_call_ms(ctx, "bench_local_step")
