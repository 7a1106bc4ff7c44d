"""Device time of one ``bench_sync_round`` program, in ms, on the slowest
device of the traced window."""
from bench.metrics._programs import per_call_ms


def read(ctx):
    return per_call_ms(ctx, "bench_sync_round")
