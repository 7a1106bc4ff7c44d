"""The one-chip averaging round's share of its HBM roofline, in %: the
round's least bytes (read and write every replica's parameters and
optimizer moments once, from the state's shapes) over the peak HBM
bandwidth times the round's device time. One chip only: across chips the
round is bound by the interconnect, not by HBM alone."""
from bench.metrics._programs import per_call_s


def read(ctx):
    s = per_call_s(ctx, "bench_sync_round")
    if ctx.chips != 1 or not s:
        return None
    return 100.0 * ctx.round_bytes / (ctx.peaks["hbm_bytes_per_s"] * s)
