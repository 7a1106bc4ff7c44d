"""Shared by the program-time readers."""


def per_call_s(ctx, program):
    times = [d["programs"][program]["device_s"] / d["programs"][program]["calls"]
             for d in ctx.trace["devices"]
             if d["programs"].get(program, {}).get("calls")]
    return max(times) if times else None


def per_call_ms(ctx, program):
    s = per_call_s(ctx, program)
    return None if s is None else 1e3 * s
