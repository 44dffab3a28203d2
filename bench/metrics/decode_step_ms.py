"""Model step: device time of one decode step, the ``decode_fn`` module's
events in the traced wave over their number."""


def read(ctx):
    calls = ctx.dev["module_calls"].get("decode_fn", 0)
    if not calls:
        return None
    return ctx.dev["module_ns"]["decode_fn"] * 1e-6 / calls
