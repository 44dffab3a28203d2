"""The traced wave's host spans, as the program's tracer recorded them
(``EngineTracer.spans``: ``[id, parent_id, name, t0_ns, t1_ns, args]``,
ids equal to positions, on the host's wall clock). A program that
records no host spans gives ``None``, and so does every reader of them."""


def of(ctx):
    return getattr(ctx.traced["observer"], "spans", None) or None


def named(spans, name):
    return [s for s in spans if s[2] == name]


def parent_name(spans, s):
    return spans[s[1]][2] if s[1] is not None else None


def ancestor(spans, s, name):
    """The nearest enclosing span called ``name``, or None."""
    while s[1] is not None:
        s = spans[s[1]]
        if s[2] == name:
            return s
    return None
