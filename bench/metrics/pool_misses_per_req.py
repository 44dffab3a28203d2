"""Adapter memory manager: adapters loaded into the pool during the window
(``AdapterMemoryManager.stats.loads``) per request served."""


def read(ctx):
    if not ctx.requests:
        return None
    return ctx.loads / ctx.requests
