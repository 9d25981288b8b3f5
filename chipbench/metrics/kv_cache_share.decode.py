"""Share of the decode program's device time (averaged over the chips) whose
innermost program scope is ``kv_cache``: the ring buffer's writes and the
cast of the whole cache for the read (models/attention.py)."""
import scopes


def read(ctx):
    return scopes.scope_share(ctx, "step", "kv_cache", "serve.decode")
