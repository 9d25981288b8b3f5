"""Share of the decode program's device time (averaged over the chips) whose
innermost program scope is ``layers``: the layer scan's slicing of each
layer's weights and cache, the restacking of the new caches, and the
residual adds (models/model.py ``_run_segments``)."""
import scopes


def read(ctx):
    return scopes.scope_share(ctx, "step", "layers", "serve.decode")
