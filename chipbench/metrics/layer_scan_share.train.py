"""Share of the train step's device time (averaged over the chips) whose
innermost program scope is ``layers``, forward and backward: the layer
scan's slicing of the stacked weights, the stacking of saved activations
and of the gradients, and the residual adds."""
import scopes


def read(ctx):
    return scopes.scope_share(ctx, "step", "layers", "trainer.step")
