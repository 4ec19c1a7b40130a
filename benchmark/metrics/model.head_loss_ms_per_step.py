"""Per step, device time of the head and the loss: ``lm_head``, the final
layer norm and the differentiated operations outside the model, forward,
backward and recomputed."""

from benchmark.lib import scopes


def read(r):
    return scopes.ms_per_step(r, "head_loss")
