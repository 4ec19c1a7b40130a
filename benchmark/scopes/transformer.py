"""Scope rules of the model family ``transformer``: which class of
``lib/scopes.py`` an operation of the compiled step belongs to, by the flax
module names (and the names JAX gives the loss's own functions) in its
``op_name``. Searched in order after the program's own scopes, first match
wins; what none matches is ``unscoped``. Another family is another file
here, with its own classes."""

CLASSES = ("remat", "head_loss", "attention", "mlp", "embed")

RULES = (
    # remat's second forward, whatever module it recomputes
    ("remat", r"rematted_computation"),
    # then the model's parts, from the narrowest name
    ("head_loss", r"(^|/)lm_head(/|$)"),
    ("attention", r"MultiHeadAttention"),
    ("mlp", r"(^|/)block_\d+(/|$)"),
    # the embedding's backward is a scatter-add outside the module's scope
    ("embed", r"(^|/)Embed_\d+(/|$)|jvp\(jit\(_take\)\)"),
    # what is left of the model (the final layer norm) goes with the head
    # it feeds
    ("head_loss", r"jvp\(Transformer\)"),
    # the loss (lib/program.py: per_chip_loss): the differentiated
    # function has no name, its gather is take_along_axis
    ("head_loss", r"(^|/)(transpose\()?jvp\((jit\(take_along_axis\))?\)\)?(/|$)"),
)
