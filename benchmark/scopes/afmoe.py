"""Scope rules of the model family ``afmoe``: which class of
``lib/scopes.py`` an operation of the compiled step belongs to, by the flax
module names and the program's ``jax.named_scope``s in its ``op_name``
(``docs/observability.md`` lists them). Searched in order after the
program's own scopes, first match wins; what none matches is ``unscoped``.

An expert layer's feed-forward part is three classes: ``moe_experts`` (the
grouped matmuls over the held experts, scope ``moe_experts``),
``moe_shared`` (the shared expert's dense matmuls, scope ``moe_shared``) and
``moe`` (what is left of the module ``moe``: the router and top-k under
``moe_route``, sort and gather under ``moe_dispatch``, the activation
between the matmuls, un-sort and weighted sum under ``moe_combine``).
``mlp`` is the rest of every ``block_N``: the four norms, the residual adds
and the leading dense layer's gated feed-forward."""

CLASSES = ("remat", "head_loss", "attention", "mlp", "moe", "moe_experts",
           "moe_shared", "embed")

RULES = (
    # remat's second forward, whatever module it recomputes
    ("remat", r"rematted_computation"),
    # then the model's parts, from the narrowest name
    ("head_loss", r"(^|/)lm_head(/|$)"),
    ("attention", r"MultiHeadAttention"),
    ("moe_experts", r"(^|/)moe_experts(/|$)"),
    ("moe_shared", r"(^|/)moe_shared(/|$)"),
    ("moe", r"(^|/)moe(/|$)"),
    ("mlp", r"(^|/)block_\d+(/|$)"),
    # the embedding's backward is a scatter-add outside the module's scope
    ("embed", r"(^|/)Embed_\d+(/|$)|jvp\(jit\(_take\)\)"),
    # what is left of the model (the embedding's scale, the final norm)
    # goes with the head it feeds
    ("head_loss", r"jvp\(Transformer\)"),
    # the loss (lib/program.py: per_chip_loss): the differentiated
    # function has no name, its gather is take_along_axis
    ("head_loss", r"(^|/)(transpose\()?jvp\((jit\(take_along_axis\))?\)\)?(/|$)"),
)
