"""Scope rules of the model family ``sdar_moe``: which class of
``lib/scopes.py`` an operation of the compiled step belongs to, by the flax
module names and the program's ``jax.named_scope``s in its ``op_name``
(``docs/observability.md`` lists them). Searched in order after the
program's own scopes, first match wins; what none matches is ``unscoped``.

Every layer's attention runs under the scope ``attn_blockdiff``
(``attention``: the projections, QK-norm, the rotation, the head transposes
and the flash kernels under the block-diffusion mask). An expert layer's
classes are the ``afmoe`` family's less ``moe_shared``, the model having no
shared expert: ``moe_experts`` (the grouped matmuls over the held experts)
and ``moe`` (what is left of the module ``moe``: router and top-k, the
dispatch's passes, the activation, the weighted sum). ``mlp`` is the rest of
every ``block_N``: the two norms and the residual adds."""

CLASSES = ("remat", "head_loss", "attention", "mlp", "moe", "moe_experts",
           "embed")

RULES = (
    # remat's second forward, whatever module it recomputes
    ("remat", r"rematted_computation"),
    # then the model's parts, from the narrowest name
    ("head_loss", r"(^|/)lm_head(/|$)"),
    ("attention", r"MultiHeadAttention"),
    ("moe_experts", r"(^|/)moe_experts(/|$)"),
    ("moe", r"(^|/)moe(/|$)"),
    ("mlp", r"(^|/)block_\d+(/|$)"),
    # the embedding's backward is a scatter-add outside the module's scope
    ("embed", r"(^|/)Embed_\d+(/|$)|jvp\(jit\(_take\)\)"),
    # what is left of the model (the cut to the noised half, the final
    # norm) goes with the head it feeds
    ("head_loss", r"jvp\(Transformer\)"),
    # the loss (models/sdar_moe.py: per_chip_loss): the differentiated
    # function has no name, its gather is take_along_axis
    ("head_loss", r"(^|/)(transpose\()?jvp\((jit\(take_along_axis\))?\)\)?(/|$)"),
)
