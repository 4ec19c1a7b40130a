"""Scope rules of the model family ``deepseek_v3``: which class of
``lib/scopes.py`` an operation of the compiled step belongs to, by the flax
module names and the program's ``jax.named_scope``s in its ``op_name``
(``docs/observability.md`` lists them). Searched in order after the
program's own scopes, first match wins; what none matches is ``unscoped``.

Every layer's attention runs under the scope ``attn_latent``. Inside it,
``latent_proj`` is what latent attention adds to a plain layer's
projections outside the kernels: the joint down-projection, the latent's
norm, the up-projection to every head's key and value, the rotation of the
rope parts and the concatenations that make the 192-wide q and k (scope
``latent_proj``); ``attention`` is the rest of ``MultiHeadAttention``: the q
and output projections, the head transposes and the flash kernels. The
expert layer's three classes and ``mlp`` are the ``afmoe`` family's."""

CLASSES = ("remat", "head_loss", "latent_proj", "attention", "mlp", "moe",
           "moe_experts", "moe_shared", "embed")

RULES = (
    # remat's second forward, whatever module it recomputes
    ("remat", r"rematted_computation"),
    # then the model's parts, from the narrowest name
    ("head_loss", r"(^|/)lm_head(/|$)"),
    ("latent_proj", r"(^|/)latent_proj(/|$)"),
    ("attention", r"MultiHeadAttention"),
    ("moe_experts", r"(^|/)moe_experts(/|$)"),
    ("moe_shared", r"(^|/)moe_shared(/|$)"),
    ("moe", r"(^|/)moe(/|$)"),
    ("mlp", r"(^|/)block_\d+(/|$)"),
    # the embedding's backward is a scatter-add outside the module's scope
    ("embed", r"(^|/)Embed_\d+(/|$)|jvp\(jit\(_take\)\)"),
    # what is left of the model (the final norm) goes with the head it feeds
    ("head_loss", r"jvp\(Transformer\)"),
    # the loss (lib/program.py: per_chip_loss): the differentiated
    # function has no name, its gather is take_along_axis
    ("head_loss", r"(^|/)(transpose\()?jvp\((jit\(take_along_axis\))?\)\)?(/|$)"),
)
