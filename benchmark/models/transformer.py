"""Model family ``transformer`` as the program builds it: the model at a
configuration file's sizes, the shapes of its parameters, and the batch a
training step of it takes. With ``lib/program.py`` the only files of the
yardstick that import the program; a configuration names its family under
``"model"``, and another family is another file here."""

import jax
import jax.numpy as jnp
import numpy as np

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
# sizes of a configuration file that the program's config takes as they are
_CONFIG_KEYS = ("vocab_size", "num_layers", "d_model", "num_heads", "d_ff",
                "max_len", "causal")


def build_model(config: dict, remat: bool = False):
    """The program's model at the sizes of a configuration file."""
    from horovod_tpu.models import Transformer, TransformerConfig

    cfg = TransformerConfig(
        **{k: config[k] for k in _CONFIG_KEYS},
        dtype=_DTYPES[config["dtype"]],
        flash_block_q=config["flash_block"],
        flash_block_k=config["flash_block"],
        remat=remat,
    )
    return Transformer(cfg)


def param_shapes(model, seq: int):
    """Shapes of the model's parameter tree (no value is taken from it)."""
    return jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, seq), jnp.int32), train=False))


def make_batch(cfg: dict, traffic: dict, world: int, seed: int):
    """Token and label rows ``[world, batch, seq]`` from the seed: every row
    differs. ``next-token`` labels are the tokens shifted left (the last
    position's label is drawn); ``random`` labels are drawn at every
    position (the program has no masking head: see the configuration)."""
    rng = np.random.default_rng([int(seed), 0x7261696E])
    shape = (world, traffic["batch_per_chip"], traffic["seq"])
    tokens = rng.integers(0, cfg["vocab_size"], shape, dtype=np.int32)
    labels = rng.integers(0, cfg["vocab_size"], shape, dtype=np.int32)
    if traffic["labels"] == "next-token":
        labels[..., :-1] = tokens[..., 1:]
    elif traffic["labels"] != "random":
        raise ValueError(f"labels {traffic['labels']!r} not known")
    return tokens, labels
