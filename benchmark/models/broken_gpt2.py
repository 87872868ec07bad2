"""A control of the check, never of a cell: the ``gpt2`` program serving
tokens altered where they are produced (the last block's MLP output
projection is zeroed, so the argmax moves).  ``tests/benchmark`` drives a
whole run over it and sees ``correct`` come out false."""

from benchmark.models import gpt2

Asker = gpt2.Asker
verify_worker = gpt2.verify_worker


def builder(args):
    import jax.numpy as jnp

    cfg, params = gpt2.builder(args)
    last = params[f"layer_{cfg.num_layers - 1}"]
    last["mlp_down"]["kernel"] = jnp.zeros_like(last["mlp_down"]["kernel"])
    last["attn"]["out"]["kernel"] = jnp.zeros_like(
        last["attn"]["out"]["kernel"])
    return cfg, params
