"""What the image-classifier configurations share: the model goes through
``LocalOptimizer.optimize()`` as a user runs it, and the forward pass is handed
to the FLOPs walk. Every number comes from the configuration's JSON."""

from __future__ import annotations


def local_trainer(model, cfg: dict, traffic, seed: int, chips: int) -> dict:
    import jax
    import jax.numpy as jnp

    from bigdl_tpu import nn
    from bigdl_tpu.optim import SGD, LocalOptimizer
    from bigdl_tpu.utils.engine import Engine
    from bigdl_tpu.utils.random import RandomGenerator

    if chips != 1:
        raise ValueError(f"{cfg['name']}: LocalOptimizer drives one chip, "
                         f"the cell asks for {chips}")
    RandomGenerator.set_seed(seed)
    Engine.set_compute_dtype(cfg["dtypes"]["compute"])
    Engine.set_activation_dtype(cfg["dtypes"]["activation"])
    opt = LocalOptimizer(model, traffic.dataset, nn.ClassNLLCriterion())
    o = cfg["optimizer"]
    opt.set_optim_method(SGD(learningrate=o["learning_rate"],
                             momentum=o["momentum"]))

    def forward():
        """(fn, args) of one step's forward pass, once the model is built."""
        x = jax.ShapeDtypeStruct(
            (traffic.batch,) + tuple(cfg["model"]["input_shape"]), jnp.float32)
        key = jax.random.PRNGKey(0)
        return (
            lambda p, s, x: model.apply(p, s, x, training=True, rng=key),
            (model.get_parameters(), model.get_state(), x),
        )

    return {"optimizer": opt, "forward": forward}
