"""ResNet-50 for ImageNet through the ZeRO-1 ``DistriOptimizer`` over every
chip of the host, built as ``chip_smoke.phase_distri`` builds it: the batch
sharded over a ``data`` mesh, one flat master vector, momentum sharded,
reduce-scatter and all-gather inside the one jitted step."""


def build(cfg: dict, traffic, seed: int, chips: int) -> dict:
    import jax
    import jax.numpy as jnp

    from bigdl_tpu import nn
    from bigdl_tpu.dataset import DataSet
    from bigdl_tpu.models import ResNet
    from bigdl_tpu.optim import SGD
    from bigdl_tpu.parallel.distri_optimizer import DistriOptimizer
    from bigdl_tpu.utils.engine import Engine
    from bigdl_tpu.utils.random import RandomGenerator

    Engine.init(devices=jax.local_devices())  # run.py has counted them
    RandomGenerator.set_seed(seed)
    Engine.set_compute_dtype(cfg["dtypes"]["compute"])
    Engine.set_activation_dtype(cfg["dtypes"]["activation"])
    m, o = cfg["model"], cfg["optimizer"]
    model = ResNet(m["depth"], class_num=m["class_num"], dataset="imagenet",
                   with_log_softmax=True)
    opt = DistriOptimizer(
        model, DataSet.distributed(traffic.dataset, chips),
        nn.ClassNLLCriterion(), parameter_sync=o["parameter_sync"])
    opt.set_optim_method(SGD(learningrate=o["learning_rate"],
                             momentum=o["momentum"]))

    def forward():
        """(fn, args) of one step's forward pass over the global batch."""
        x = jax.ShapeDtypeStruct(
            (traffic.batch,) + tuple(m["input_shape"]), jnp.float32)
        key = jax.random.PRNGKey(0)
        return (
            lambda p, s, x: model.apply(p, s, x, training=True, rng=key),
            (model.get_parameters(), model.get_state(), x),
        )

    return {"optimizer": opt, "forward": forward}
