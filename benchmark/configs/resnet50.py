"""ResNet-50 for ImageNet, as ``bigdl_tpu.models.ResNet`` builds it."""

from benchmark.configs._image_classifier import local_trainer


def build(cfg: dict, traffic, seed: int, chips: int) -> dict:
    from bigdl_tpu.models import ResNet

    m = cfg["model"]
    model = ResNet(m["depth"], class_num=m["class_num"], dataset="imagenet",
                   with_log_softmax=True)
    return local_trainer(model, cfg, traffic, seed, chips)
