"""Inception-v1 (GoogLeNet) without auxiliary classifiers, dropout on, as
``bigdl_tpu.models.Inception_v1`` builds it."""

from benchmark.configs._image_classifier import local_trainer


def build(cfg: dict, traffic, seed: int, chips: int) -> dict:
    from bigdl_tpu.models import Inception_v1

    m = cfg["model"]
    model = Inception_v1(m["class_num"], has_dropout=m["dropout"])
    return local_trainer(model, cfg, traffic, seed, chips)
