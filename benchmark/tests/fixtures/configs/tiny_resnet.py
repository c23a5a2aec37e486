from benchmark.configs._image_classifier import local_trainer


def build(cfg, traffic, seed, chips):
    from bigdl_tpu.models import ResNet

    m = cfg["model"]
    model = ResNet(m["depth"], class_num=m["class_num"], dataset="cifar10",
                   with_log_softmax=True)
    return local_trainer(model, cfg, traffic, seed, chips)
