from .lenet import LeNet5
from .autoencoder import Autoencoder
from .maskrcnn import MaskRCNN
from .resnet import ResNet
from .vgg import VggForCifar10, Vgg_16, Vgg_19
from .inception import Inception_v1
from .alexnet import AlexNet
from .textclassifier import BiLSTMClassifier, CNNTextClassifier, PTBModel
from .widedeep import WideAndDeep
from .ncf import NeuralCF
from . import decoder_lm  # decoder_lm.from_config(dict) -> nn.DecoderLM

def flagship_model(batch: int = 8, seed: int = 0, stem: str = "conv7"):
    """The framework's flagship config (what __graft_entry__ builds):
    ResNet-50 / synthetic ImageNet.

    Returns (model, example_images (B,3,224,224) f32, example_labels, name).
    """
    import numpy as np

    model = ResNet(50, class_num=1000, dataset="imagenet", stem=stem)
    x = np.random.default_rng(seed).standard_normal((batch, 3, 224, 224)).astype(np.float32)
    labels = np.random.default_rng(seed + 1).integers(0, 1000, batch)
    return model, x, labels, "ResNet-50 synthetic-ImageNet"


__all__ = [
    "Autoencoder",
    "flagship_model",
    "LeNet5",
    "ResNet",
    "VggForCifar10",
    "Vgg_16",
    "Vgg_19",
    "Inception_v1",
    "AlexNet",
    "BiLSTMClassifier",
    "CNNTextClassifier",
    "PTBModel",
    "WideAndDeep",
    "NeuralCF",
    "decoder_lm",
]
