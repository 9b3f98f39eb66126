"""Model zoo.

Parity: deeplearning4j-zoo (SURVEY.md §2.8) — standard architectures as
config builders. Each returns a configuration whose JSON round-trips, so zoo
models are data, not code.
"""

from deeplearning4j_tpu.models.labels import (
    BaseLabels,
    DarknetLabels,
    ImageNetLabels,
    VOCLabels,
)
from deeplearning4j_tpu.models.pretrained import init_pretrained, pretrained_path
from deeplearning4j_tpu.models.zoo import (
    HybridLM, LatentAttentionLM, LeNet5, LoopedLM, ShortConvLM, SimpleCNN,
    TextGenerationLSTM, TransformerLM)
from deeplearning4j_tpu.models.zoo_graph import (
    AlexNet,
    Darknet19,
    FaceNetNN4Small2,
    GoogLeNet,
    InceptionResNetV1,
    ResNet50,
    TinyYOLO,
    VGG16,
    VGG19,
)

__all__ = [
    "LeNet5", "SimpleCNN", "TextGenerationLSTM", "TransformerLM", "HybridLM",
    "LatentAttentionLM", "ShortConvLM", "LoopedLM",
    "AlexNet", "VGG16", "VGG19", "ResNet50", "GoogLeNet", "Darknet19",
    "TinyYOLO", "InceptionResNetV1", "FaceNetNN4Small2",
    "init_pretrained", "pretrained_path",
    "BaseLabels", "ImageNetLabels", "DarknetLabels", "VOCLabels",
]
