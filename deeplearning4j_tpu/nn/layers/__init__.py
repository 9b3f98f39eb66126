"""Layer library.

One config dataclass per layer type, registered for JSON serde. Coverage
targets the reference's nn/conf/layers/ set (~45 classes, SURVEY.md §2.1).
"""

from deeplearning4j_tpu.nn.layers.core import (
    ActivationLayer,
    AlphaDropout,
    AutoEncoder,
    Dense,
    DropoutLayer,
    ELULayer,
    Embedding,
    EmbeddingSequence,
    GaussianDropout,
    GaussianNoise,
    LeakyReLULayer,
    LossLayer,
    OutputLayer,
    Permute,
    PReLU,
    RepeatVector,
    SpatialDropout,
    ThresholdedReLULayer,
)
from deeplearning4j_tpu.nn.layers.convolution import (
    Conv1D,
    Conv2D,
    Cropping1D,
    Cropping2D,
    Deconv2D,
    DepthToSpace,
    DepthwiseConv2D,
    SeparableConv2D,
    SpaceToDepth,
    Subsampling1D,
    Subsampling2D,
    Upsampling1D,
    Upsampling2D,
    ZeroPadding1D,
    ZeroPadding2D,
)
from deeplearning4j_tpu.nn.layers.normalization import BatchNorm, LayerNorm, LocalResponseNormalization, RMSNorm
from deeplearning4j_tpu.nn.layers.attention import (
    GroupedQueryAttention,
    MultiHeadLatentAttention,
    MultiHeadAttention,
    PositionalEmbedding,
    TransformerBlock,
)
from deeplearning4j_tpu.nn.layers.moe import GatedMLP, MixtureOfExperts, SparseMoE
from deeplearning4j_tpu.nn.layers.residual import ResidualBlock
from deeplearning4j_tpu.nn.layers.mtp import MTPOutputLayer
from deeplearning4j_tpu.nn.layers.shortconv import ShortConvMixer
from deeplearning4j_tpu.nn.layers.ssm import Mamba2Mixer
from deeplearning4j_tpu.nn.layers.variational import VariationalAutoencoder
from deeplearning4j_tpu.nn.layers.objdetect import (
    Yolo2OutputLayer,
    get_predicted_objects,
    non_max_suppression,
)
from deeplearning4j_tpu.nn.layers.custom import (
    CenterLossOutputLayer,
    CnnLossLayer,
    CustomLayer,
    FrozenLayer,
    LambdaLayer,
)
from deeplearning4j_tpu.nn.layers.pooling import GlobalPooling
from deeplearning4j_tpu.nn.layers.recurrent import (
    Bidirectional,
    BidirectionalLastTimeStep,
    GravesLSTM,
    GRU,
    LastTimeStep,
    LSTM,
    MaskZero,
    RnnOutputLayer,
    SimpleRnn,
)

__all__ = [
    "ActivationLayer",
    "AlphaDropout",
    "EmbeddingSequence",
    "GaussianDropout",
    "GaussianNoise",
    "AutoEncoder",
    "Dense",
    "DropoutLayer",
    "Embedding",
    "LossLayer",
    "OutputLayer",
    "Conv1D",
    "Conv2D",
    "Deconv2D",
    "DepthwiseConv2D",
    "SeparableConv2D",
    "SpatialDropout",
    "Subsampling1D",
    "Subsampling2D",
    "Upsampling1D",
    "Upsampling2D",
    "ZeroPadding1D",
    "ZeroPadding2D",
    "Cropping1D",
    "BatchNorm",
    "LayerNorm",
    "MultiHeadAttention",
    "PositionalEmbedding",
    "TransformerBlock",
    "MixtureOfExperts",
    "SparseMoE",
    "GatedMLP",
    "MultiHeadLatentAttention",
    "MTPOutputLayer",
    "ResidualBlock",
    "Mamba2Mixer",
    "ShortConvMixer",
    "GroupedQueryAttention",
    "RMSNorm",
    "VariationalAutoencoder",
    "Yolo2OutputLayer",
    "get_predicted_objects",
    "non_max_suppression",
    "CenterLossOutputLayer",
    "CnnLossLayer",
    "CustomLayer",
    "FrozenLayer",
    "LambdaLayer",
    "LocalResponseNormalization",
    "GlobalPooling",
    "Bidirectional",
    "GravesLSTM",
    "LastTimeStep",
    "LSTM",
    "MaskZero",
    "RnnOutputLayer",
    "SimpleRnn",
]
