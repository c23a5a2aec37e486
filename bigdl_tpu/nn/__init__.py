from .module import AbstractModule, Container, Sequential, Identity, Echo
from .initialization import (
    Zeros,
    Ones,
    ConstInitMethod,
    RandomUniform,
    RandomNormal,
    Xavier,
    MsraFiller,
    BilinearFiller,
)
from .linear import Highway, Linear, Maxout, SparseLinear
from .activations import (
    SReLU,
    ThresholdedReLU,
    ReLU,
    ReLU6,
    Threshold,
    Tanh,
    Sigmoid,
    HardSigmoid,
    HardTanh,
    ELU,
    SELU,
    LeakyReLU,
    PReLU,
    RReLU,
    SoftMax,
    LogSoftMax,
    SoftPlus,
    SoftSign,
    SoftMin,
    GELU,
    Swish,
)
from .conv import (
    LocallyConnected1D,
    LocallyConnected2D,
    SpatialConvolution,
    SpatialDilatedConvolution,
    SpatialFullConvolution,
    SpatialSeparableConvolution,
    TemporalConvolution,
    VolumetricConvolution,
)
from .pooling import (
    RoiPooling,
    SpatialMaxPooling,
    SpatialAveragePooling,
    SpatialAdaptiveMaxPooling,
    TemporalAveragePooling,
    TemporalMaxPooling,
    VolumetricAveragePooling,
    VolumetricMaxPooling,
)
from .structural import (
    Cropping1D,
    Cropping2D,
    Cropping3D,
    MaskedSelect,
    Replicate,
    SpaceToDepth,
    UpSampling1D,
    UpSampling2D,
    UpSampling3D,
    Reshape,
    View,
    Squeeze,
    Unsqueeze,
    Transpose,
    Contiguous,
    Narrow,
    Select,
    Index,
    Padding,
    SpatialZeroPadding,
    ZeroPadding2D,
    Masking,
    InferReshape,
    Flatten,
)
from .normalization import (
    BatchNormalization,
    SpatialBatchNormalization,
    LayerNormalization,
    RMSNorm,
    SpatialCrossMapLRN,
    SpatialWithinChannelLRN,
    Normalize,
)
from .dropout import (
    Dropout,
    SpatialDropout1D,
    SpatialDropout2D,
    SpatialDropout3D,
    GaussianNoise,
    GaussianDropout,
)
from .graph import Graph, Input, ModuleNode
from .table_ops import (
    Concat,
    ConcatTable,
    ParallelTable,
    MapTable,
    JoinTable,
    CAddTable,
    CSubTable,
    CMulTable,
    CDivTable,
    CMaxTable,
    CMinTable,
    CAveTable,
    SelectTable,
    FlattenTable,
    MixtureTable,
    DotProduct,
    CosineDistance,
    PairwiseDistance,
    MM,
    MV,
)
from .embedding import SparseJoinTable, LookupTable, LookupTableSparse, DenseToSparse
from .recurrent import (
    ConvLSTMPeephole,
    Cell,
    RnnCell,
    LSTM,
    LSTMPeephole,
    GRU,
    Recurrent,
    BiRecurrent,
    TimeDistributed,
    RecurrentDecoder,
)
from .math_ops import (
    Abs,
    Scale,
    Power,
    Square,
    Sqrt,
    Log,
    Exp,
    Clamp,
    MulConstant,
    AddConstant,
    Neg,
    Mul,
    Add,
    CMul,
    CAdd,
    Sum,
    Mean,
    Max,
    Min,
    Bilinear,
    Euclidean,
    Cosine,
)
from .criterion import (
    AbstractCriterion,
    ClassNLLCriterion,
    CrossEntropyCriterion,
    MSECriterion,
    AbsCriterion,
    SmoothL1Criterion,
    BCECriterion,
    BCECriterionWithLogits,
    DistKLDivCriterion,
    MarginRankingCriterion,
    HingeEmbeddingCriterion,
    CosineEmbeddingCriterion,
    MultiLabelSoftMarginCriterion,
    L1Cost,
    ParallelCriterion,
    MultiCriterion,
    TimeDistributedCriterion,
    TokenCrossEntropyCriterion,
    MultiTokenCrossEntropyCriterion,
    MarginCriterion,
    MultiLabelMarginCriterion,
    DiceCoefficientCriterion,
    ClassSimplexCriterion,
)
from .attention import (
    Attention,
    FeedForwardNetwork,
    Transformer,
    SequenceBeamSearch,
    sequence_beam_search,
    scaled_dot_product_attention,
    attention_bias_lower_triangle,
    padding_attention_bias,
    get_position_encoding,
)
from .moe import MoE, RoutedExperts
from .decoder import (
    DecoderBlock,
    DecoderLM,
    GatedMLP,
    GroupedQueryAttention,
    LatentAttention,
    LMHead,
    MixerBlock,
    MultiTokenPredictor,
)
from .ssm import Mamba2Mixer
from .delta import GatedDeltaNet
from .pipelined import PipelinedBlocks
from .remat import Remat
from .quantized import (
    Fp8Linear,
    Fp8SpatialConvolution,
    Fp8SpatialDilatedConvolution,
    QuantizedLinear,
    QuantizedSpatialConvolution,
    QuantizedSpatialDilatedConvolution,
    quantize,
    quantized_mode,
)
from .tree_lstm import BinaryTreeLSTM, encode_tree
from .detection import (
    Anchor,
    BoxHead,
    FPN,
    MaskHead,
    Pooler,
    RegionProposal,
    bbox_clip,
    bbox_decode,
    bbox_encode,
    bbox_iou,
    fast_rcnn_loss,
    match_targets,
    multilevel_roi_align,
    nms,
    roi_align,
    rpn_loss,
    sample_matches,
)


def load_module(path):
    """Rebuild a model saved by ``save_module`` — topology + arrays — in a
    fresh process (reference: ``Module.loadModule``)."""
    from ..utils.module_serializer import load_module_def

    return load_module_def(path)


def load_caffe(prototxt_path, weights=None):
    """Import a Caffe prototxt topology (reference: ``Module.loadCaffeModel``)."""
    from ..utils.caffe import load_caffe as _load

    return _load(prototxt_path, weights)


def load_tf(path, inputs, outputs):
    """Import a frozen TF GraphDef (reference: ``Module.loadTF``)."""
    from ..utils.tf_loader import load_tf as _load

    return _load(path, inputs, outputs)
