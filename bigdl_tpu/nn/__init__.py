"""Torch-style NN module zoo, TPU-native.

Reference surface: spark/dl/src/main/scala/com/intel/analytics/bigdl/nn/.
"""

from bigdl_tpu.nn.module import Module, Container, Criterion, Identity, child_rng
from bigdl_tpu.nn.containers import (
    Sequential, Concat, ConcatTable, ParallelTable, MapTable,
    CAddTable, CMulTable, CSubTable, CDivTable, CMaxTable, CMinTable,
    JoinTable, SelectTable, FlattenTable, Remat, ScanLayers,
    checkpoint_policy_names, resolve_checkpoint_policy,
    stack_layer_trees, unstack_layer_trees,
)
from bigdl_tpu.nn.graph import Graph, Node, Input
from bigdl_tpu.nn.linear import Linear
from bigdl_tpu.nn.quantized import (
    QuantizedLinear, QuantizedSpatialConvolution, model_bytes,
    quantize_model, quantize_params,
)
from bigdl_tpu.nn.conv import (
    SpatialConvolution, SpatialDilatedConvolution, SpatialFullConvolution,
    TemporalConvolution, Conv1D, SpaceToDepthStem, SpatialConvolutionMap,
)
from bigdl_tpu.nn.pooling import (
    SpatialMaxPooling, SpatialAveragePooling,
    GlobalAveragePooling2D, GlobalMaxPooling2D,
)
from bigdl_tpu.nn.normalization import (
    BatchNormalization, SpatialBatchNormalization, LayerNorm, RMSNorm,
    Dropout, SpatialCrossMapLRN, Normalize,
)
from bigdl_tpu.nn.attention import (
    MultiHeadAttention, GroupedQueryAttention, TransformerBlock,
    TransformerLM, rotary_embedding, stack_block_params,
    unstack_block_params,
)
from bigdl_tpu.nn.gated import GatedMLP, GatedShortConv
from bigdl_tpu.nn.moe import DroplessMoE
from bigdl_tpu.nn.generation_state import StateSpec
from bigdl_tpu.nn.latent_attention import LatentAttention
from bigdl_tpu.nn.linear_attention import KimiDeltaAttention
from bigdl_tpu.nn.state_space import Mamba2Mixer
from bigdl_tpu.nn.activations import (
    ReLU, Tanh, Sigmoid, SoftMax, SoftMin, LogSoftMax, HardTanh, Clamp,
    ReLU6, ELU, SoftPlus, SoftSign, LeakyReLU, Threshold, HardSigmoid,
    LogSigmoid, TanhShrink, SoftShrink, HardShrink, Power, Square, Sqrt,
    Abs, Exp, Log, Negative, MulConstant, AddConstant, GELU, SiLU, PReLU,
)
from bigdl_tpu.nn.reshape import (
    Reshape, View, InferReshape, Flatten, Squeeze, Unsqueeze, Transpose,
    Permute, Select, Narrow, Contiguous, Padding, Replicate, Tile,
)
from bigdl_tpu.nn.embedding import LookupTable
from bigdl_tpu.nn.recurrent import (
    Cell, RnnCell, LSTM, GRU, MultiRNNCell, Recurrent, BiRecurrent,
    RecurrentDecoder, TimeDistributed, LSTMPeephole, ConvLSTMPeephole,
    ConvLSTMPeephole3D,
)
from bigdl_tpu.nn.tree import BinaryTreeLSTM
from bigdl_tpu.nn.sparse import (
    SparseTensor, DenseToSparse, LookupTableSparse, SparseLinear,
    sparse_join, sparse_stack, sparse_recommender,
)
from bigdl_tpu.nn.detection import (
    PriorBox, Anchor, Proposal, Nms, NormalizeScale,
    DetectionOutputSSD, DetectionOutputFrcnn,
    bbox_transform_inv, clip_boxes, decode_boxes, nms,
)
from bigdl_tpu.nn.criterion import (
    ClassNLLCriterion, CrossEntropyCriterion,
    FusedSoftmaxCrossEntropyCriterion, MSECriterion, AbsCriterion,
    BCECriterion, BCEWithLogitsCriterion, SmoothL1Criterion,
    DistKLDivCriterion, MarginCriterion, HingeEmbeddingCriterion, L1Cost,
    CosineEmbeddingCriterion, KullbackLeiblerDivergenceCriterion,
    MultiLabelSoftMarginCriterion, MultiCriterion, ParallelCriterion,
    TimeDistributedCriterion,
)
from bigdl_tpu.nn.table_ops import (
    SplitTable, BifurcateSplitTable, NarrowTable, MixtureTable, DotProduct,
    CosineDistance, PairwiseDistance, MM, MV, CrossProduct, Index, Pack,
    CAveTable, Bottle, SparseJoinTable,
)
from bigdl_tpu.nn.simple_layers import (
    Add, CAdd, CMul, Mul, Scale, Bilinear, Cosine, Euclidean, Maxout, Highway,
    LocallyConnected1D, LocallyConnected2D, RReLU, SReLU, BinaryThreshold,
    GaussianDropout, GaussianNoise, GradientReversal, Masking, MaskedSelect,
    L1Penalty, ActivityRegularization, NegativeEntropyPenalty, Echo,
    SpatialDropout1D, SpatialDropout2D, SpatialDropout3D, Sum, Mean, Max,
    Min, Reverse, GaussianSampler,
)
from bigdl_tpu.nn.spatial_extras import (
    SpatialZeroPadding, Cropping2D, Cropping3D, UpSampling1D, UpSampling2D,
    UpSampling3D, ResizeBilinear, SpatialShareConvolution,
    SpatialSeparableConvolution, SpatialWithinChannelLRN,
    SpatialSubtractiveNormalization, SpatialDivisiveNormalization,
    SpatialContrastiveNormalization, RoiPooling, TemporalMaxPooling,
    VolumetricConvolution, VolumetricFullConvolution, VolumetricMaxPooling,
    VolumetricAveragePooling,
)
from bigdl_tpu.nn.criterion_extras import (
    SmoothL1CriterionWithWeights, SoftmaxWithCriterion, PGCriterion,
    CategoricalCrossEntropy, CosineDistanceCriterion,
    CosineProximityCriterion, DiceCoefficientCriterion, DotProductCriterion,
    L1HingeEmbeddingCriterion, MarginRankingCriterion,
    MeanAbsolutePercentageCriterion, MeanSquaredLogarithmicCriterion,
    MultiLabelMarginCriterion, MultiMarginCriterion, PoissonCriterion,
    SoftMarginCriterion, KLDCriterion, GaussianCriterion,
    TransformerCriterion, TimeDistributedMaskCriterion,
    ClassSimplexCriterion,
)

from bigdl_tpu.nn.control_flow import (  # noqa: E402
    DynamicGraph, Merge, Switch, WhileLoop, on_branch,
)
from bigdl_tpu.nn.multibox_loss import MultiBoxCriterion  # noqa: E402

# reference-name aliases (the underlying class covers the same surface)
from bigdl_tpu.nn.recurrent import RnnCell as RNN  # noqa: E402
from bigdl_tpu.nn.graph import Graph as StaticGraph  # noqa: E402
