"""Reflection-complete serialization round-trip: EVERY Module and Criterion
class exported from bigdl_tpu.nn must round-trip through the protobuf
format (generic reflection path or wire-compat converter).

Reference strategy: utils/serializer SerializerSpec enumerates all modules
by reflection and fails on any class without a (de)serialization story.
Classes with no example entry here FAIL the completeness test.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import bigdl_tpu.nn as nn
from bigdl_tpu.nn.module import Container, Criterion, Module
from bigdl_tpu.utils.random_generator import RNG


def _r(*shape, seed=0, positive=False, scale=1.0):
    rng = np.random.default_rng(seed + sum(shape))
    a = rng.normal(size=shape).astype(np.float32) * scale
    if positive:
        a = np.abs(a) + 0.5
    return jnp.asarray(a)


def _ri(*shape, high=5, seed=0):
    rng = np.random.default_rng(seed + sum(shape))
    return jnp.asarray(rng.integers(0, high, shape).astype(np.int32))


X34 = lambda: _r(2, 3, 4)
XP = lambda: _r(2, 3, 4, positive=True)
IMG = lambda: _r(2, 6, 6, 3)
VOL = lambda: _r(2, 4, 4, 4, 2)
SEQ = lambda: _r(2, 5, 4)

# name -> (module factory, input factory).  None input => skip forward
# (architecture-only round-trip).
EXAMPLES = {
    # element-wise / simple
    "Abs": (lambda: nn.Abs(), X34),
    "ActivityRegularization": (lambda: nn.ActivityRegularization(0.01, 0.01),
                               X34),
    "AddConstant": (lambda: nn.AddConstant(1.5), X34),
    "BinaryThreshold": (lambda: nn.BinaryThreshold(0.1), X34),
    "Clamp": (lambda: nn.Clamp(-0.5, 0.5), X34),
    "Contiguous": (lambda: nn.Contiguous(), X34),
    "ELU": (lambda: nn.ELU(0.9), X34),
    "Echo": (lambda: nn.Echo(), X34),
    "Exp": (lambda: nn.Exp(), X34),
    "Flatten": (lambda: nn.Flatten(), IMG),
    "GELU": (lambda: nn.GELU(), X34),
    "GradientReversal": (lambda: nn.GradientReversal(0.5), X34),
    "HardShrink": (lambda: nn.HardShrink(0.4), X34),
    "HardSigmoid": (lambda: nn.HardSigmoid(), X34),
    "HardTanh": (lambda: nn.HardTanh(-0.7, 0.7), X34),
    "Identity": (lambda: nn.Identity(), X34),
    "LeakyReLU": (lambda: nn.LeakyReLU(0.02), X34),
    "Log": (lambda: nn.Log(), XP),
    "LogSigmoid": (lambda: nn.LogSigmoid(), X34),
    "LogSoftMax": (lambda: nn.LogSoftMax(), lambda: _r(2, 6)),
    "Masking": (lambda: nn.Masking(0.0), X34),
    "Mul": (lambda: nn.Mul(), X34),
    "MulConstant": (lambda: nn.MulConstant(2.0), X34),
    "Negative": (lambda: nn.Negative(), X34),
    "Power": (lambda: nn.Power(2.0, 1.0, 0.0), XP),
    "ReLU": (lambda: nn.ReLU(), X34),
    "ReLU6": (lambda: nn.ReLU6(), X34),
    "SiLU": (lambda: nn.SiLU(), X34),
    "Sigmoid": (lambda: nn.Sigmoid(), X34),
    "SoftMax": (lambda: nn.SoftMax(), lambda: _r(2, 6)),
    "SoftMin": (lambda: nn.SoftMin(), lambda: _r(2, 6)),
    "SoftPlus": (lambda: nn.SoftPlus(1.0), X34),
    "SoftShrink": (lambda: nn.SoftShrink(0.4), X34),
    "SoftSign": (lambda: nn.SoftSign(), X34),
    "Sqrt": (lambda: nn.Sqrt(), XP),
    "Square": (lambda: nn.Square(), X34),
    "Tanh": (lambda: nn.Tanh(), X34),
    "TanhShrink": (lambda: nn.TanhShrink(), X34),
    "Threshold": (lambda: nn.Threshold(0.1, 0.0), X34),
    # noise / dropout family
    "Dropout": (lambda: nn.Dropout(0.3), X34),
    "GaussianDropout": (lambda: nn.GaussianDropout(0.3), X34),
    "GaussianNoise": (lambda: nn.GaussianNoise(0.1), X34),
    "GaussianSampler": (lambda: nn.GaussianSampler(),
                        lambda: (_r(2, 4), _r(2, 4))),
    "RReLU": (lambda: nn.RReLU(), X34),
    "SpatialDropout1D": (lambda: nn.SpatialDropout1D(0.3), SEQ),
    "SpatialDropout2D": (lambda: nn.SpatialDropout2D(0.3), IMG),
    "SpatialDropout3D": (lambda: nn.SpatialDropout3D(0.3), VOL),
    # shaping
    "InferReshape": (lambda: nn.InferReshape((-1, 6)), lambda: _r(2, 3, 4)),
    "Narrow": (lambda: nn.Narrow(1, 0, 2), X34),
    "Pack": (lambda: nn.Pack(1), lambda: (_r(2, 4), _r(2, 4))),
    "Padding": (lambda: nn.Padding(1, 2, 0.0), X34),
    "Permute": (lambda: nn.Permute((1, 0, 2)), X34),
    "Replicate": (lambda: nn.Replicate(3, 1), X34),
    "Tile": (lambda: nn.Tile(1, 2), X34),
    "Reshape": (lambda: nn.Reshape((4, 3)), X34),
    "Reverse": (lambda: nn.Reverse(1), X34),
    "Select": (lambda: nn.Select(1, 1), X34),
    "Squeeze": (lambda: nn.Squeeze(1), lambda: _r(2, 1, 4)),
    "Sum": (lambda: nn.Sum(1), X34),
    "Max": (lambda: nn.Max(1), X34),
    "Mean": (lambda: nn.Mean(1), X34),
    "Min": (lambda: nn.Min(1), X34),
    "Transpose": (lambda: nn.Transpose([(0, 1)]), X34),
    "Unsqueeze": (lambda: nn.Unsqueeze(1), X34),
    "View": (lambda: nn.View((12,)), X34),
    "SpatialZeroPadding": (lambda: nn.SpatialZeroPadding(1, 1, 1, 1), IMG),
    "Cropping2D": (lambda: nn.Cropping2D((1, 1), (1, 1)), IMG),
    "Cropping3D": (lambda: nn.Cropping3D((1, 1), (1, 1), (1, 1)), VOL),
    # parameterised simple layers
    "BatchNormalization": (lambda: nn.BatchNormalization(4),
                           lambda: _r(3, 4)),
    "Bilinear": (lambda: nn.Bilinear(3, 4, 5),
                 lambda: (_r(2, 3), _r(2, 4))),
    "Add": (lambda: nn.Add(4), lambda: _r(2, 4)),
    "CAdd": (lambda: nn.CAdd((4,)), lambda: _r(2, 4)),
    "CMul": (lambda: nn.CMul((4,)), lambda: _r(2, 4)),
    "Cosine": (lambda: nn.Cosine(4, 3), lambda: _r(2, 4)),
    "Euclidean": (lambda: nn.Euclidean(4, 3), lambda: _r(2, 4)),
    "Highway": (lambda: nn.Highway(4), lambda: _r(2, 4)),
    "LayerNorm": (lambda: nn.LayerNorm(4), lambda: _r(2, 4)),
    "Linear": (lambda: nn.Linear(4, 3), lambda: _r(2, 4)),
    # int8 quantized twins (reference: nn/quantized/QuantSerializer.scala;
    # the pre-quantized-array constructors ARE the deserialization path)
    "QuantizedLinear": (
        lambda: nn.QuantizedLinear(
            output_size=3,
            weight_q=np.asarray(_ri(3, 4, high=127)) - 63,
            scale=np.abs(np.asarray(_r(3))) / 127.0 + 1e-4,
            bias=np.asarray(_r(3))),
        lambda: _r(2, 4)),
    "QuantizedSpatialConvolution": (
        lambda: nn.QuantizedSpatialConvolution(
            nn.SpatialConvolution(3, 4, 3, 3, 1, 1, 1, 1),
            weight_q=np.asarray(_ri(3, 3, 3, 4, high=127)) - 63,
            scale=np.abs(np.asarray(_r(4))) / 127.0 + 1e-4,
            bias=np.asarray(_r(4))),
        IMG),
    "LocallyConnected1D": (lambda: nn.LocallyConnected1D(5, 4, 3, 2), SEQ),
    "LocallyConnected2D": (
        lambda: nn.LocallyConnected2D(3, 6, 6, 4, 3, 3), IMG),
    "LookupTable": (lambda: nn.LookupTable(10, 4), lambda: _ri(2, 3)),
    "Maxout": (lambda: nn.Maxout(4, 3, 2), lambda: _r(2, 4)),
    "PReLU": (lambda: nn.PReLU(), X34),
    "RMSNorm": (lambda: nn.RMSNorm(4), lambda: _r(2, 4)),
    "ZeroCenteredGatedNorm": (lambda: nn.ZeroCenteredGatedNorm(4),
                              lambda: _r(2, 4)),
    "SReLU": (lambda: nn.SReLU(), X34),
    "Scale": (lambda: nn.Scale((4,)), lambda: _r(2, 4)),
    "Normalize": (lambda: nn.Normalize(2.0), lambda: _r(2, 4)),
    "NormalizeScale": (
        lambda: nn.NormalizeScale(2.0, scale=20.0, size=(1, 1, 1, 3)), IMG),
    "L1Penalty": (lambda: nn.L1Penalty(0.01), X34),
    "NegativeEntropyPenalty": (lambda: nn.NegativeEntropyPenalty(0.01),
                               lambda: jnp.abs(_r(2, 4)) + 0.1),
    # conv / pool
    "Conv1D": (lambda: nn.Conv1D(4, 6, 3), SEQ),
    "SpatialConvolution": (lambda: nn.SpatialConvolution(3, 4, 3, 3), IMG),
    "SpatialConvolutionMap": (
        lambda: nn.SpatialConvolutionMap([[0, 0], [1, 1], [2, 2]], 3, 3,
                                         pad_w=1, pad_h=1), IMG),
    "SpatialDilatedConvolution": (
        lambda: nn.SpatialDilatedConvolution(3, 4, 3, 3, 1, 1, 1, 1, 2, 2),
        IMG),
    "SpatialFullConvolution": (
        lambda: nn.SpatialFullConvolution(3, 4, 3, 3), IMG),
    "SpatialSeparableConvolution": (
        lambda: nn.SpatialSeparableConvolution(3, 6, 2, 3, 3), IMG),
    "SpatialShareConvolution": (
        lambda: nn.SpatialShareConvolution(3, 4, 3, 3), IMG),
    "SpatialMaxPooling": (lambda: nn.SpatialMaxPooling(2, 2, 2, 2), IMG),
    "SpatialAveragePooling": (lambda: nn.SpatialAveragePooling(2, 2, 2, 2),
                              IMG),
    "SpatialBatchNormalization": (lambda: nn.SpatialBatchNormalization(3),
                                  IMG),
    "SpatialCrossMapLRN": (lambda: nn.SpatialCrossMapLRN(5), IMG),
    "SpatialWithinChannelLRN": (lambda: nn.SpatialWithinChannelLRN(3), IMG),
    "SpatialContrastiveNormalization": (
        lambda: nn.SpatialContrastiveNormalization(3, 3), IMG),
    "SpatialSubtractiveNormalization": (
        lambda: nn.SpatialSubtractiveNormalization(3, 3), IMG),
    "SpatialDivisiveNormalization": (
        lambda: nn.SpatialDivisiveNormalization(3, 3), IMG),
    "GlobalAveragePooling2D": (lambda: nn.GlobalAveragePooling2D(), IMG),
    "GlobalMaxPooling2D": (lambda: nn.GlobalMaxPooling2D(), IMG),
    "UpSampling1D": (lambda: nn.UpSampling1D(2), SEQ),
    "UpSampling2D": (lambda: nn.UpSampling2D((2, 2)), IMG),
    "UpSampling3D": (lambda: nn.UpSampling3D((2, 2, 2)), VOL),
    "ResizeBilinear": (lambda: nn.ResizeBilinear(8, 8), IMG),
    "TemporalMaxPooling": (lambda: nn.TemporalMaxPooling(2), SEQ),
    "VolumetricConvolution": (
        lambda: nn.VolumetricConvolution(2, 3, 2, 2, 2), VOL),
    "VolumetricFullConvolution": (
        lambda: nn.VolumetricFullConvolution(2, 3, 2, 2, 2), VOL),
    "VolumetricMaxPooling": (lambda: nn.VolumetricMaxPooling(2, 2, 2), VOL),
    "VolumetricAveragePooling": (
        lambda: nn.VolumetricAveragePooling(2, 2, 2), VOL),
    "RoiPooling": (
        lambda: nn.RoiPooling(2, 2, 1.0),
        lambda: (_r(1, 8, 8, 2), jnp.asarray([[0, 0, 0, 3, 3]],
                                             jnp.float32))),
    # table ops
    "BifurcateSplitTable": (lambda: nn.BifurcateSplitTable(1), X34),
    "CAddTable": (lambda: nn.CAddTable(), lambda: (X34(), X34())),
    "CAveTable": (lambda: nn.CAveTable(), lambda: (X34(), X34())),
    "CDivTable": (lambda: nn.CDivTable(), lambda: (X34(), XP())),
    "CMaxTable": (lambda: nn.CMaxTable(), lambda: (X34(), X34())),
    "CMinTable": (lambda: nn.CMinTable(), lambda: (X34(), X34())),
    "CMulTable": (lambda: nn.CMulTable(), lambda: (X34(), X34())),
    "CSubTable": (lambda: nn.CSubTable(), lambda: (X34(), X34())),
    "CosineDistance": (lambda: nn.CosineDistance(),
                       lambda: (_r(2, 4), _r(2, 4))),
    "CrossProduct": (lambda: nn.CrossProduct(),
                     lambda: (_r(2, 4), _r(2, 4), _r(2, 4))),
    "DotProduct": (lambda: nn.DotProduct(), lambda: (_r(2, 4), _r(2, 4))),
    "FlattenTable": (lambda: nn.FlattenTable(),
                     lambda: (_r(2, 3), (_r(2, 3), _r(2, 3)))),
    "Index": (lambda: nn.Index(0), lambda: (_r(5, 3), _ri(2, high=5))),
    "JoinTable": (lambda: nn.JoinTable(1), lambda: (X34(), X34())),
    "MM": (lambda: nn.MM(), lambda: (_r(2, 3, 4), _r(2, 4, 5))),
    "MV": (lambda: nn.MV(), lambda: (_r(2, 3, 4), _r(2, 4))),
    "MaskedSelect": (
        lambda: nn.MaskedSelect(),
        lambda: (_r(2, 4), jnp.asarray([[1, 0, 1, 0], [1, 0, 1, 0]],
                                       jnp.bool_))),
    "MixtureTable": (
        lambda: nn.MixtureTable(),
        lambda: (jax.nn.softmax(_r(2, 3)), _r(2, 3, 4))),
    "NarrowTable": (lambda: nn.NarrowTable(0, 2),
                    lambda: (_r(2, 3), _r(2, 3), _r(2, 3))),
    "PairwiseDistance": (lambda: nn.PairwiseDistance(),
                         lambda: (_r(2, 4), _r(2, 4))),
    "SelectTable": (lambda: nn.SelectTable(1), lambda: (_r(2, 3), _r(2, 4))),
    "SplitTable": (lambda: nn.SplitTable(1), X34),
    "DenseToSparse": (lambda: nn.DenseToSparse(), None),
    "SparseJoinTable": (lambda: nn.SparseJoinTable(1), None),
    "SparseLinear": (lambda: nn.SparseLinear(4, 3), None),
    "LookupTableSparse": (lambda: nn.LookupTableSparse(10, 4), None),
    # containers
    "Bottle": (lambda: nn.Bottle(nn.Linear(4, 3), 2, 2), X34),
    "Concat": (lambda: nn.Concat(1).add(nn.Linear(4, 3)).add(
        nn.Linear(4, 2)), lambda: _r(2, 4)),
    "ConcatTable": (lambda: nn.ConcatTable().add(nn.Linear(4, 3)).add(
        nn.Tanh()), lambda: _r(2, 4)),
    "MapTable": (lambda: nn.MapTable(nn.Linear(4, 3)),
                 lambda: (_r(2, 4), _r(2, 4))),
    "Remat": (lambda: nn.Remat(nn.Linear(4, 3), policy="dots_saveable"),
              lambda: _r(2, 4)),
    "ScanLayers": (lambda: nn.ScanLayers(
        [nn.Linear(4, 4), nn.Linear(4, 4)], policy="nothing_saveable"),
        lambda: _r(2, 4)),
    "MultiHeadAttention": (lambda: nn.MultiHeadAttention(8, 2, causal=True),
                           lambda: _r(2, 5, 8)),
    "GroupedQueryAttention": (
        lambda: nn.GroupedQueryAttention(8, 4, 2, rope_theta=1e4),
        lambda: _r(2, 5, 8)),
    "GatedShortConv": (lambda: nn.GatedShortConv(8, 3), lambda: _r(2, 5, 8)),
    "GatedMLP": (lambda: nn.GatedMLP(8, 12), lambda: _r(2, 5, 8)),
    "DroplessMoE": (lambda: nn.DroplessMoE(8, 4, 4, 2, experts_held=(1, 2)),
                    lambda: _r(2, 5, 8)),
    "KimiDeltaAttention": (
        lambda: nn.KimiDeltaAttention(8, 2, head_dim=4, use_kernel="never"),
        lambda: _r(2, 5, 8)),
    "GatedDeltaNet": (
        lambda: nn.GatedDeltaNet(8, 1, 2, head_dim=4, use_kernel="never"),
        lambda: _r(2, 5, 8)),
    "Mamba2Mixer": (
        lambda: nn.Mamba2Mixer(8, 2, 4, state_dim=4, chunk_size=4,
                               use_kernel="never"),
        lambda: _r(2, 5, 8)),
    "LatentAttention": (
        lambda: nn.LatentAttention(8, 2, kv_rank=4, nope_dim=4, rope_dim=2,
                                   v_dim=4),
        lambda: _r(2, 5, 8)),
    "TransformerBlock": (lambda: nn.TransformerBlock(8, 2),
                         lambda: _r(2, 5, 8)),
    "TransformerLM": (lambda: nn.TransformerLM(11, 8, 2, 2, max_len=6),
                      lambda: np.arange(8, dtype=np.int32).reshape(2, 4)
                      % 11),
    "SpaceToDepthStem": (lambda: nn.SpaceToDepthStem(
        3, 8, 7, weight_init=__import__(
            "bigdl_tpu.nn.initialization", fromlist=["MsraFiller"]
        ).MsraFiller(False)), lambda: _r(2, 8, 8, 3)),
    "ParallelTable": (lambda: nn.ParallelTable().add(nn.Linear(4, 3)).add(
        nn.Tanh()), lambda: (_r(2, 4), _r(2, 3))),
    "Sequential": (lambda: nn.Sequential().add(nn.Linear(4, 3)).add(
        nn.ReLU()), lambda: _r(2, 4)),
    "TimeDistributed": (lambda: nn.TimeDistributed(nn.Linear(4, 3)), SEQ),
    # recurrent
    "RnnCell": (lambda: nn.RnnCell(4, 6), None),
    "LSTM": (lambda: nn.LSTM(4, 6), None),
    "GRU": (lambda: nn.GRU(4, 6), None),
    "LSTMPeephole": (lambda: nn.LSTMPeephole(4, 6), None),
    "Recurrent": (lambda: nn.Recurrent(nn.LSTM(4, 6)), SEQ),
    "BiRecurrent": (lambda: nn.BiRecurrent(nn.GRU(4, 6), nn.GRU(4, 6)),
                    SEQ),
    "RecurrentDecoder": (lambda: nn.RecurrentDecoder(nn.RnnCell(4, 4), 3),
                         lambda: _r(2, 4)),
    "MultiRNNCell": (lambda: nn.MultiRNNCell([nn.RnnCell(4, 6),
                                              nn.RnnCell(6, 6)]), None),
    "ConvLSTMPeephole": (
        lambda: nn.ConvLSTMPeephole(3, 4, 3, 3), None),
    "ConvLSTMPeephole3D": (
        lambda: nn.ConvLSTMPeephole3D(3, 4, 3, 3), None),
    "BinaryTreeLSTM": (lambda: nn.BinaryTreeLSTM(4, 6), None),
    # misc / detection
    "PriorBox": (lambda: nn.PriorBox([1.0], img_size=32), None),
    "Proposal": (lambda: nn.Proposal(10, 5, [0.5, 1.0], [4.0]), None),
    "DetectionOutputSSD": (lambda: nn.DetectionOutputSSD(n_classes=3), None),
    "DetectionOutputFrcnn": (
        lambda: nn.DetectionOutputFrcnn(n_classes=3), None),
    # control flow (nn/control_flow.py): Switch/Merge are no-arg graph
    # plumbing; WhileLoop/DynamicGraph carry graph topology and round-trip
    # architecture-only like the detection heads
    "Switch": (lambda: nn.Switch(), None),
    "Merge": (lambda: nn.Merge(), None),
}

CRIT_EXAMPLES = {
    "AbsCriterion": lambda: nn.AbsCriterion(),
    "BCECriterion": lambda: nn.BCECriterion(),
    "BCEWithLogitsCriterion": lambda: nn.BCEWithLogitsCriterion(),
    "CategoricalCrossEntropy": lambda: nn.CategoricalCrossEntropy(),
    "ClassNLLCriterion": lambda: nn.ClassNLLCriterion(),
    "ClassSimplexCriterion": lambda: nn.ClassSimplexCriterion(5),
    "CosineDistanceCriterion": lambda: nn.CosineDistanceCriterion(),
    "CosineEmbeddingCriterion": lambda: nn.CosineEmbeddingCriterion(0.1),
    "CosineProximityCriterion": lambda: nn.CosineProximityCriterion(),
    "CrossEntropyCriterion": lambda: nn.CrossEntropyCriterion(),
    "FusedSoftmaxCrossEntropyCriterion":
        lambda: nn.FusedSoftmaxCrossEntropyCriterion(),
    "DiceCoefficientCriterion": lambda: nn.DiceCoefficientCriterion(),
    "DistKLDivCriterion": lambda: nn.DistKLDivCriterion(),
    "DotProductCriterion": lambda: nn.DotProductCriterion(),
    "GaussianCriterion": lambda: nn.GaussianCriterion(),
    "HingeEmbeddingCriterion": lambda: nn.HingeEmbeddingCriterion(1.0),
    "KLDCriterion": lambda: nn.KLDCriterion(),
    "KullbackLeiblerDivergenceCriterion":
        lambda: nn.KullbackLeiblerDivergenceCriterion(),
    "L1Cost": lambda: nn.L1Cost(),
    "L1HingeEmbeddingCriterion": lambda: nn.L1HingeEmbeddingCriterion(1.0),
    "MSECriterion": lambda: nn.MSECriterion(),
    "MarginCriterion": lambda: nn.MarginCriterion(),
    "MarginRankingCriterion": lambda: nn.MarginRankingCriterion(),
    "MeanAbsolutePercentageCriterion":
        lambda: nn.MeanAbsolutePercentageCriterion(),
    "MeanSquaredLogarithmicCriterion":
        lambda: nn.MeanSquaredLogarithmicCriterion(),
    "MultiCriterion": lambda: nn.MultiCriterion().add(nn.MSECriterion()),
    "MultiLabelMarginCriterion": lambda: nn.MultiLabelMarginCriterion(),
    "MultiLabelSoftMarginCriterion":
        lambda: nn.MultiLabelSoftMarginCriterion(),
    "MultiMarginCriterion": lambda: nn.MultiMarginCriterion(),
    "PGCriterion": lambda: nn.PGCriterion(),
    "ParallelCriterion": lambda: nn.ParallelCriterion().add(
        nn.MSECriterion(), 0.5).add(nn.AbsCriterion(), 0.5),
    "PoissonCriterion": lambda: nn.PoissonCriterion(),
    "SmoothL1Criterion": lambda: nn.SmoothL1Criterion(),
    "SmoothL1CriterionWithWeights":
        lambda: nn.SmoothL1CriterionWithWeights(1.0),
    "SoftMarginCriterion": lambda: nn.SoftMarginCriterion(),
    "SoftmaxWithCriterion": lambda: nn.SoftmaxWithCriterion(),
    "TimeDistributedCriterion":
        lambda: nn.TimeDistributedCriterion(nn.MSECriterion()),
    "TimeDistributedMaskCriterion":
        lambda: nn.TimeDistributedMaskCriterion(nn.MSECriterion()),
    "TransformerCriterion":
        lambda: nn.TransformerCriterion(nn.MSECriterion()),
    "MultiBoxCriterion": lambda: nn.MultiBoxCriterion(3),
}

# abstract bases / helper types exempt from example coverage
EXEMPT = {"Module", "Container", "Cell", "Graph", "Criterion",
          # node-graph constructor args (serialized via the Graph topology
          # converter when embedded in a model, not constructible from
          # recorded init args alone)
          "DynamicGraph", "WhileLoop"}


def _all_module_classes():
    out = []
    for k in sorted(dir(nn)):
        v = getattr(nn, k)
        if isinstance(v, type) and issubclass(v, Module) \
                and v.__name__ == k and k not in EXEMPT:
            out.append(k)
    return out


def _all_criterion_classes():
    out = []
    for k in sorted(dir(nn)):
        v = getattr(nn, k)
        if isinstance(v, type) and issubclass(v, Criterion) \
                and v.__name__ == k and k not in EXEMPT:
            out.append(k)
    return out


class TestCompleteness:
    def test_every_module_has_an_example(self):
        missing = [k for k in _all_module_classes() if k not in EXAMPLES]
        assert not missing, (
            f"modules with no serialization example (add to EXAMPLES): "
            f"{missing}")

    def test_every_criterion_has_an_example(self):
        missing = [k for k in _all_criterion_classes()
                   if k not in CRIT_EXAMPLES]
        assert not missing, (
            f"criterions with no serialization example: {missing}")


@pytest.mark.parametrize("name", sorted(EXAMPLES))
def test_module_round_trip(name, tmp_path):
    RNG.set_seed(7)
    factory, input_factory = EXAMPLES[name]
    m = factory()
    path = str(tmp_path / f"{name}.bigdl")
    if input_factory is None:
        # architecture-only round-trip (cells / heads needing complex
        # harnesses are exercised through their wrappers elsewhere)
        m.save(path)
        m2 = Module.load(path)
        assert type(m2) is type(m)
        return
    x = input_factory()
    m.evaluate()
    y = m.forward(x)
    m.save(path)
    m2 = Module.load(path)
    m2.evaluate()
    y2 = m2.forward(x)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5), y, y2)


@pytest.mark.parametrize("name", sorted(CRIT_EXAMPLES))
def test_criterion_round_trip(name, tmp_path):
    """Criterions round-trip as constructor args of a wrapper module is the
    production path; here we round-trip the AttrValue codec directly."""
    from bigdl_tpu.interop import bigdl_pb2 as pb
    from bigdl_tpu.interop.bigdl_format import (_Ctx, _decode_value,
                                                _encode_value)

    RNG.set_seed(7)
    c = CRIT_EXAMPLES[name]()
    a = pb.AttrValue()
    _encode_value(a, c, _Ctx())
    c2 = _decode_value(a, _Ctx())
    assert type(c2) is type(c)
