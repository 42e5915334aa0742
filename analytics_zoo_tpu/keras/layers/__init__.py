"""Layer library — ref pipeline/api/keras/layers (~115 layers, SURVEY.md §2.1).

Round-1 coverage prioritizes the subset the model zoo uses; the attention
family (TransformerLayer/BERT) lives in ``attention.py``.
"""

from analytics_zoo_tpu.keras.engine.base import KerasLayer, Lambda, L1, L2, L1L2
from analytics_zoo_tpu.keras.layers.core import (
    Activation, Dense, Dropout, Flatten, Reshape, Permute, RepeatVector,
    Squeeze, ExpandDim, Masking, Select, Narrow, Merge, merge,
    LeakyReLU, ELU, ThresholdedReLU, SReLU, PReLU,
    GaussianNoise, GaussianDropout, SpatialDropout1D, SpatialDropout2D,
    get_activation,
)
from analytics_zoo_tpu.keras.layers.convolutional import (
    Convolution1D, Convolution2D, Convolution3D, Conv1D, Conv2D, Conv3D,
    AtrousConvolution2D, Deconvolution2D, SeparableConvolution2D,
    DepthwiseConvolution2D,
    MaxPooling1D, MaxPooling2D, MaxPooling3D,
    AveragePooling1D, AveragePooling2D, AveragePooling3D,
    GlobalMaxPooling1D, GlobalMaxPooling2D, GlobalMaxPooling3D,
    GlobalAveragePooling1D, GlobalAveragePooling2D, GlobalAveragePooling3D,
    ZeroPadding1D, ZeroPadding2D, ZeroPadding3D,
    Cropping1D, Cropping2D, UpSampling1D, UpSampling2D, UpSampling3D,
    LocallyConnected1D,
)
from analytics_zoo_tpu.keras.layers.normalization import (
    BatchNormalization, LayerNorm, WithinChannelLRN2D,
)
from analytics_zoo_tpu.keras.layers.embeddings import Embedding, WordEmbedding
from analytics_zoo_tpu.keras.layers.recurrent import (
    SimpleRNN, LSTM, GRU, ConvLSTM2D, Bidirectional, TimeDistributed,
    Highway, MaxoutDense,
)
from analytics_zoo_tpu.keras.layers.crf import CRF, crf_decode, crf_nll, viterbi_decode, crf_log_likelihood
from analytics_zoo_tpu.keras.layers.extras import (
    AddConstant, AtrousConvolution1D, BinaryThreshold, CAdd, CMul,
    ComputeMask, ConvLSTM3D, Cropping3D, Exp, Expand, GaussianSampler,
    GetShape,
    HardShrink, HardTanh, Identity, LRN2D, LocallyConnected2D, Log, Max,
    Mul, MulConstant, Negative, Power, RReLU, ResizeBilinear, Scale,
    SelectTable, ShareConvolution2D, SoftShrink, Softmax, SparseDense,
    SparseEmbedding, SpatialDropout3D, Sqrt, Square, Threshold,
    split_tensor,
)
from analytics_zoo_tpu.keras.layers.attention import (
    MultiHeadAttention, TransformerBlock, TransformerLayer, BERT,
)
from analytics_zoo_tpu.keras.layers.moe import MoE, SparseMoE
from analytics_zoo_tpu.keras.layers.decoder import (
    RMSNorm, SwiGLU, GroupedQueryAttention, LatentAttention, GatedShortConv,
    Mamba2Mixer, DecoderBlock, rotary_embedding,
)
from analytics_zoo_tpu.keras.engine.topology import Input, InputLayer

__all__ = [n for n in dir() if not n.startswith("_")]
