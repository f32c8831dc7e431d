"""paddle.nn parity surface (python/paddle/nn/__init__.py)."""


class ParamAttr:
    """python/paddle/fluid/param_attr.py ParamAttr parity."""

    def __init__(self, name=None, initializer=None, learning_rate=1.0, regularizer=None,
                 trainable=True, do_model_average=True, need_clip=True):
        self.name = name
        self.initializer = initializer
        self.learning_rate = learning_rate
        self.regularizer = regularizer
        self.trainable = trainable
        self.do_model_average = do_model_average
        self.need_clip = need_clip


from . import functional  # noqa: E402,F401
from . import initializer  # noqa: E402,F401
from .layer.layers import Layer  # noqa: E402,F401
from .layer.activation import (  # noqa: E402,F401
    CELU, ELU, GELU, GLU, Hardshrink, Hardsigmoid, Hardswish, Hardtanh, LeakyReLU,
    LogSigmoid, LogSoftmax, Maxout, Mish, PReLU, ReLU, ReLU6, RReLU, SELU, Sigmoid,
    Silu, Softmax, Softplus, Softshrink, Softsign, Swish, Tanh, Tanhshrink,
    ThresholdedReLU,
)
from .layer.common import (  # noqa: E402,F401
    AlphaDropout, Bilinear, CosineSimilarity, Dropout, Dropout2D, Dropout3D, Embedding,
    Flatten, Identity, LayerList, Linear, Pad1D, Pad2D, Pad3D, PairwiseDistance,
    ChannelShuffle, Fold, ParameterList, PixelShuffle, PixelUnshuffle, Sequential, Unfold, Upsample,
    UpsamplingBilinear2D, UpsamplingNearest2D, ZeroPad2D,
)
from .layer.conv import (  # noqa: E402,F401
    Conv1D, Conv1DTranspose, Conv2D, Conv2DTranspose, Conv3D, Conv3DTranspose,
)
from .layer.loss import (  # noqa: E402,F401
    BCELoss, BCEWithLogitsLoss, CosineEmbeddingLoss, CrossEntropyLoss, CTCLoss,
    HingeEmbeddingLoss, HSigmoidLoss, KLDivLoss, L1Loss, MarginRankingLoss, MSELoss,
    NLLLoss, SmoothL1Loss, TripletMarginLoss,
)
from .layer.norm import (  # noqa: E402,F401
    BatchNorm, BatchNorm1D, BatchNorm2D, BatchNorm3D, GroupNorm, InstanceNorm1D,
    InstanceNorm2D, InstanceNorm3D, LayerNorm, LocalResponseNorm, SpectralNorm,
    SyncBatchNorm,
)
from .layer.pooling import (  # noqa: E402,F401
    AdaptiveAvgPool1D, AdaptiveAvgPool2D, AdaptiveAvgPool3D, AdaptiveMaxPool1D,
    AdaptiveMaxPool2D, AdaptiveMaxPool3D, AvgPool1D, AvgPool2D, AvgPool3D, MaxPool1D,
    MaxPool2D, MaxPool3D, MaxUnPool2D,
)
from .layer.rnn import (  # noqa: E402,F401
    BiRNN, GRU, GRUCell, LSTM, LSTMCell, RNN, RNNCellBase, SimpleRNN, SimpleRNNCell,
)
from .layer.transformer import (  # noqa: E402,F401
    MultiHeadAttention, Transformer, TransformerDecoder, TransformerDecoderLayer,
    TransformerEncoder, TransformerEncoderLayer,
)
from .layer.moe import DroplessMoELayer, MoELayer  # noqa: E402,F401
from .clip import ClipGradByGlobalNorm, ClipGradByNorm, ClipGradByValue  # noqa: E402,F401
from .utils_weight_norm import remove_weight_norm, weight_norm  # noqa: E402,F401

# reference exposes the layer submodules at paddle.nn.<name> (nn/__init__.py
# imports them); alias ours so `from paddle.nn import loss` style works
from .layer import common, conv, loss, norm, rnn  # noqa: E402,F401
from .functional import extension  # noqa: E402,F401
from ..vision import ops as vision  # noqa: E402,F401
from .utils_weight_norm import weight_norm as weight_norm_hook  # noqa: E402,F401
from .decode import BeamSearchDecoder, dynamic_decode  # noqa: E402,F401
