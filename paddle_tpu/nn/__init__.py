"""paddle_tpu.nn — neural-network layers.

ref: python/paddle/nn/__init__.py. Layer base + the standard layer set,
functional/ namespace, initializers, grad-clip strategies.
"""
from . import functional  # noqa: F401
from . import initializer  # noqa: F401
from .clip import (  # noqa: F401
    ClipGradByGlobalNorm,
    ClipGradByNorm,
    ClipGradByValue,
)
from .layer.activation import *  # noqa: F401,F403
from .layer.common import *  # noqa: F401,F403
from .layer.container import *  # noqa: F401,F403
from .layer.conv import *  # noqa: F401,F403
from .layer.layers import Layer, Parameter  # noqa: F401
from .layer.loss import *  # noqa: F401,F403
from .layer.moe import *  # noqa: F401,F403
from .layer.norm import *  # noqa: F401,F403
from .layer.pooling import *  # noqa: F401,F403
from .layer.rnn import *  # noqa: F401,F403
from .layer.extension import *  # noqa: F401,F403
from . import quant  # noqa: F401
from .layer.transformer import *  # noqa: F401,F403

from . import utils  # noqa: F401
