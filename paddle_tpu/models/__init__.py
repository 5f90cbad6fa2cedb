"""paddle_tpu.models — model zoo (BASELINE configs).

llama: decoder LM family (config #3); gpt: decoder LM with learned
positions (config #4); bert: bidirectional encoder + MLM head
(config #2); zaya: compressed convolutional attention + top-1 routed
experts, training path only; afmoe: window and full gated attention
mixed, a sigmoid top-k router beside a shared expert, a held share of the
experts, training path only; qwen3_next: Gated DeltaNet and gated full
attention mixed 3:1, a softmax top-k router beside a gated shared expert,
a held share, training path only; minicpm_sala: lightning linear attention
and block-sparse softmax attention mixed by a published list, dense
SwiGLU, muP scalings, a held share of layers and vocabulary, training path
only; granite_hybrid: Mamba-2 state-space layers and position-free
grouped-query attention mixed by a published list, dense SwiGLU, Granite's
four multipliers, a tied head, a held share of layers and vocabulary,
training path only; smallthinker: a softmax top-k router that reads the
block's input BEFORE attention, ReGLU experts with no shared one, window
(RoPE) and position-free full attention mixed by two published lists, a
held share, training path only; vision models live in paddle_tpu.vision
(config #1).
"""
from .llama import (  # noqa: F401
    LlamaConfig,
    LlamaDecoderLayer,
    LlamaForCausalLM,
    LlamaModel,
)
from .gpt import GPTConfig, GPTForCausalLM, GPTModel  # noqa: F401
from .bert import (  # noqa: F401
    BertConfig,
    BertForMaskedLM,
    BertForSequenceClassification,
    BertModel,
)
from .zaya import (  # noqa: F401
    ZayaConfig,
    ZayaDecoderLayer,
    ZayaForCausalLM,
    ZayaModel,
)
from .afmoe import (  # noqa: F401
    AfmoeConfig,
    AfmoeDecoderLayer,
    AfmoeForCausalLM,
    AfmoeModel,
)
from .qwen3_next import (  # noqa: F401
    Qwen3NextConfig,
    Qwen3NextDecoderLayer,
    Qwen3NextForCausalLM,
    Qwen3NextModel,
)
from .minicpm_sala import (  # noqa: F401
    MiniCPMSALAConfig,
    MiniCPMSALADecoderLayer,
    MiniCPMSALAForCausalLM,
    MiniCPMSALAModel,
)
from .granite_hybrid import (  # noqa: F401
    GraniteHybridConfig,
    GraniteHybridDecoderLayer,
    GraniteHybridForCausalLM,
    GraniteHybridModel,
)
from .smallthinker import (  # noqa: F401
    SmallThinkerConfig,
    SmallThinkerDecoderLayer,
    SmallThinkerForCausalLM,
    SmallThinkerModel,
)
from .unet import UNet2DConditionModel, UNetConfig  # noqa: F401
from .generation import generate  # noqa: F401
