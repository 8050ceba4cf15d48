from .vision import (build_alexnet, build_alexnet_cifar10, build_resnet50,
                     build_resnext50, build_inception_v3)  # noqa: F401
from .nlp import (TransformerConfig, BertConfig, GPTConfig, NMTConfig,
                  LlamaConfig, MixtralConfig, LatentMoEConfig,
                  JoyAIFlashRankConfig, build_transformer,
                  build_bert, build_gpt2, build_nmt, build_llama,
                  build_mixtral, build_latent_moe)  # noqa: F401
from .recsys import DLRMConfig, XDLConfig, build_dlrm, build_xdl  # noqa: F401
from .misc import (CandleConfig, MoeConfig, build_mlp, build_candle_uno,
                   build_moe_mnist)  # noqa: F401
