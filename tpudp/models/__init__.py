"""Model zoo: config-driven VGG family (reference parity) plus beyond-parity
ResNet, GPT-2, LLaMA, LFM2-MoE and ViT families reusing the same train/sync layers."""

from tpudp.models.vgg import VGG, VGG11, VGG13, VGG16, VGG19  # noqa: F401
from tpudp.models.resnet import ResNet, ResNet50, ResNet101, ResNet152  # noqa: F401
from tpudp.models.gpt2 import GPT2, GPT2Config, gpt2_small, gpt2_medium  # noqa: F401
from tpudp.models.llama import Llama, LlamaConfig, llama_small  # noqa: F401
from tpudp.models.lfm2 import Lfm2, Lfm2Config  # noqa: F401
from tpudp.models.vit import ViT, ViTConfig, vit_tiny, vit_small, vit_base_224  # noqa: F401
from tpudp.models.generate import beam_search, generate  # noqa: F401
