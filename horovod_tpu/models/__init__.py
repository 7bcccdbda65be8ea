from horovod_tpu.models.diffusion import noise_blocks
from horovod_tpu.models.resnet import ResNet, ResNet50, ResNet101, ResNet152
from horovod_tpu.models.transformer import GPT, GPTConfig
from horovod_tpu.models.vision import InceptionV3, VGG16

__all__ = ["ResNet", "ResNet50", "ResNet101", "ResNet152", "GPT",
           "GPTConfig", "VGG16", "InceptionV3", "noise_blocks"]
