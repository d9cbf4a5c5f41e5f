"""Model zoo — the north-star benchmark configs (BASELINE.md):
LeNet-MNIST, VGG16, ResNet-50, GravesLSTM char-RNN, the LFM2-MoE decoder
(short convolutions, grouped-query attention, sparse experts), the Ouro
looped decoder (one stack of blocks run several times over the same
leaves, an exit gate after every pass) and the SDAR-MoE block-diffusion
decoder (a noised and a clean copy of every sequence under one mask
rule, softmax-routed experts).

The reference ships these as dl4j-examples recipes / keras-imported
models; here they are first-class builders over the same config DSL.
"""

from deeplearning4j_tpu.models.lenet import lenet  # noqa: F401
from deeplearning4j_tpu.models.vgg import vgg16  # noqa: F401
from deeplearning4j_tpu.models.resnet import resnet50  # noqa: F401
from deeplearning4j_tpu.models.charrnn import char_rnn  # noqa: F401
from deeplearning4j_tpu.models.lfm2 import lfm2_moe  # noqa: F401
from deeplearning4j_tpu.models.ouro import ouro  # noqa: F401
from deeplearning4j_tpu.models.sdar import sdar_moe  # noqa: F401
