"""Model zoo (BigDL models/ — SURVEY.md §2.4)."""
from bigdl_tpu.models.lenet import LeNet5
from bigdl_tpu.models.vgg import VggForCifar10, Vgg_16, Vgg_19
from bigdl_tpu.models.resnet import ResNet
from bigdl_tpu.models.inception import (
    Inception_v1, Inception_v1_NoAuxClassifier, Inception_v2,
    Inception_v2_NoAuxClassifier)
from bigdl_tpu.models.alexnet import AlexNet, AlexNet_OWT
from bigdl_tpu.models.rnn import SimpleRNN, PTBModel
from bigdl_tpu.models.autoencoder import Autoencoder
from bigdl_tpu.models.transformer import (TransformerBlock, TransformerLM,
                                          FeedForward)
from bigdl_tpu.models.transformer.pipelined import PipelinedTransformerLM
from bigdl_tpu.models.transformer.decoder import PatternDecoderLM
