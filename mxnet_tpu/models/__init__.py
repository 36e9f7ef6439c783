"""Model zoo: symbol builders for the reference's example model families
(reference example/image-classification/symbol_*.py, example/rnn/).

Each builder returns a Symbol ending in SoftmaxOutput, ready for
Module.fit. ResNet is the flagship/benchmark model (the benchmark's
ResNet-50 cells: PERF.md section 4).
"""
from .mlp import get_mlp
from .lenet import get_lenet
from .resnet import get_resnet
from .resnext import get_resnext
from .alexnet import get_alexnet
from .googlenet import get_googlenet
from .inception import get_inception_bn
from .inception_v3 import get_inception_v3
from .inception_resnet_v2 import get_inception_resnet_v2
from .vgg import get_vgg
from .lstm_lm import get_lstm_lm, lstm_lm_sym_gen
from .ssd import get_ssd_train, get_ssd_detect
from .transformer import get_transformer
