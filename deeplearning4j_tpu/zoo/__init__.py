"""Model zoo (``deeplearning4j/deeplearning4j-zoo``).

Each zoo class mirrors a DL4J ``org.deeplearning4j.zoo.model.*`` builder:
a named architecture with the reference hyperparameters, constructed on the
framework's own config system (GraphBuilder / ListBuilder) — so every zoo
model is also a round-trippable JSON config, exactly like upstream.

Coverage vs the upstream zoo table: complete (NASNet's skip-adjust
plumbing is simplified — see zoo/nasnet.py's docstring).
"""
from deeplearning4j_tpu.zoo.base import ZooModel
from deeplearning4j_tpu.zoo.lenet import LeNet
from deeplearning4j_tpu.zoo.alexnet import AlexNet
from deeplearning4j_tpu.zoo.vgg import VGG16, VGG19
from deeplearning4j_tpu.zoo.resnet import ResNet50
from deeplearning4j_tpu.zoo.simple_cnn import SimpleCNN
from deeplearning4j_tpu.zoo.text_generation_lstm import TextGenerationLSTM
from deeplearning4j_tpu.zoo.unet import UNet
from deeplearning4j_tpu.zoo.inception import InceptionResNetV1
from deeplearning4j_tpu.zoo.darknet import (Darknet19, TinyYOLO, YOLO2,
                                            Yolo2OutputLayer)
from deeplearning4j_tpu.zoo.facenet import FaceNetNN4Small2
from deeplearning4j_tpu.zoo.bert import Bert
from deeplearning4j_tpu.zoo.gpt import Gpt
from deeplearning4j_tpu.zoo.hybrid_decoder import HybridDecoder
from deeplearning4j_tpu.zoo.sparse_window_decoder import SparseWindowDecoder
from deeplearning4j_tpu.zoo.squeezenet import SqueezeNet
from deeplearning4j_tpu.zoo.xception import Xception
from deeplearning4j_tpu.zoo.nasnet import NASNet
from deeplearning4j_tpu.zoo.pretrained import (load_pretrained, register,
                                               save_pretrained)

__all__ = ["ZooModel", "LeNet", "AlexNet", "VGG16", "VGG19", "ResNet50",
           "SimpleCNN", "TextGenerationLSTM", "UNet", "InceptionResNetV1",
           "Darknet19", "TinyYOLO", "YOLO2", "FaceNetNN4Small2",
           "Yolo2OutputLayer", "Bert", "Gpt", "HybridDecoder", "SparseWindowDecoder",
           "SqueezeNet", "Xception", "NASNet",
           "save_pretrained", "load_pretrained", "register"]
