"""Model zoo (reference ``deeplearning4j-zoo``: 13 architectures built
programmatically, ``zoo/model/*.java``)."""

from deeplearning4j_tpu.models.alexnet import AlexNet
from deeplearning4j_tpu.models.labels import (
    BaseLabels,
    COCOLabels,
    ClassPrediction,
    DarknetLabels,
    ImageNetLabels,
    VOCLabels,
)
from deeplearning4j_tpu.models.darknet import TinyYOLO, YOLO2, Darknet19
from deeplearning4j_tpu.models.decoder_lm import DecoderConfig, DecoderLM
from deeplearning4j_tpu.models.facenet import FaceNetNN4Small2, InceptionResNetV1
from deeplearning4j_tpu.models.googlenet import GoogLeNet
from deeplearning4j_tpu.models.lenet import LeNet
from deeplearning4j_tpu.models.resnet50 import ResNet50
from deeplearning4j_tpu.models.selector import ZOO, ModelSelector, PretrainedType
from deeplearning4j_tpu.models.simplecnn import SimpleCNN
from deeplearning4j_tpu.models.textgen_lstm import TextGenerationLSTM
from deeplearning4j_tpu.models.transformer_lm import TransformerLM
from deeplearning4j_tpu.models.vgg import VGG16, VGG19
from deeplearning4j_tpu.models.zoo import ZooModel

__all__ = [
    "ZooModel", "ModelSelector", "PretrainedType", "ZOO",
    "AlexNet", "Darknet19", "FaceNetNN4Small2", "GoogLeNet",
    "InceptionResNetV1", "LeNet", "ResNet50", "SimpleCNN",
    "TextGenerationLSTM", "TinyYOLO", "VGG16", "VGG19", "YOLO2",
    "TransformerLM", "DecoderLM", "DecoderConfig",
    "BaseLabels", "ClassPrediction", "ImageNetLabels", "DarknetLabels",
    "COCOLabels", "VOCLabels",
]
