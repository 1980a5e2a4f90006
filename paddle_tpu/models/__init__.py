from ..vision.models import (LeNet, MobileNetV1, MobileNetV2, ResNet, VGG,
                             mobilenet_v1, mobilenet_v2, resnet18, resnet34,
                             resnet50, resnet101, resnet152, vgg11, vgg13,
                             vgg16, vgg19)  # noqa: F401
from .ernie import (ErnieConfig, ErnieModel, ErnieForPretraining,
                    ErnieStageFirst, ErnieStageMiddle, ErnieStageLast,
                    ernie_pipeline_stages,
                    ErnieForSequenceClassification)  # noqa: F401
from .gpt import GPTConfig, GPTModel, GPTForCausalLM  # noqa: F401
from .generation import generate_gpt  # noqa: F401
from .retention import RetentionConfig, RetentionForCausalLM  # noqa: F401
from .yolo import YOLOv3, DarkNetTiny, yolov3_default_anchors  # noqa: F401
