"""Model zoo: the 10 instantiable reference architectures plus base/selector
(parity: deeplearning4j-zoo/.../zoo/model/ — AlexNet, FaceNetNN4Small2,
GoogLeNet, InceptionResNetV1, LeNet, ResNet50, SimpleCNN,
TextGenerationLSTM, VGG16, VGG19; ZooModel.java:40-81, ModelSelector.java).

All conv models are NHWC + bfloat16-friendly (MXU-aligned channel counts
where the original architecture allows)."""

from deeplearning4j_tpu.zoo.base import ZooModel, ModelSelector, ZooType  # noqa: F401
from deeplearning4j_tpu.zoo.decoder import CausalTransformer  # noqa: F401
from deeplearning4j_tpu.zoo.hybrid_delta import (  # noqa: F401
    HybridDeltaTransformer,
)
from deeplearning4j_tpu.zoo.latent_moe import (  # noqa: F401
    LatentMoETransformer,
)
from deeplearning4j_tpu.zoo.mamba_moe import (  # noqa: F401
    MambaMoETransformer,
)
from deeplearning4j_tpu.zoo.short_conv_moe import (  # noqa: F401
    ShortConvMoETransformer,
)
from deeplearning4j_tpu.zoo.window_moe import (  # noqa: F401
    WindowMoETransformer,
)
from deeplearning4j_tpu.zoo.models import (  # noqa: F401
    AlexNet,
    FaceNetNN4Small2,
    GoogLeNet,
    InceptionResNetV1,
    LeNet,
    ResNet50,
    SimpleCNN,
    TextGenerationLSTM,
    VGG16,
    VGG19,
)
from deeplearning4j_tpu.zoo.util.imagenet import (  # noqa: F401
    ImageNetLabels,
    decode_predictions,
)
