"""Image-classification model catalog.

Ref: models/image/imageclassification (ImageClassifier, LabelOutput,
ImageClassificationConfig.scala:33-52 — the catalog of
alexnet/inception-v1/v3/resnet-50/vgg-16/19/densenet-161/squeezenet/
mobilenet-v1/v2 + quantized variants).

TPU-first design choices (vs the reference's BigDL graphs):
- NHWC layout (Keras "tf" ordering) — the natural conv layout for XLA:TPU.
- bfloat16 compute with float32 master weights (``compute_dtype`` policy).
- Architectures are functional ``Model`` graphs; the whole forward compiles
  into one XLA program (BN fused into convs by XLA).

ResNet-50 is one of the benchmark's configurations
(``benchmark/configs/resnet50.json``; the reference's published rate is in
BASELINE.md).
"""

from __future__ import annotations

from typing import Optional, Tuple

from analytics_zoo_tpu.autograd.variable import Variable
from analytics_zoo_tpu.keras.engine.topology import Input, Model, Sequential
from analytics_zoo_tpu.keras.layers import (
    Activation,
    AveragePooling2D,
    BatchNormalization,
    Convolution2D,
    Dense,
    Dropout,
    Flatten,
    GlobalAveragePooling2D,
    MaxPooling2D,
    Merge,
    ZeroPadding2D,
)
from analytics_zoo_tpu.models.common import ZooModel


def _conv_bn(x: Variable, filters: int, kernel, stride=1, padding="same",
             activation: Optional[str] = "relu", name=None,
             momentum: float = 0.99) -> Variable:
    """``momentum`` is the Keras-1 moving-average retain factor (ref
    BatchNormalization.scala:55 default 0.99). Short training recipes (tens
    of EMA updates) leave 0.99-stats dominated by their 0/1 init at eval
    time, so the training-benchmark builders expose a ``bn_momentum`` knob
    threaded down to here."""
    x = Convolution2D(filters, kernel, subsample=stride, border_mode=padding,
                      dim_ordering="tf", bias=False,
                      name=None if name is None else f"{name}_conv")(x)
    x = BatchNormalization(dim_ordering="tf", momentum=momentum,
                           name=None if name is None else f"{name}_bn")(x)
    if activation:
        x = Activation(activation)(x)
    return x


# ---------------------------------------------------------------------------
# ResNet-50 (the benchmark architecture)
# ---------------------------------------------------------------------------


def _bottleneck(x: Variable, filters: int, stride: int, downsample: bool,
                name: str, momentum: float = 0.99) -> Variable:
    shortcut = x
    if downsample:
        shortcut = _conv_bn(x, filters * 4, (1, 1), stride=stride,
                            activation=None, name=f"{name}_proj",
                            momentum=momentum)
    y = _conv_bn(x, filters, (1, 1), stride=stride, name=f"{name}_a",
                 momentum=momentum)
    y = _conv_bn(y, filters, (3, 3), name=f"{name}_b", momentum=momentum)
    y = _conv_bn(y, filters * 4, (1, 1), activation=None, name=f"{name}_c",
                 momentum=momentum)
    out = Merge(mode="sum", name=f"{name}_add")([y, shortcut])
    return Activation("relu")(out)


def resnet_50(num_classes: int = 1000, input_shape: Tuple[int, int, int] = (224, 224, 3),
              include_top: bool = True,
              classifier_activation: Optional[str] = "softmax",
              bn_momentum: Optional[float] = None) -> Model:
    """ResNet-50 v1.5 (stride-2 in the 3x3, the standard benchmark variant).

    ``classifier_activation=None`` leaves the head as raw logits for use with
    from-logits losses (the fused softmax+CE training path). ``bn_momentum``
    overrides the Keras-1 moving-average retain factor for short recipes.
    """
    bn_momentum = 0.99 if bn_momentum is None else float(bn_momentum)
    inp = Input(shape=input_shape, name="image")
    x = _conv_bn(inp, 64, (7, 7), stride=2, name="stem", momentum=bn_momentum)
    x = MaxPooling2D((3, 3), strides=(2, 2), border_mode="same",
                     dim_ordering="tf")(x)
    blocks = [(64, 3), (128, 4), (256, 6), (512, 3)]
    for stage, (filters, reps) in enumerate(blocks):
        for i in range(reps):
            stride = 2 if (stage > 0 and i == 0) else 1
            x = _bottleneck(x, filters, stride=stride, downsample=(i == 0),
                            name=f"res{stage + 2}{chr(ord('a') + i)}",
                            momentum=bn_momentum)
    x = GlobalAveragePooling2D(dim_ordering="tf")(x)
    if include_top:
        x = Dense(num_classes, activation=classifier_activation, name="fc1000")(x)
    model = Model(inp, x, name="resnet50")
    model.compute_dtype = "bfloat16"
    return model


# ---------------------------------------------------------------------------
# LeNet-5 (the README quickstart model)
# ---------------------------------------------------------------------------


def lenet(num_classes: int = 10, input_shape=(28, 28, 1)) -> Sequential:
    """LeNet-5 (ref ImageClassification catalog 'lenet')."""
    m = Sequential(name="lenet")
    m.add(Convolution2D(6, (5, 5), activation="tanh", border_mode="same",
                        dim_ordering="tf", input_shape=input_shape))
    m.add(MaxPooling2D((2, 2), dim_ordering="tf"))
    m.add(Convolution2D(16, (5, 5), activation="tanh", dim_ordering="tf"))
    m.add(MaxPooling2D((2, 2), dim_ordering="tf"))
    m.add(Flatten())
    m.add(Dense(120, activation="tanh"))
    m.add(Dense(84, activation="tanh"))
    m.add(Dense(num_classes, activation="softmax"))
    return m


# ---------------------------------------------------------------------------
# AlexNet / VGG / MobileNet (catalog parity)
# ---------------------------------------------------------------------------


def alexnet(num_classes: int = 1000, input_shape=(227, 227, 3)) -> Sequential:
    """AlexNet (ref catalog 'alexnet')."""
    m = Sequential(name="alexnet")
    m.add(Convolution2D(96, (11, 11), subsample=4, activation="relu",
                        dim_ordering="tf", input_shape=input_shape))
    m.add(MaxPooling2D((3, 3), strides=(2, 2), dim_ordering="tf"))
    m.add(Convolution2D(256, (5, 5), activation="relu", border_mode="same",
                        dim_ordering="tf"))
    m.add(MaxPooling2D((3, 3), strides=(2, 2), dim_ordering="tf"))
    m.add(Convolution2D(384, (3, 3), activation="relu", border_mode="same",
                        dim_ordering="tf"))
    m.add(Convolution2D(384, (3, 3), activation="relu", border_mode="same",
                        dim_ordering="tf"))
    m.add(Convolution2D(256, (3, 3), activation="relu", border_mode="same",
                        dim_ordering="tf"))
    m.add(MaxPooling2D((3, 3), strides=(2, 2), dim_ordering="tf"))
    m.add(Flatten())
    m.add(Dense(4096, activation="relu"))
    m.add(Dropout(0.5))
    m.add(Dense(4096, activation="relu"))
    m.add(Dropout(0.5))
    m.add(Dense(num_classes, activation="softmax"))
    return m


def _vgg(cfg, num_classes, input_shape, name) -> Sequential:
    m = Sequential(name=name)
    first = True
    for block, convs in enumerate(cfg):
        for filters in convs:
            kw = dict(border_mode="same", activation="relu", dim_ordering="tf")
            if first:
                kw["input_shape"] = input_shape
                first = False
            m.add(Convolution2D(filters, (3, 3), **kw))
        m.add(MaxPooling2D((2, 2), dim_ordering="tf"))
    m.add(Flatten())
    m.add(Dense(4096, activation="relu"))
    m.add(Dropout(0.5))
    m.add(Dense(4096, activation="relu"))
    m.add(Dropout(0.5))
    m.add(Dense(num_classes, activation="softmax"))
    return m


def vgg16(num_classes=1000, input_shape=(224, 224, 3)) -> Sequential:
    """VGG-16 (ref catalog 'vgg-16')."""
    return _vgg([[64, 64], [128, 128], [256, 256, 256],
                 [512, 512, 512], [512, 512, 512]], num_classes, input_shape, "vgg16")


def vgg19(num_classes=1000, input_shape=(224, 224, 3)) -> Sequential:
    """VGG-19 (ref catalog 'vgg-19')."""
    return _vgg([[64, 64], [128, 128], [256, 256, 256, 256],
                 [512, 512, 512, 512], [512, 512, 512, 512]],
                num_classes, input_shape, "vgg19")


def mobilenet_v1(num_classes=1000, input_shape=(224, 224, 3), alpha=1.0) -> Model:
    """MobileNet-v1 with depthwise-separable blocks and width
    multiplier ``alpha`` (ref catalog 'mobilenet')."""
    from analytics_zoo_tpu.keras.layers import SeparableConvolution2D

    def dw_block(x, filters, stride, name):
        x = SeparableConvolution2D(int(filters * alpha), 3, 3,
                                   subsample=(stride, stride),
                                   border_mode="same", dim_ordering="tf",
                                   bias=False, name=f"{name}_sep")(x)
        x = BatchNormalization(dim_ordering="tf")(x)
        return Activation("relu")(x)

    inp = Input(shape=input_shape, name="image")
    x = _conv_bn(inp, int(32 * alpha), (3, 3), stride=2, name="stem")
    cfg = [(64, 1), (128, 2), (128, 1), (256, 2), (256, 1), (512, 2)] \
        + [(512, 1)] * 5 + [(1024, 2), (1024, 1)]
    for i, (f, s) in enumerate(cfg):
        x = dw_block(x, f, s, f"dw{i}")
    x = GlobalAveragePooling2D(dim_ordering="tf")(x)
    x = Dense(num_classes, activation="softmax")(x)
    model = Model(inp, x, name="mobilenet_v1")
    model.compute_dtype = "bfloat16"
    return model


# ---------------------------------------------------------------------------
# Inception v1 / v3 (ref ImageClassificationConfig.scala:33-52 catalog names
# "inception-v1", "inception-v3")
# ---------------------------------------------------------------------------


def _inception_v1_block(x: Variable, n1x1, n3x3r, n3x3, n5x5r, n5x5, pool_proj,
                        name: str, momentum: float = 0.99) -> Variable:
    b1 = _conv_bn(x, n1x1, (1, 1), name=f"{name}_1x1", momentum=momentum)
    b2 = _conv_bn(x, n3x3r, (1, 1), name=f"{name}_3x3r", momentum=momentum)
    b2 = _conv_bn(b2, n3x3, (3, 3), name=f"{name}_3x3", momentum=momentum)
    b3 = _conv_bn(x, n5x5r, (1, 1), name=f"{name}_5x5r", momentum=momentum)
    b3 = _conv_bn(b3, n5x5, (5, 5), name=f"{name}_5x5", momentum=momentum)
    b4 = MaxPooling2D((3, 3), strides=(1, 1), border_mode="same",
                      dim_ordering="tf")(x)
    b4 = _conv_bn(b4, pool_proj, (1, 1), name=f"{name}_pool",
                  momentum=momentum)
    return Merge(mode="concat", concat_axis=-1, name=f"{name}_out")([b1, b2, b3, b4])


def inception_v1(num_classes: int = 1000,
                 input_shape: Tuple[int, int, int] = (224, 224, 3),
                 bn_momentum: Optional[float] = None) -> Model:
    """GoogLeNet / Inception-v1 (the reference training benchmark model,
    examples/inception/Train.scala). BN variant (BN-Inception stem) — the
    TPU-friendly form; aux classifiers omitted (inference parity; the
    reference's zoo catalog model is also inference-oriented).

    ``bn_momentum`` overrides the 0.99 Keras-1 moving-average retain factor
    (useful for short recipes whose running stats would otherwise stay
    dominated by initialization at evaluation time)."""
    m = 0.99 if bn_momentum is None else float(bn_momentum)
    inp = Input(shape=input_shape, name="image")
    x = _conv_bn(inp, 64, (7, 7), stride=2, name="conv1", momentum=m)
    x = MaxPooling2D((3, 3), strides=(2, 2), border_mode="same",
                     dim_ordering="tf")(x)
    x = _conv_bn(x, 64, (1, 1), name="conv2r", momentum=m)
    x = _conv_bn(x, 192, (3, 3), name="conv2", momentum=m)
    x = MaxPooling2D((3, 3), strides=(2, 2), border_mode="same",
                     dim_ordering="tf")(x)
    x = _inception_v1_block(x, 64, 96, 128, 16, 32, 32, "mixed3a", momentum=m)
    x = _inception_v1_block(x, 128, 128, 192, 32, 96, 64, "mixed3b", momentum=m)
    x = MaxPooling2D((3, 3), strides=(2, 2), border_mode="same",
                     dim_ordering="tf")(x)
    x = _inception_v1_block(x, 192, 96, 208, 16, 48, 64, "mixed4a", momentum=m)
    x = _inception_v1_block(x, 160, 112, 224, 24, 64, 64, "mixed4b", momentum=m)
    x = _inception_v1_block(x, 128, 128, 256, 24, 64, 64, "mixed4c", momentum=m)
    x = _inception_v1_block(x, 112, 144, 288, 32, 64, 64, "mixed4d", momentum=m)
    x = _inception_v1_block(x, 256, 160, 320, 32, 128, 128, "mixed4e", momentum=m)
    x = MaxPooling2D((3, 3), strides=(2, 2), border_mode="same",
                     dim_ordering="tf")(x)
    x = _inception_v1_block(x, 256, 160, 320, 32, 128, 128, "mixed5a", momentum=m)
    x = _inception_v1_block(x, 384, 192, 384, 48, 128, 128, "mixed5b", momentum=m)
    x = GlobalAveragePooling2D(dim_ordering="tf")(x)
    x = Dropout(0.4)(x)
    x = Dense(num_classes, activation="softmax", name="logits")(x)
    model = Model(inp, x, name="inception_v1")
    model.compute_dtype = "bfloat16"
    return model


def _inc3_a(x, pool_filters, name):
    b1 = _conv_bn(x, 64, (1, 1), name=f"{name}_1x1")
    b2 = _conv_bn(x, 48, (1, 1), name=f"{name}_5x5r")
    b2 = _conv_bn(b2, 64, (5, 5), name=f"{name}_5x5")
    b3 = _conv_bn(x, 64, (1, 1), name=f"{name}_dbl_r")
    b3 = _conv_bn(b3, 96, (3, 3), name=f"{name}_dbl_1")
    b3 = _conv_bn(b3, 96, (3, 3), name=f"{name}_dbl_2")
    b4 = AveragePooling2D((3, 3), strides=(1, 1), border_mode="same",
                          dim_ordering="tf")(x)
    b4 = _conv_bn(b4, pool_filters, (1, 1), name=f"{name}_pool")
    return Merge(mode="concat", concat_axis=-1)([b1, b2, b3, b4])


def _inc3_b(x, name):  # grid reduction 35->17
    b1 = _conv_bn(x, 384, (3, 3), stride=2, padding="valid", name=f"{name}_3x3")
    b2 = _conv_bn(x, 64, (1, 1), name=f"{name}_dbl_r")
    b2 = _conv_bn(b2, 96, (3, 3), name=f"{name}_dbl_1")
    b2 = _conv_bn(b2, 96, (3, 3), stride=2, padding="valid", name=f"{name}_dbl_2")
    b3 = MaxPooling2D((3, 3), strides=(2, 2), dim_ordering="tf")(x)
    return Merge(mode="concat", concat_axis=-1)([b1, b2, b3])


def _inc3_c(x, c7, name):  # factorized 7x7
    b1 = _conv_bn(x, 192, (1, 1), name=f"{name}_1x1")
    b2 = _conv_bn(x, c7, (1, 1), name=f"{name}_7x7r")
    b2 = _conv_bn(b2, c7, (1, 7), name=f"{name}_7x7_1")
    b2 = _conv_bn(b2, 192, (7, 1), name=f"{name}_7x7_2")
    b3 = _conv_bn(x, c7, (1, 1), name=f"{name}_dbl_r")
    b3 = _conv_bn(b3, c7, (7, 1), name=f"{name}_dbl_1")
    b3 = _conv_bn(b3, c7, (1, 7), name=f"{name}_dbl_2")
    b3 = _conv_bn(b3, c7, (7, 1), name=f"{name}_dbl_3")
    b3 = _conv_bn(b3, 192, (1, 7), name=f"{name}_dbl_4")
    b4 = AveragePooling2D((3, 3), strides=(1, 1), border_mode="same",
                          dim_ordering="tf")(x)
    b4 = _conv_bn(b4, 192, (1, 1), name=f"{name}_pool")
    return Merge(mode="concat", concat_axis=-1)([b1, b2, b3, b4])


def _inc3_d(x, name):  # grid reduction 17->8
    b1 = _conv_bn(x, 192, (1, 1), name=f"{name}_3x3r")
    b1 = _conv_bn(b1, 320, (3, 3), stride=2, padding="valid", name=f"{name}_3x3")
    b2 = _conv_bn(x, 192, (1, 1), name=f"{name}_7x7r")
    b2 = _conv_bn(b2, 192, (1, 7), name=f"{name}_7x7_1")
    b2 = _conv_bn(b2, 192, (7, 1), name=f"{name}_7x7_2")
    b2 = _conv_bn(b2, 192, (3, 3), stride=2, padding="valid", name=f"{name}_7x7_3")
    b3 = MaxPooling2D((3, 3), strides=(2, 2), dim_ordering="tf")(x)
    return Merge(mode="concat", concat_axis=-1)([b1, b2, b3])


def _inc3_e(x, name):  # expanded-filter-bank output blocks
    b1 = _conv_bn(x, 320, (1, 1), name=f"{name}_1x1")
    b2 = _conv_bn(x, 384, (1, 1), name=f"{name}_3x3r")
    b2a = _conv_bn(b2, 384, (1, 3), name=f"{name}_3x3a")
    b2b = _conv_bn(b2, 384, (3, 1), name=f"{name}_3x3b")
    b2 = Merge(mode="concat", concat_axis=-1)([b2a, b2b])
    b3 = _conv_bn(x, 448, (1, 1), name=f"{name}_dbl_r")
    b3 = _conv_bn(b3, 384, (3, 3), name=f"{name}_dbl_1")
    b3a = _conv_bn(b3, 384, (1, 3), name=f"{name}_dbl_a")
    b3b = _conv_bn(b3, 384, (3, 1), name=f"{name}_dbl_b")
    b3 = Merge(mode="concat", concat_axis=-1)([b3a, b3b])
    b4 = AveragePooling2D((3, 3), strides=(1, 1), border_mode="same",
                          dim_ordering="tf")(x)
    b4 = _conv_bn(b4, 192, (1, 1), name=f"{name}_pool")
    return Merge(mode="concat", concat_axis=-1)([b1, b2, b3, b4])


def inception_v3(num_classes: int = 1000,
                 input_shape: Tuple[int, int, int] = (299, 299, 3)) -> Model:
    """Inception-v3 (ref catalog 'inception-v3'; the Inception
    training-recipe example trains this family)."""
    inp = Input(shape=input_shape, name="image")
    x = _conv_bn(inp, 32, (3, 3), stride=2, padding="valid", name="conv1a")
    x = _conv_bn(x, 32, (3, 3), padding="valid", name="conv2a")
    x = _conv_bn(x, 64, (3, 3), name="conv2b")
    x = MaxPooling2D((3, 3), strides=(2, 2), dim_ordering="tf")(x)
    x = _conv_bn(x, 80, (1, 1), padding="valid", name="conv3b")
    x = _conv_bn(x, 192, (3, 3), padding="valid", name="conv4a")
    x = MaxPooling2D((3, 3), strides=(2, 2), dim_ordering="tf")(x)
    x = _inc3_a(x, 32, "mixed0")
    x = _inc3_a(x, 64, "mixed1")
    x = _inc3_a(x, 64, "mixed2")
    x = _inc3_b(x, "mixed3")
    x = _inc3_c(x, 128, "mixed4")
    x = _inc3_c(x, 160, "mixed5")
    x = _inc3_c(x, 160, "mixed6")
    x = _inc3_c(x, 192, "mixed7")
    x = _inc3_d(x, "mixed8")
    x = _inc3_e(x, "mixed9")
    x = _inc3_e(x, "mixed10")
    x = GlobalAveragePooling2D(dim_ordering="tf")(x)
    x = Dropout(0.5)(x)
    x = Dense(num_classes, activation="softmax", name="logits")(x)
    model = Model(inp, x, name="inception_v3")
    model.compute_dtype = "bfloat16"
    return model


# ---------------------------------------------------------------------------
# DenseNet-161 / SqueezeNet / MobileNet-v2
# ---------------------------------------------------------------------------


def densenet_161(num_classes: int = 1000,
                 input_shape: Tuple[int, int, int] = (224, 224, 3),
                 growth_rate: int = 48) -> Model:
    """DenseNet-161 (catalog name "densenet-161"): blocks (6, 12, 36, 24),
    growth 48, init 96 channels, BN-ReLU-Conv pre-activation ordering."""

    def dense_layer(x, name):
        y = BatchNormalization(dim_ordering="tf", name=f"{name}_bn1")(x)
        y = Activation("relu")(y)
        y = Convolution2D(4 * growth_rate, (1, 1), dim_ordering="tf",
                          bias=False, name=f"{name}_conv1")(y)
        y = BatchNormalization(dim_ordering="tf", name=f"{name}_bn2")(y)
        y = Activation("relu")(y)
        y = Convolution2D(growth_rate, (3, 3), border_mode="same",
                          dim_ordering="tf", bias=False, name=f"{name}_conv2")(y)
        return Merge(mode="concat", concat_axis=-1)([x, y])

    def transition(x, out_ch, name):
        x = BatchNormalization(dim_ordering="tf", name=f"{name}_bn")(x)
        x = Activation("relu")(x)
        x = Convolution2D(out_ch, (1, 1), dim_ordering="tf", bias=False,
                          name=f"{name}_conv")(x)
        return AveragePooling2D((2, 2), dim_ordering="tf")(x)

    inp = Input(shape=input_shape, name="image")
    x = Convolution2D(96, (7, 7), subsample=2, border_mode="same",
                      dim_ordering="tf", bias=False, name="stem_conv")(inp)
    x = BatchNormalization(dim_ordering="tf", name="stem_bn")(x)
    x = Activation("relu")(x)
    x = MaxPooling2D((3, 3), strides=(2, 2), border_mode="same",
                     dim_ordering="tf")(x)
    channels = 96
    for bi, reps in enumerate((6, 12, 36, 24)):
        for li in range(reps):
            x = dense_layer(x, f"dense{bi + 1}_{li + 1}")
            channels += growth_rate
        if bi < 3:
            channels //= 2
            x = transition(x, channels, f"trans{bi + 1}")
    x = BatchNormalization(dim_ordering="tf", name="final_bn")(x)
    x = Activation("relu")(x)
    x = GlobalAveragePooling2D(dim_ordering="tf")(x)
    x = Dense(num_classes, activation="softmax", name="logits")(x)
    model = Model(inp, x, name="densenet_161")
    model.compute_dtype = "bfloat16"
    return model


def squeezenet(num_classes: int = 1000,
               input_shape: Tuple[int, int, int] = (227, 227, 3)) -> Model:
    """SqueezeNet v1.1 (catalog name "squeezenet")."""

    def fire(x, squeeze, expand, name):
        s = Convolution2D(squeeze, (1, 1), activation="relu",
                          dim_ordering="tf", name=f"{name}_squeeze")(x)
        e1 = Convolution2D(expand, (1, 1), activation="relu",
                           dim_ordering="tf", name=f"{name}_e1x1")(s)
        e3 = Convolution2D(expand, (3, 3), activation="relu",
                           border_mode="same", dim_ordering="tf",
                           name=f"{name}_e3x3")(s)
        return Merge(mode="concat", concat_axis=-1)([e1, e3])

    inp = Input(shape=input_shape, name="image")
    x = Convolution2D(64, (3, 3), subsample=2, activation="relu",
                      dim_ordering="tf", name="conv1")(inp)
    x = MaxPooling2D((3, 3), strides=(2, 2), dim_ordering="tf")(x)
    x = fire(x, 16, 64, "fire2")
    x = fire(x, 16, 64, "fire3")
    x = MaxPooling2D((3, 3), strides=(2, 2), dim_ordering="tf")(x)
    x = fire(x, 32, 128, "fire4")
    x = fire(x, 32, 128, "fire5")
    x = MaxPooling2D((3, 3), strides=(2, 2), dim_ordering="tf")(x)
    x = fire(x, 48, 192, "fire6")
    x = fire(x, 48, 192, "fire7")
    x = fire(x, 64, 256, "fire8")
    x = fire(x, 64, 256, "fire9")
    x = Dropout(0.5)(x)
    x = Convolution2D(num_classes, (1, 1), activation="relu",
                      dim_ordering="tf", name="conv10")(x)
    x = GlobalAveragePooling2D(dim_ordering="tf")(x)
    x = Activation("softmax")(x)
    model = Model(inp, x, name="squeezenet")
    model.compute_dtype = "bfloat16"
    return model


def mobilenet_v2(num_classes=1000, input_shape=(224, 224, 3),
                 alpha: float = 1.0) -> Model:
    """MobileNet-v2 (catalog name "mobilenet-v2"): inverted residuals with
    linear bottlenecks; ReLU6 clamps match the original recipe."""
    from analytics_zoo_tpu.keras.layers import DepthwiseConvolution2D

    def _ch(v):
        v = v * alpha
        new_v = max(8, (int(v) + 4) // 8 * 8)
        if new_v < 0.9 * v:  # make_divisible: never round down by >10%
            new_v += 8
        return new_v

    def inverted_residual(x, in_ch, out_ch, stride, expand, name):
        y = x
        hidden = in_ch * expand
        if expand != 1:
            y = Convolution2D(hidden, (1, 1), dim_ordering="tf", bias=False,
                              name=f"{name}_expand")(y)
            y = BatchNormalization(dim_ordering="tf", name=f"{name}_expand_bn")(y)
            y = Activation("relu6")(y)
        y = DepthwiseConvolution2D(3, subsample=(stride, stride),
                                   border_mode="same", dim_ordering="tf",
                                   bias=False, name=f"{name}_dw")(y)
        y = BatchNormalization(dim_ordering="tf", name=f"{name}_dw_bn")(y)
        y = Activation("relu6")(y)
        y = Convolution2D(out_ch, (1, 1), dim_ordering="tf", bias=False,
                          name=f"{name}_project")(y)
        y = BatchNormalization(dim_ordering="tf", name=f"{name}_project_bn")(y)
        if stride == 1 and in_ch == out_ch:
            y = Merge(mode="sum")([x, y])
        return y

    inp = Input(shape=input_shape, name="image")
    x = Convolution2D(_ch(32), (3, 3), subsample=2, border_mode="same",
                      dim_ordering="tf", bias=False, name="stem")(inp)
    x = BatchNormalization(dim_ordering="tf", name="stem_bn")(x)
    x = Activation("relu6")(x)
    cfg = [  # (expand, out, reps, first_stride)
        (1, 16, 1, 1), (6, 24, 2, 2), (6, 32, 3, 2), (6, 64, 4, 2),
        (6, 96, 3, 1), (6, 160, 3, 2), (6, 320, 1, 1)]
    in_ch = _ch(32)
    for bi, (t, c, n, s) in enumerate(cfg):
        for i in range(n):
            out_ch = _ch(c)
            x = inverted_residual(x, in_ch, out_ch, s if i == 0 else 1, t,
                                  f"block{bi}_{i}")
            in_ch = out_ch
    last = _ch(1280) if alpha > 1.0 else 1280
    x = Convolution2D(last, (1, 1), dim_ordering="tf", bias=False,
                      name="head_conv")(x)
    x = BatchNormalization(dim_ordering="tf", name="head_bn")(x)
    x = Activation("relu6")(x)
    x = GlobalAveragePooling2D(dim_ordering="tf")(x)
    x = Dense(num_classes, activation="softmax", name="logits")(x)
    model = Model(inp, x, name="mobilenet_v2")
    model.compute_dtype = "bfloat16"
    return model


_CATALOG = {
    "lenet": lenet,
    "alexnet": alexnet,
    "vgg-16": vgg16,
    "vgg-19": vgg19,
    "resnet-50": resnet_50,
    "inception-v1": inception_v1,
    "inception-v3": inception_v3,
    "densenet-161": densenet_161,
    "squeezenet": squeezenet,
    "mobilenet-v1": mobilenet_v1,
    "mobilenet-v2": mobilenet_v2,
}

# Quantized catalog variants (ref ImageClassificationConfig.scala:33-52 lists
# "*-quantize" names; quantization here = InferenceModel.do_quantize int8 path).
QUANTIZED_SUFFIX = "-quantize"


def build_model(name: str, num_classes: int = 1000, **kw):
    """Catalog factory (ref ImageClassificationConfig.scala:57). Accepts
    "<arch>-quantize" names (ref :33-52): the graph is identical; int8
    weights are applied at serving time via InferenceModel.do_quantize."""
    key = name.lower()
    if key.endswith(QUANTIZED_SUFFIX):
        key = key[: -len(QUANTIZED_SUFFIX)]
    if key not in _CATALOG:
        raise ValueError(f"Unknown model '{name}'. Catalog: {sorted(_CATALOG)}")
    return _CATALOG[key](num_classes=num_classes, **kw)


def load_pretrained_weights(model, path: str):
    """Pour local pretrained weights into a catalog model — the offline
    analogue of the reference's downloadable catalog
    (ImageClassificationConfig.scala:33-52; zero egress here, so the catalog
    resolves names to *architectures* and weights come from a local file).

    Accepted layouts:
    - a ``save_weights`` checkpoint (the atomic checkpoint directory, a
      legacy ``.npz`` file, or the extensionless prefix ``save_weights``
      was called with) — the framework's own format;
    - a Keras HDF5 weight file (classic or ``.weights.h5``) — mapped by
      layer name via ``Net.load_keras`` (rename your layers to the published
      names; unmatched layers are skipped so partial backbones pour too).
    Conversion recipe for other sources: torch/TF → Keras H5 or ONNX
    (``Net.load_onnx``), or run the original graph directly via
    ``Net.load_tf``.
    """
    import os

    if path.endswith((".h5", ".hdf5")):
        from analytics_zoo_tpu.net import Net

        return Net.load_keras(path, model, by_name=True, strict=False)
    # the framework's own checkpoint: the atomic directory save_weights
    # writes (callers may still name it with a legacy .npz suffix), or a
    # pre-atomic .npz file / its extensionless prefix
    base = path[:-4] if path.endswith(".npz") else path
    if (os.path.isdir(base) or os.path.exists(path)
            or os.path.exists(path + ".npz")):
        model.load_weights(path)
        return [l.name for l in model.layers() if l.weight_specs]
    raise ValueError(
        f"unrecognized weights path '{path}' (expected a save_weights "
        "checkpoint [directory, .npz, or its prefix] or a Keras .h5 file)")


class LabelOutput:
    """Ref LabelOutput.scala / pyzoo LabelOutput — a reusable transform
    from class probabilities to (label, confidence) top-N lists."""

    def __init__(self, label_map=None, top_k: int = 1):
        self.label_map = label_map
        self.top_k = top_k

    def __call__(self, probs):
        import numpy as np

        probs = np.asarray(probs)
        idx = np.argsort(-probs, axis=-1)[:, :self.top_k]
        return [[(self.label_map[int(i)] if self.label_map else int(i),
                  float(probs[r, i])) for i in ids]
                for r, ids in enumerate(idx)]


# name → (tf.keras.applications factory, keras preprocess mode). The
# preprocess mode is what the published ImageNet weights were trained with
# (keras imagenet_utils): "caffe" = RGB→BGR + mean subtraction, "tf" =
# scale to [-1, 1], "torch" = /255 + ImageNet mean/std, None = the model
# embeds its own preprocessing (EfficientNet's Rescaling/Normalization).
_KERAS_APPS = {
    "resnet-50": ("ResNet50", "caffe"),
    "vgg-16": ("VGG16", "caffe"),
    "vgg-19": ("VGG19", "caffe"),
    "inception-v3": ("InceptionV3", "tf"),
    "mobilenet-v1": ("MobileNet", "tf"),
    "mobilenet-v2": ("MobileNetV2", "tf"),
    "densenet-121": ("DenseNet121", "torch"),
    "xception": ("Xception", "tf"),
    "efficientnet-b0": ("EfficientNetB0", None),
}


def imagenet_preprocess(images, mode: Optional[str]):
    """The keras imagenet_utils preprocessing the published weights expect.
    ``images``: RGB HWC float/uint8 batch."""
    import numpy as np

    x = np.asarray(images, np.float32)
    if mode is None:
        return x
    if mode == "tf":
        return x / 127.5 - 1.0
    if mode == "torch":
        x = x / 255.0
        return (x - np.array([0.485, 0.456, 0.406], np.float32)) / \
            np.array([0.229, 0.224, 0.225], np.float32)
    if mode == "caffe":
        return x[..., ::-1] - np.array([103.939, 116.779, 123.68], np.float32)
    raise ValueError(f"unknown preprocess mode {mode!r}")


class ImageClassifier(ZooModel):
    """Ref models/image/imageclassification/ImageClassifier.scala — wraps a
    catalog architecture; predict returns class probabilities. ``weights``:
    optional local pretrained-weights path (see
    :func:`load_pretrained_weights` for accepted layouts).

    For the reference's "name → downloadable pretrained model → correct
    ImageNet label" flow (ImageClassificationConfig.scala:33-52,
    ZooModel.loadModel, ZooModel.scala:149) use
    :meth:`from_pretrained` — this environment has no network egress, so
    the download happens once on any connected machine:

    1. ``python -c "import tensorflow as tf;
       tf.keras.applications.ResNet50(weights='imagenet')
       .save('resnet50_imagenet.h5')"``  (or ``.save_weights(...)``, or
       grab the official h5 from the keras-applications release storage),
    2. copy the file over, then
       ``clf = ImageClassifier.from_pretrained("resnet-50",
       "resnet50_imagenet.h5")`` and
       ``clf.predict_labels(images, top_k=5)`` returns
       (class-name, confidence) lists via the bundled ImageNet label map.
    """

    def __init__(self, model_name: str = "resnet-50", num_classes: int = 1000,
                 weights: str = None, **build_kw):
        super().__init__()
        self.model_name = model_name
        self.num_classes = num_classes
        self._build_kw = build_kw
        self.preprocess_mode = None
        self.model = self.build_model()
        if weights:
            load_pretrained_weights(self.model, weights)

    @classmethod
    def from_pretrained(cls, model_name: str, weights: str,
                        input_shape=None) -> "ImageClassifier":
        """Build ``model_name`` carrying real pretrained ImageNet weights
        from a local file (see the class docstring for the offline
        download recipe). Accepted files:

        - a WHOLE-model Keras ``.h5`` (from ``model.save``): architecture
          and weights both come from the file via the keras converter —
          exact 1:1 predictions;
        - a weights-only Keras ``.h5`` (``save_weights`` / the official
          keras-applications release files): the matching
          ``tf.keras.applications`` architecture is built locally
          (no download), the weights poured in, and the model converted;
        - a framework ``.npz`` checkpoint: poured into the catalog
          architecture.
        """
        import h5py

        key = model_name.lower()
        self = cls.__new__(cls)
        ZooModel.__init__(self)
        self.model_name = key
        self.num_classes = 1000
        self._build_kw = {}
        self.preprocess_mode = (_KERAS_APPS[key][1]
                                if key in _KERAS_APPS else None)
        if weights.endswith((".h5", ".hdf5", ".keras")):
            from analytics_zoo_tpu.keras_convert import convert_keras_model

            with h5py.File(weights, "r") as f:
                whole_model = "model_config" in f.attrs
            if whole_model:
                from analytics_zoo_tpu.net import Net

                self.model = Net.load_keras(weights)
            else:
                if key not in _KERAS_APPS:
                    raise ValueError(
                        f"no tf.keras.applications architecture mapped for "
                        f"'{model_name}' — supply a whole-model .h5 "
                        f"(known: {sorted(_KERAS_APPS)})")
                import tensorflow as tf

                factory = getattr(tf.keras.applications, _KERAS_APPS[key][0])
                kw = {"weights": None}
                if input_shape is not None:
                    kw["input_shape"] = tuple(input_shape)
                km = factory(**kw)
                km.load_weights(weights)
                self.model = convert_keras_model(km)
        else:
            self.model = build_model(key)
            load_pretrained_weights(self.model, weights)
        return self

    def predict_labels(self, images, top_k: int = 5, batch_size: int = 32,
                       label_map=None):
        """images (RGB, HWC, the architecture's input size) → top-k
        (class-name, confidence) per image, through the bundled ImageNet
        label map and the preprocessing the weights were published with."""
        from analytics_zoo_tpu.models.image.labels import LabelReader

        x = imagenet_preprocess(images, self.preprocess_mode)
        probs = self.model.predict(x, batch_size=batch_size)
        import numpy as np

        probs = np.asarray(probs)
        if label_map is None:
            label_map = LabelReader.read_imagenet(self.model_name)
        return self.label_output(probs, label_map, top_k)

    def build_model(self):
        return build_model(self.model_name, num_classes=self.num_classes,
                           **self._build_kw)

    def config(self):
        return {"model_name": self.model_name, "num_classes": self.num_classes,
                **self._build_kw}

    def label_output(self, probs, label_map=None, top_k: int = 1):
        """Ref LabelOutput — map probabilities to (label, confidence) lists."""
        return LabelOutput(label_map, top_k)(probs)
