"""ResNet v1/v2 (reference: mxnet/gluon/model_zoo/vision/resnet.py; the
ptrendx fork's headline benchmark model).

TPU-first: default layout NHWC (XLA-native conv layout on TPU; the
reference uses NCHW+cuDNN). BatchNorm axis follows the layout.
"""
from __future__ import annotations

from ..gluon import nn
from ..gluon.block import HybridBlock, HybridSequential
from . import register_model

__all__ = ["ResNetV1", "ResNetV2", "BasicBlockV1", "BottleneckV1",
           "BasicBlockV2", "BottleneckV2", "get_resnet",
           "resnet18_v1", "resnet34_v1", "resnet50_v1", "resnet101_v1",
           "resnet152_v1", "resnet18_v2", "resnet34_v2", "resnet50_v2",
           "resnet101_v2", "resnet152_v2"]


def _bn_axis(layout):
    return layout.index("C")


def _conv3x3(channels, stride, layout):
    return nn.Conv2D(channels, kernel_size=3, strides=stride, padding=1,
                     use_bias=False, layout=layout)


class BasicBlockV1(HybridBlock):
    def __init__(self, channels, stride, downsample=False, layout="NHWC",
                 **kwargs):
        super().__init__(**kwargs)
        ax = _bn_axis(layout)
        self.body = HybridSequential()
        self.body.add(_conv3x3(channels, stride, layout),
                      nn.BatchNorm(axis=ax), nn.Activation("relu"),
                      _conv3x3(channels, 1, layout),
                      nn.BatchNorm(axis=ax))
        if downsample:
            self.downsample = HybridSequential()
            self.downsample.add(
                nn.Conv2D(channels, kernel_size=1, strides=stride,
                          use_bias=False, layout=layout),
                nn.BatchNorm(axis=ax))
        else:
            self.downsample = None

    def forward(self, x):
        residual = x
        out = self.body(x)
        if self.downsample is not None:
            residual = self.downsample(x)
        from .. import nd
        return nd.relu(out + residual)


class BottleneckV1(HybridBlock):
    def __init__(self, channels, stride, downsample=False, layout="NHWC",
                 **kwargs):
        super().__init__(**kwargs)
        ax = _bn_axis(layout)
        self.body = HybridSequential()
        self.body.add(
            nn.Conv2D(channels // 4, kernel_size=1, strides=stride,
                      use_bias=False, layout=layout),
            nn.BatchNorm(axis=ax), nn.Activation("relu"),
            _conv3x3(channels // 4, 1, layout),
            nn.BatchNorm(axis=ax), nn.Activation("relu"),
            nn.Conv2D(channels, kernel_size=1, strides=1, use_bias=False,
                      layout=layout),
            nn.BatchNorm(axis=ax))
        if downsample:
            self.downsample = HybridSequential()
            self.downsample.add(
                nn.Conv2D(channels, kernel_size=1, strides=stride,
                          use_bias=False, layout=layout),
                nn.BatchNorm(axis=ax))
        else:
            self.downsample = None

    def forward(self, x):
        residual = x
        out = self.body(x)
        if self.downsample is not None:
            residual = self.downsample(x)
        from .. import nd
        return nd.relu(out + residual)


class BasicBlockV2(HybridBlock):
    def __init__(self, channels, stride, downsample=False, layout="NHWC",
                 **kwargs):
        super().__init__(**kwargs)
        ax = _bn_axis(layout)
        self.bn1 = nn.BatchNorm(axis=ax)
        self.conv1 = _conv3x3(channels, stride, layout)
        self.bn2 = nn.BatchNorm(axis=ax)
        self.conv2 = _conv3x3(channels, 1, layout)
        if downsample:
            self.downsample = nn.Conv2D(channels, 1, stride,
                                        use_bias=False, layout=layout)
        else:
            self.downsample = None

    def forward(self, x):
        from .. import nd
        residual = x
        x = nd.relu(self.bn1(x))
        if self.downsample is not None:
            residual = self.downsample(x)
        x = self.conv1(x)
        x = nd.relu(self.bn2(x))
        x = self.conv2(x)
        return x + residual


class BottleneckV2(HybridBlock):
    def __init__(self, channels, stride, downsample=False, layout="NHWC",
                 **kwargs):
        super().__init__(**kwargs)
        ax = _bn_axis(layout)
        self.bn1 = nn.BatchNorm(axis=ax)
        self.conv1 = nn.Conv2D(channels // 4, 1, 1, use_bias=False,
                               layout=layout)
        self.bn2 = nn.BatchNorm(axis=ax)
        self.conv2 = _conv3x3(channels // 4, stride, layout)
        self.bn3 = nn.BatchNorm(axis=ax)
        self.conv3 = nn.Conv2D(channels, 1, 1, use_bias=False,
                               layout=layout)
        if downsample:
            self.downsample = nn.Conv2D(channels, 1, stride,
                                        use_bias=False, layout=layout)
        else:
            self.downsample = None

    def forward(self, x):
        from .. import nd
        residual = x
        x = nd.relu(self.bn1(x))
        if self.downsample is not None:
            residual = self.downsample(x)
        x = self.conv1(x)
        x = nd.relu(self.bn2(x))
        x = self.conv2(x)
        x = nd.relu(self.bn3(x))
        x = self.conv3(x)
        return x + residual


class ResNetV1(HybridBlock):
    def __init__(self, block, layers, channels, classes=1000, thumbnail=False,
                 layout="NHWC", **kwargs):
        super().__init__(**kwargs)
        ax = _bn_axis(layout)
        self.features = HybridSequential()
        if thumbnail:
            self.features.add(_conv3x3(channels[0], 1, layout))
        else:
            self.features.add(
                nn.Conv2D(channels[0], 7, 2, 3, use_bias=False,
                          layout=layout),
                nn.BatchNorm(axis=ax), nn.Activation("relu"),
                nn.MaxPool2D(3, 2, 1, layout=layout))
        for i, num_layer in enumerate(layers):
            stride = 1 if i == 0 else 2
            stage = HybridSequential()
            stage.add(block(channels[i + 1], stride,
                            channels[i + 1] != channels[i], layout=layout))
            for _ in range(num_layer - 1):
                stage.add(block(channels[i + 1], 1, False, layout=layout))
            self.features.add(stage)
        self.features.add(nn.GlobalAvgPool2D(layout=layout))
        self.output = nn.Dense(classes)

    def forward(self, x):
        x = self.features(x)
        return self.output(x)


class ResNetV2(HybridBlock):
    def __init__(self, block, layers, channels, classes=1000, thumbnail=False,
                 layout="NHWC", **kwargs):
        super().__init__(**kwargs)
        ax = _bn_axis(layout)
        self.features = HybridSequential()
        self.features.add(nn.BatchNorm(axis=ax, scale=False, center=False))
        if thumbnail:
            self.features.add(_conv3x3(channels[0], 1, layout))
        else:
            self.features.add(
                nn.Conv2D(channels[0], 7, 2, 3, use_bias=False,
                          layout=layout),
                nn.BatchNorm(axis=ax), nn.Activation("relu"),
                nn.MaxPool2D(3, 2, 1, layout=layout))
        in_ch = channels[0]
        for i, num_layer in enumerate(layers):
            stride = 1 if i == 0 else 2
            stage = HybridSequential()
            stage.add(block(channels[i + 1], stride,
                            channels[i + 1] != in_ch, layout=layout))
            for _ in range(num_layer - 1):
                stage.add(block(channels[i + 1], 1, False, layout=layout))
            self.features.add(stage)
            in_ch = channels[i + 1]
        self.features.add(nn.BatchNorm(axis=ax), nn.Activation("relu"),
                          nn.GlobalAvgPool2D(layout=layout))
        self.output = nn.Dense(classes)

    def forward(self, x):
        x = self.features(x)
        return self.output(x)


_SPECS = {18: ("basic", [2, 2, 2, 2], [64, 64, 128, 256, 512]),
          34: ("basic", [3, 4, 6, 3], [64, 64, 128, 256, 512]),
          50: ("bottle", [3, 4, 6, 3], [64, 256, 512, 1024, 2048]),
          101: ("bottle", [3, 4, 23, 3], [64, 256, 512, 1024, 2048]),
          152: ("bottle", [3, 8, 36, 3], [64, 256, 512, 1024, 2048])}

_BLOCKS = {(1, "basic"): BasicBlockV1, (1, "bottle"): BottleneckV1,
           (2, "basic"): BasicBlockV2, (2, "bottle"): BottleneckV2}


def get_resnet(version, num_layers, **kwargs):
    kind, layers, channels = _SPECS[num_layers]
    block = _BLOCKS[(version, kind)]
    net_cls = ResNetV1 if version == 1 else ResNetV2
    return net_cls(block, layers, channels, **kwargs)


def _make(version, n):
    def f(**kwargs):
        return get_resnet(version, n, **kwargs)
    f.__name__ = f"resnet{n}_v{version}"
    return register_model(f.__name__)(f)


resnet18_v1 = _make(1, 18)
resnet34_v1 = _make(1, 34)
resnet50_v1 = _make(1, 50)
resnet101_v1 = _make(1, 101)
resnet152_v1 = _make(1, 152)
resnet18_v2 = _make(2, 18)
resnet34_v2 = _make(2, 34)
resnet50_v2 = _make(2, 50)
resnet101_v2 = _make(2, 101)
resnet152_v2 = _make(2, 152)
