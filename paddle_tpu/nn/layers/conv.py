"""Conv layers (reference: python/paddle/nn/layer/conv.py)."""
import numpy as np

from .. import functional as F
from ..layer import Layer
from .. import initializer as I


class _ConvNd(Layer):
    def __init__(self, in_channels, out_channels, kernel_size, stride, padding,
                 dilation, groups, padding_mode, weight_attr, bias_attr, data_format,
                 nd, transposed=False, output_padding=0):
        super().__init__()
        if isinstance(kernel_size, int):
            kernel_size = (kernel_size,) * nd
        self._in_channels = in_channels
        self._out_channels = out_channels
        self._kernel_size = tuple(kernel_size)
        self._stride = stride
        self._padding = padding
        self._dilation = dilation
        self._groups = groups
        self._data_format = data_format
        self._transposed = transposed
        self._output_padding = output_padding
        if transposed:
            filter_shape = [in_channels, out_channels // groups] + list(self._kernel_size)
        else:
            filter_shape = [out_channels, in_channels // groups] + list(self._kernel_size)
        fan_in = (in_channels // groups) * int(np.prod(self._kernel_size))
        self.weight = self.create_parameter(
            filter_shape, attr=weight_attr,
            default_initializer=I.KaimingUniform(fan_in=fan_in, negative_slope=np.sqrt(5.0),
                                                 nonlinearity="leaky_relu"))
        self.bias = self.create_parameter([out_channels], attr=bias_attr, is_bias=True)


class Conv1D(_ConvNd):
    def __init__(self, in_channels, out_channels, kernel_size, stride=1, padding=0,
                 dilation=1, groups=1, padding_mode="zeros", weight_attr=None,
                 bias_attr=None, data_format="NCL"):
        super().__init__(in_channels, out_channels, kernel_size, stride, padding,
                         dilation, groups, padding_mode, weight_attr, bias_attr,
                         data_format, nd=1)

    def forward(self, x):
        return F.conv1d(x, self.weight, self.bias, self._stride, self._padding,
                        self._dilation, self._groups, self._data_format)


class CausalDepthwiseConv1D(Layer):
    """``F.causal_depthwise_conv1d`` with its weight: [kernel_size,
    channels], one filter a channel over the sequence axis of [batch, seq,
    channels], and with ``bias=True`` one number a channel added to the
    taps' sum before the activation (``bias`` [channels]; Mamba-2's
    convolution has one, the linear-attention layers' and LFM2's have none).
    A row's history is its own: the ``kernel_size`` - 1 positions before a
    row's first token are zeros, whatever the batch holds before it (row r
    never reads row r - 1; a caller that carries a history across calls, as
    a decoder would, has to pass it in the row). Taps and bias start as
    torch's depthwise ``Conv1d`` starts them
    (Uniform(+-1/sqrt(kernel_size)))."""

    def __init__(self, channels, kernel_size, activation=None,
                 weight_attr=None, bias=False):
        super().__init__()
        self._activation = activation
        bound = 1.0 / np.sqrt(kernel_size)
        self.weight = self.create_parameter(
            [kernel_size, channels], attr=weight_attr,
            default_initializer=I.Uniform(-bound, bound))
        self.bias = self.create_parameter(
            [channels], default_initializer=I.Uniform(-bound, bound)
        ) if bias else None

    def forward(self, x):
        return F.causal_depthwise_conv1d(x, self.weight, self._activation,
                                         bias=self.bias)


class Conv2D(_ConvNd):
    """reference: nn/layer/conv.py Conv2D -> operators/conv_op.cc."""

    def __init__(self, in_channels, out_channels, kernel_size, stride=1, padding=0,
                 dilation=1, groups=1, padding_mode="zeros", weight_attr=None,
                 bias_attr=None, data_format="NCHW"):
        super().__init__(in_channels, out_channels, kernel_size, stride, padding,
                         dilation, groups, padding_mode, weight_attr, bias_attr,
                         data_format, nd=2)

    def forward(self, x):
        return F.conv2d(x, self.weight, self.bias, self._stride, self._padding,
                        self._dilation, self._groups, self._data_format)


class Conv3D(_ConvNd):
    def __init__(self, in_channels, out_channels, kernel_size, stride=1, padding=0,
                 dilation=1, groups=1, padding_mode="zeros", weight_attr=None,
                 bias_attr=None, data_format="NCDHW"):
        super().__init__(in_channels, out_channels, kernel_size, stride, padding,
                         dilation, groups, padding_mode, weight_attr, bias_attr,
                         data_format, nd=3)

    def forward(self, x):
        return F.conv3d(x, self.weight, self.bias, self._stride, self._padding,
                        self._dilation, self._groups, self._data_format)


class Conv1DTranspose(_ConvNd):
    def __init__(self, in_channels, out_channels, kernel_size, stride=1, padding=0,
                 output_padding=0, groups=1, dilation=1, weight_attr=None,
                 bias_attr=None, data_format="NCL"):
        super().__init__(in_channels, out_channels, kernel_size, stride, padding,
                         dilation, groups, "zeros", weight_attr, bias_attr,
                         data_format, nd=1, transposed=True,
                         output_padding=output_padding)

    def forward(self, x, output_size=None):
        return F.conv1d_transpose(x, self.weight, self.bias, self._stride,
                                  self._padding, self._output_padding,
                                  groups=self._groups,
                                  dilation=self._dilation,
                                  data_format=self._data_format,
                                  output_size=output_size)


class Conv2DTranspose(_ConvNd):
    def __init__(self, in_channels, out_channels, kernel_size, stride=1, padding=0,
                 output_padding=0, dilation=1, groups=1, weight_attr=None,
                 bias_attr=None, data_format="NCHW"):
        super().__init__(in_channels, out_channels, kernel_size, stride, padding,
                         dilation, groups, "zeros", weight_attr, bias_attr,
                         data_format, nd=2, transposed=True,
                         output_padding=output_padding)

    def forward(self, x, output_size=None):
        return F.conv2d_transpose(x, self.weight, self.bias, self._stride,
                                  self._padding, self._output_padding,
                                  groups=self._groups,
                                  dilation=self._dilation,
                                  data_format=self._data_format,
                                  output_size=output_size)


class Conv3DTranspose(_ConvNd):
    def __init__(self, in_channels, out_channels, kernel_size, stride=1,
                 padding=0, output_padding=0, dilation=1, groups=1,
                 weight_attr=None, bias_attr=None, data_format="NCDHW"):
        super().__init__(in_channels, out_channels, kernel_size, stride,
                         padding, dilation, groups, "zeros", weight_attr,
                         bias_attr, data_format, nd=3, transposed=True,
                         output_padding=output_padding)

    def forward(self, x, output_size=None):
        return F.conv3d_transpose(x, self.weight, self.bias, self._stride,
                                  self._padding, self._output_padding,
                                  groups=self._groups,
                                  dilation=self._dilation,
                                  data_format=self._data_format,
                                  output_size=output_size)
