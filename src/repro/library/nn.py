"""Neural-network Library Nodes (paper §5, DaCeML/ONNX analogue).

Operators used by the LeNet-5 case study, each with multi-level expansions:
``xla`` composites, and for the compute hot-spots (Conv2d, Linear) a
``pallas`` expansion lowering to the im2col + systolic-GEMM kernel — the
paper's §5.2 'convolutions are implemented using the im2col approach,
relying heavily on the systolic matrix multiplication of §2.6'.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..codegen.device import resolve_interpret
from ..codegen.pipeline_fusion import register_fusion
from ..core.sdfg import LibraryNode, SDFG, State
from .util import replace_with_tasklet


# ---------------------------------------------------------------------------
def _im2col(x, R, S):
    """x: (N, C, H, W) -> patches (N*OH*OW, C*R*S) for VALID conv."""
    N, C, H, W = x.shape
    OH, OW = H - R + 1, W - S + 1
    idx_h = jnp.arange(OH)[:, None] + jnp.arange(R)[None, :]
    idx_w = jnp.arange(OW)[:, None] + jnp.arange(S)[None, :]
    # (N, C, OH, R, W)
    g = x[:, :, idx_h, :]
    # (N, C, OH, R, OW, S)
    g = g[:, :, :, :, idx_w]
    # -> (N, OH, OW, C, R, S)
    g = g.transpose(0, 2, 4, 1, 3, 5)
    return g.reshape(N * OH * OW, C * R * S), (N, OH, OW)


class Conv2d(LibraryNode):
    """VALID 2D convolution, NCHW, weights (K, C, R, S) + bias (K,)."""
    default_expansion = "xla"

    def __init__(self, name="conv", activation: str = None):
        super().__init__(name, inputs=["x", "W", "b"], outputs=["y"])
        self.activation = activation


def _conv_xla(node: Conv2d, sdfg: SDFG, state: State):
    act = node.activation

    def fn(x, W, b):
        y = jax.lax.conv_general_dilated(
            x.astype(jnp.float32), W.astype(jnp.float32),
            window_strides=(1, 1), padding="VALID",
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        y = y + b.astype(jnp.float32)[None, :, None, None]
        if act == "relu":
            y = jnp.maximum(y, 0.0)
        return y.astype(x.dtype)

    replace_with_tasklet(node, sdfg, state, fn, "xla")


def _conv_pallas(node: Conv2d, sdfg: SDFG, state: State):
    """im2col + systolic GEMM with fused bias(+activation) epilogue."""
    act = node.activation
    interpret = resolve_interpret(sdfg.metadata.get("pallas_interpret"))

    def fn(x, W, b):
        from ..kernels.gemm import matmul
        K, C, R, S = W.shape
        cols, (N, OH, OW) = _im2col(x, R, S)
        w2 = W.reshape(K, C * R * S).T
        y = matmul(cols, w2, b, activation=act, interpret=interpret)
        return y.reshape(N, OH, OW, K).transpose(0, 3, 1, 2)

    replace_with_tasklet(node, sdfg, state, fn, "pallas")


Conv2d.expansions = {"xla": _conv_xla, "generic": _conv_xla,
                     "pallas": _conv_pallas}


# ---------------------------------------------------------------------------
class Relu(LibraryNode):
    default_expansion = "xla"

    def __init__(self, name="relu"):
        super().__init__(name, inputs=["x"], outputs=["y"])


def _relu_xla(node: Relu, sdfg: SDFG, state: State):
    replace_with_tasklet(node, sdfg, state,
                         lambda x: jnp.maximum(x, 0), "xla")


Relu.expansions = {"xla": _relu_xla, "generic": _relu_xla,
                   "pallas": _relu_xla}


# ---------------------------------------------------------------------------
class MaxPool2d(LibraryNode):
    """Window=stride pooling via sliding window (paper §5.2: implemented
    with shift registers on Intel; reduce_window on TPU)."""
    default_expansion = "xla"

    def __init__(self, name="maxpool", window: int = 2):
        super().__init__(name, inputs=["x"], outputs=["y"])
        self.window = window

    def out_shape(self, in_shape):
        n, c, h, w = in_shape
        return (n, c, h // self.window, w // self.window)


def _maxpool_xla(node: MaxPool2d, sdfg: SDFG, state: State):
    wdw = node.window

    def fn(x):
        return jax.lax.reduce_window(
            x, -jnp.inf if x.dtype.kind == "f" else x.dtype.type(-2**31),
            jax.lax.max, (1, 1, wdw, wdw), (1, 1, wdw, wdw), "VALID")

    replace_with_tasklet(node, sdfg, state, fn, "xla")


MaxPool2d.expansions = {"xla": _maxpool_xla, "generic": _maxpool_xla,
                        "pallas": _maxpool_xla}


# ---------------------------------------------------------------------------
class Linear(LibraryNode):
    """y = act(x @ W^T + b); W: (out, in)."""
    default_expansion = "xla"

    def __init__(self, name="linear", activation: str = None):
        super().__init__(name, inputs=["x", "W", "b"], outputs=["y"])
        self.activation = activation


def _linear_xla(node: Linear, sdfg: SDFG, state: State):
    act = node.activation

    def fn(x, W, b):
        y = x.astype(jnp.float32) @ W.astype(jnp.float32).T \
            + b.astype(jnp.float32)
        if act == "relu":
            y = jnp.maximum(y, 0.0)
        return y.astype(x.dtype)

    replace_with_tasklet(node, sdfg, state, fn, "xla")


def _linear_pallas(node: Linear, sdfg: SDFG, state: State):
    act = node.activation
    interpret = resolve_interpret(sdfg.metadata.get("pallas_interpret"))

    def fn(x, W, b):
        from ..kernels.gemm import matmul
        return matmul(x, W.T, b, activation=act, interpret=interpret)

    replace_with_tasklet(node, sdfg, state, fn, "pallas")


Linear.expansions = {"xla": _linear_xla, "generic": _linear_xla,
                     "pallas": _linear_pallas}


# ---------------------------------------------------------------------------
class Softmax(LibraryNode):
    default_expansion = "xla"

    def __init__(self, name="softmax", axis: int = -1):
        super().__init__(name, inputs=["x"], outputs=["y"])
        self.axis = axis


def _softmax_xla(node: Softmax, sdfg: SDFG, state: State):
    axis = node.axis
    replace_with_tasklet(node, sdfg, state,
                         lambda x: jax.nn.softmax(x, axis=axis), "xla")


Softmax.expansions = {"xla": _softmax_xla, "generic": _softmax_xla,
                      "pallas": _softmax_xla}


# ---------------------------------------------------------------------------
class Flatten(LibraryNode):
    default_expansion = "xla"

    def __init__(self, name="flatten"):
        super().__init__(name, inputs=["x"], outputs=["y"])


def _flatten_xla(node: Flatten, sdfg: SDFG, state: State):
    replace_with_tasklet(node, sdfg, state,
                         lambda x: x.reshape(x.shape[0], -1), "xla")


Flatten.expansions = {"xla": _flatten_xla, "generic": _flatten_xla,
                      "pallas": _flatten_xla}


# ---------------------------------------------------------------------------
# Fused pipelines (paper Fig. 16: streaming between Conv/ReLU/MaxPool).
# Conv2d carries its own activation; a streamed Conv2d->MaxPool2d chain
# fuses into im2col-GEMM + pooling without materializing the conv output.
# ---------------------------------------------------------------------------
@register_fusion(("Conv2d", "MaxPool2d"))
def _fuse_conv_pool(chain, sdfg, state, interpret, in_map, out_map):
    conv_n, pool_n = chain
    act = conv_n.activation
    wdw = pool_n.window
    x_c = in_map[(conv_n.label, "x")]
    W_c = in_map[(conv_n.label, "W")]
    b_c = in_map[(conv_n.label, "b")]
    y_c = out_map[(pool_n.label, "y")]

    def fn(**kw):
        from ..kernels.gemm import matmul
        x, W, b = kw[x_c], kw[W_c], kw[b_c]
        K, C, R, S = W.shape
        cols, (N, OH, OW) = _im2col(x, R, S)
        y = matmul(cols, W.reshape(K, C * R * S).T, b, activation=act,
                   interpret=interpret)
        y = y.reshape(N, OH, OW, K).transpose(0, 3, 1, 2)
        y = jax.lax.reduce_window(y, -jnp.inf, jax.lax.max,
                                  (1, 1, wdw, wdw), (1, 1, wdw, wdw), "VALID")
        return {y_c: y}

    return fn
