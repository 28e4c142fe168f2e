"""Plain float32 reference of P2M's VWW network after the in-pixel layer:
MobileNetV2's bottleneck stack (Sandler et al., arXiv:1801.04381, Table 2)
written straight from the table in ``jax.numpy``, without ``HeadGraph``.

Every contraction runs at ``Precision.HIGHEST``; the depthwise 3x3 convs are
nine shifted multiply-adds over the SAME-padded map, and the 1x1 convs are
``einsum`` contractions, so nothing here shares a lowering with the program
under test.  Parameters are a dict keyed by the zoo's node names
(``block_<i>_expand`` / ``_depthwise`` / ``_project``, ``conv_1``,
``logits``), each ``{"w", "b"}`` with conv weights ``(c_out, k, k,
c_in // groups)`` and dense weights ``(d_in, d_out)``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST

# (expansion t, output channels c, repeats n, first stride s), Table 2
TABLE2 = (
    (1, 16, 1, 1),
    (6, 24, 2, 2),
    (6, 32, 3, 2),
    (6, 64, 4, 2),
    (6, 96, 3, 1),
    (6, 160, 3, 2),
    (6, 320, 1, 1),
)
LAST = 1280


def param_shapes(c_in: int, n_classes: int) -> dict:
    """``{node: {"w": shape, "b": shape}}`` for a ``c_in``-channel count map."""
    shapes, i = {}, 0
    for t, c, n, _ in TABLE2:
        for _ in range(n):
            hidden = t * c_in
            if t != 1:
                shapes[f"block_{i}_expand"] = {"w": (hidden, 1, 1, c_in), "b": (hidden,)}
            shapes[f"block_{i}_depthwise"] = {"w": (hidden, 3, 3, 1), "b": (hidden,)}
            shapes[f"block_{i}_project"] = {"w": (c, 1, 1, hidden), "b": (c,)}
            c_in, i = c, i + 1
    shapes["conv_1"] = {"w": (LAST, 1, 1, c_in), "b": (LAST,)}
    shapes["logits"] = {"w": (LAST, n_classes), "b": (n_classes,)}
    return shapes


def linear_node(name: str) -> bool:
    """The nodes with no activation after them: the projections and the
    logits."""
    return name.endswith("_project") or name == "logits"


def make_params(key, c_in: int, n_classes: int = 2, bias_std: float = 0.1) -> dict:
    """Weights N(0, 2/fan_in) ahead of a relu6 and N(0, 1/fan_in) ahead of
    none (so each layer keeps its input's scale, as BatchNorm folded into a
    trained network's convs does), biases N(0, ``bias_std``), from
    ``key``."""
    shapes = param_shapes(c_in, n_classes)
    keys = jax.random.split(key, 2 * len(shapes))
    out = {}
    for j, (name, s) in enumerate(shapes.items()):
        w = s["w"]
        fan_in = w[0] if len(w) == 2 else w[1] * w[2] * w[3]
        gain = 1.0 if linear_node(name) else 2.0
        out[name] = {"w": jax.random.normal(keys[2 * j], w) * (gain / fan_in) ** 0.5,
                     "b": jax.random.normal(keys[2 * j + 1], s["b"]) * bias_std}
    return out


def _pointwise(x, p, precision):
    """1x1 conv: ``(b, h, w, c_in) -> (b, h, w, c_out)``."""
    return jnp.einsum("bhwi,oi->bhwo", x, p["w"][:, 0, 0, :],
                      precision=precision) + p["b"]


def _depthwise(x, p, stride: int):
    """3x3 depthwise conv, SAME padding (TF's: the extra pad row and column
    of an even map at stride 2 go to the bottom and right)."""
    _, h, w, _ = x.shape
    pads = []
    for n in (h, w):
        out = -(-n // stride)
        total = max((out - 1) * stride + 3 - n, 0)
        pads.append((total // 2, total - total // 2))
    xp = jnp.pad(x, ((0, 0), pads[0], pads[1], (0, 0)))
    h_o, w_o = -(-h // stride), -(-w // stride)
    y = 0.0
    for dy in range(3):
        for dx in range(3):
            tap = xp[:, dy: dy + (h_o - 1) * stride + 1: stride,
                     dx: dx + (w_o - 1) * stride + 1: stride, :]
            y = y + tap * p["w"][:, dy, dx, 0]
    return y + p["b"]


def forward(params: dict, counts, input_scale: float, precision=HIGHEST):
    """``(b, h, w, c)`` count maps -> ``(b, n_classes)`` logits, in the
    dtype of the parameters."""
    dt = params["logits"]["w"].dtype
    x = (counts * input_scale).astype(dt)
    relu6 = lambda v: jnp.clip(v, 0.0, 6.0)
    c_in, i = x.shape[-1], 0
    for t, c, n, s in TABLE2:
        for r in range(n):
            stride = s if r == 0 else 1
            y = x
            if t != 1:
                y = relu6(_pointwise(y, params[f"block_{i}_expand"], precision))
            y = relu6(_depthwise(y, params[f"block_{i}_depthwise"], stride))
            y = _pointwise(y, params[f"block_{i}_project"], precision)
            x = x + y if stride == 1 and c_in == c else y
            c_in, i = c, i + 1
    x = relu6(_pointwise(x, params["conv_1"], precision))
    x = jnp.mean(x, axis=(1, 2))
    return jnp.dot(x, params["logits"]["w"], precision=precision) + params["logits"]["b"]
