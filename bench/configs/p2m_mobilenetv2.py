"""p2m_mobilenetv2: the plain reference of P2M's VWW network behind the
in-pixel layer, its parameters drawn from the seed, its head FLOPs and the
work its roofline reads.  The sizes are in ``p2m_mobilenetv2.json``.

The head is MobileNetV2 (Sandler et al., arXiv:1801.04381, Table 2) at width
multiplier 1.0 after its stem, written straight from the table: counts times
``input_scale``; 17 inverted-residual blocks, each a 1x1 expand to ``t *
c_in`` channels with ReLU6 (none where ``t == 1``), a 3x3 depthwise conv
(stride 1 or 2, SAME) with ReLU6 and a linear 1x1 projection, plus the
block's input where the stride is 1 and the width unchanged; a 1x1 conv to
1280 with ReLU6, the mean over the map, and Dense ``n_classes`` (logits).
Depthwise convs are nine shifted multiply-adds and 1x1 convs ``einsum``
contractions, so nothing here shares a lowering with the program.
Parameters are keyed by the zoo's node names.
"""

import jax
import jax.numpy as jnp

from bench import work

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
_F32 = 4


def zoo_cfg(cfg: dict) -> dict:
    h = cfg["head"]
    return {"arch": cfg["arch"], "n_classes": h["n_classes"],
            "input_scale": h["input_scale"]}


def _same(n: int, stride: int) -> int:
    return -(-n // stride)


def layers(cfg: dict) -> list:
    """The head's parameterized layers in order: ``(node, kind, h_in, w_in,
    c_in, h_out, w_out, c_out, kernel, groups, residual)``; ``residual``
    marks a projection whose block adds its input."""
    h, w, _, c_in = work.frontend_dims(cfg)
    out, i = [], 0
    for t, c, n, s in TABLE2:
        for r in range(n):
            stride, hidden, name = s if r == 0 else 1, t * c_in, f"block_{i}"
            if t != 1:
                out.append((f"{name}_expand", "conv", h, w, c_in, h, w, hidden, 1, 1, False))
            h_o, w_o = _same(h, stride), _same(w, stride)
            out.append((f"{name}_depthwise", "conv", h, w, hidden, h_o, w_o, hidden, 3,
                        hidden, False))
            out.append((f"{name}_project", "conv", h_o, w_o, hidden, h_o, w_o, c, 1, 1,
                        stride == 1 and c_in == c))
            h, w, c_in, i = h_o, w_o, c, i + 1
    out.append(("conv_1", "conv", h, w, c_in, h, w, LAST, 1, 1, False))
    out.append(("logits", "dense", 1, 1, LAST, 1, 1, cfg["head"]["n_classes"], 1, 1, False))
    return out


def make_head_params(key, cfg: dict) -> dict:
    """Weights N(0, 2/fan_in) ahead of a ReLU6 and N(0, 1/fan_in) ahead of
    none (the projections and the logits), biases N(0, ``bias_std``)."""
    bias = cfg["weights"]["bias_std"]
    lay = layers(cfg)
    keys = jax.random.split(key, 2 * len(lay))
    params = {}
    for j, (name, kind, _, _, c_in, _, _, c_out, k, g, _) in enumerate(lay):
        shape = (c_in, c_out) if kind == "dense" else (c_out, k, k, c_in // g)
        fan_in = k * k * c_in // g
        gain = 1.0 if name.endswith("_project") or kind == "dense" else 2.0
        params[name] = {"w": jax.random.normal(keys[2 * j], shape) * (gain / fan_in) ** 0.5,
                        "b": jax.random.normal(keys[2 * j + 1], (c_out,)) * bias}
    return params


def _pointwise(x, p, precision):
    return jnp.einsum("bhwi,oi->bhwo", x, p["w"][:, 0, 0, :],
                      precision=precision) + p["b"]


def _depthwise(x, p, stride: int):
    """SAME padding as TF pads: an odd pad goes to the bottom and right."""
    _, h, w, _ = x.shape
    pads = []
    for n in (h, w):
        total = max((_same(n, stride) - 1) * stride + 3 - n, 0)
        pads.append((total // 2, total - total // 2))
    xp = jnp.pad(x, ((0, 0), pads[0], pads[1], (0, 0)))
    h_o, w_o = _same(h, stride), _same(w, stride)
    y = 0.0
    for dy in range(3):
        for dx in range(3):
            tap = xp[:, dy: dy + (h_o - 1) * stride + 1: stride,
                     dx: dx + (w_o - 1) * stride + 1: stride, :]
            y = y + tap * p["w"][:, dy, dx, 0]
    return y + p["b"]


def head(params: dict, eff, cfg: dict, precision=jax.lax.Precision.HIGHEST):
    """``(b, h_o, w_o, C)`` effective count maps -> ``(b, n_classes)``
    logits, in the dtype of the parameters."""
    dt = params["logits"]["w"].dtype
    x = (eff * cfg["head"]["input_scale"]).astype(dt)
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


def head_flops(cfg: dict) -> int:
    """``2 * MACs`` of one frame: ``h_o * w_o * k * k * (c_in / groups) *
    c_out`` per conv, ``d_in * d_out`` for the logits."""
    return sum(2 * ho * wo * k * k * (ci // g) * co
               for _, _, _, _, ci, ho, wo, co, k, g, _ in layers(cfg))


def head_work(cfg: dict, frames: int) -> tuple[float, float]:
    """``(flops, bytes)`` the head needs for ``frames`` frames, from unpadded
    shapes: the FLOPs of :func:`head_flops`, and the bytes no implementation
    can avoid, the float32 effective map read and the float32 logits
    written.  The weights (8.8 MB, read once a call, not once a frame) are
    not counted, nor are intermediate maps: XLA keeps them in bfloat16 and
    fuses each depthwise conv with its neighbours, so counting every layer's
    float32 input and output (49 MB a frame) would put the bound above what
    the chip moves."""
    h, w, _, c = work.frontend_dims(cfg)
    nbytes = _F32 * (h * w * c + cfg["head"]["n_classes"])
    return float(head_flops(cfg)) * frames, float(nbytes) * frames
