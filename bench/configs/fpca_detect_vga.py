"""fpca_detect_vga: the plain reference of the zoo's detection head, its
parameters drawn from the seed, and its head FLOPs.  The sizes are in
``fpca_detect_vga.json``.

The head is a ``width``-channel 3x3 SAME conv with relu over the count map
(times ``input_scale``), then a ``detect_kernel`` SAME conv to
``n_classes + 4`` raw channels per cell (class scores, then box values).
"""

import jax

from bench import work

_TRUNK_KERNEL = 3


def zoo_cfg(cfg: dict) -> dict:
    h = cfg["head"]
    return {"arch": cfg["arch"], "width": h["width"], "n_classes": h["n_classes"],
            "detect_kernel": h["detect_kernel"], "input_scale": h["input_scale"]}


def make_head_params(key, cfg: dict) -> dict:
    _, _, _, c = work.frontend_dims(cfg)
    width, kd = cfg["head"]["width"], cfg["head"]["detect_kernel"]
    out = cfg["head"]["n_classes"] + 4
    bias = cfg["weights"]["bias_std"]
    k = jax.random.split(key, 4)
    fan_t, fan_d = _TRUNK_KERNEL * _TRUNK_KERNEL * c, kd * kd * width
    return {
        "trunk": {"w": jax.random.normal(k[0], (width, _TRUNK_KERNEL, _TRUNK_KERNEL, c))
                  * fan_t ** -0.5,
                  "b": jax.random.normal(k[1], (width,)) * bias},
        "det": {"w": jax.random.normal(k[2], (out, kd, kd, width)) * fan_d ** -0.5,
                "b": jax.random.normal(k[3], (out,)) * bias},
    }


def _conv_same(x, p, precision):
    """NHWC input, ``(c_out, k, k, c_in)`` weights, stride 1, SAME, bias."""
    y = jax.lax.conv_general_dilated(
        x, p["w"], (1, 1), "SAME",
        dimension_numbers=("NHWC", "OHWI", "NHWC"), precision=precision)
    return y + p["b"]


def head(params: dict, eff, cfg: dict, precision=jax.lax.Precision.HIGHEST):
    """``(b, h_o, w_o, C)`` effective count maps -> ``(b, h_o, w_o, n+4)``."""
    x = (eff * cfg["head"]["input_scale"]).astype(params["trunk"]["w"].dtype)
    x = jax.nn.relu(_conv_same(x, params["trunk"], precision))
    return _conv_same(x, params["det"], precision)


def head_flops(cfg: dict) -> int:
    h_o, w_o, _, c = work.frontend_dims(cfg)
    width, kd = cfg["head"]["width"], cfg["head"]["detect_kernel"]
    out = cfg["head"]["n_classes"] + 4
    return (work.conv_flops(h_o, w_o, c, width, _TRUNK_KERNEL)
            + work.conv_flops(h_o, w_o, width, out, kd))
