"""fpca_cnn: the plain reference of its digital head, its parameters drawn
from the seed, and its head FLOPs.  The sizes are in ``fpca_cnn.json``.

The head is the repository's ``fpca_cnn`` MLP behind P2M's VWW in-pixel
layer: counts times ``input_scale``, flattened, Dense ``hidden`` with relu,
then Dense ``n_classes`` (logits).
"""

import jax
import jax.numpy as jnp

from bench import work


def zoo_cfg(cfg: dict) -> dict:
    h = cfg["head"]
    return {"arch": cfg["arch"], "hidden": h["hidden"],
            "n_classes": h["n_classes"], "input_scale": h["input_scale"]}


def make_head_params(key, cfg: dict) -> list:
    h_o, w_o, _, c = work.frontend_dims(cfg)
    d_in, hidden, n_cls = h_o * w_o * c, cfg["head"]["hidden"], cfg["head"]["n_classes"]
    bias = cfg["weights"]["bias_std"]
    k = jax.random.split(key, 4)
    return [
        {"w": jax.random.normal(k[0], (d_in, hidden)) * d_in ** -0.5,
         "b": jax.random.normal(k[1], (hidden,)) * bias},
        {"w": jax.random.normal(k[2], (hidden, n_cls)) * hidden ** -0.5,
         "b": jax.random.normal(k[3], (n_cls,)) * bias},
    ]


def head(params: list, eff, cfg: dict, precision=jax.lax.Precision.HIGHEST):
    """``(b, h_o, w_o, C)`` effective count maps -> ``(b, n_classes)`` logits."""
    dt = params[0]["w"].dtype
    x = (eff * cfg["head"]["input_scale"]).astype(dt).reshape(eff.shape[0], -1)
    x = jax.nn.relu(jnp.dot(x, params[0]["w"], precision=precision) + params[0]["b"])
    return jnp.dot(x, params[1]["w"], precision=precision) + params[1]["b"]


def head_flops(cfg: dict) -> int:
    h_o, w_o, _, c = work.frontend_dims(cfg)
    hidden, n_cls = cfg["head"]["hidden"], cfg["head"]["n_classes"]
    return work.dense_flops(h_o * w_o * c, hidden) + work.dense_flops(hidden, n_cls)
