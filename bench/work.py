"""Operations and bytes that the algorithm needs, from unpadded shapes.

Counts come from the configuration file, never from the compiled program, so
they read the same whatever backend, tiling or padding implements a layer.

Frontend kernel (``kernels/fpca_conv``): each kept window reads its
``N = max_kernel**2 * in_channels`` photocurrents and writes ``C`` counts.
The bucket-select surfaces factor into power-basis contractions; the
operations that are dot products are the three ``(a, b) >= 1`` monomial pairs
of the degree-3 bucket surfaces for both weight phases (``12 N C``), the
three per-window power sums (``6 N``) and the step-1 estimate on the
``T_avg`` monomials of the average surface for both phases (``4 T_avg C``).
The sigmoid bucket gates are elementwise and are not counted.  Bytes are the
patches in and the counts out, in float32; the per-launch weight planes
(about 10 KB) are not counted.

Model FLOPs (for ``mfu``) are those of the dense model on one frame: the
signed analog convolution over every window (``2 M N C``) plus the digital
head, with the arithmetic of ``core/analysis.head_flops``: ``2 * MACs`` of
each conv and dense stage.
"""

from __future__ import annotations

_MM_PAIRS = 3          # (1,1), (1,2), (2,1) monomials of a degree-3 surface
_VEC_POWERS = 3        # <I^a, mask> for a = 1, 2, 3


def frontend_dims(cfg: dict) -> tuple[int, int, int, int]:
    """``(h_o, w_o, N, C)`` of the configured FPCA layer."""
    s = cfg["spec"]
    n, st, p = s["max_kernel"], s["stride"], s["padding"]
    eff_h, eff_w = s["image_h"] // s["binning"], s["image_w"] // s["binning"]
    h_o = (eff_h - n + 2 * p) // st + 1
    w_o = (eff_w - n + 2 * p) // st + 1
    return h_o, w_o, n * n * s["in_channels"], s["out_channels"]


def avg_terms(cfg: dict) -> int:
    d = cfg["curvefit"]["degree_avg"]
    return (d + 1) * (d + 2) // 2


def kernel_flops_per_window(cfg: dict) -> int:
    _, _, n, c = frontend_dims(cfg)
    return 2 * (2 * _MM_PAIRS) * n * c + 2 * _VEC_POWERS * n + 4 * avg_terms(cfg) * c


def kernel_bytes_per_window(cfg: dict) -> int:
    _, _, n, c = frontend_dims(cfg)
    return 4 * (n + c)


def kernel_work(cfg: dict, windows: int) -> tuple[float, float]:
    """``(flops, bytes)`` of the frontend kernel over ``windows`` kept windows."""
    return (float(kernel_flops_per_window(cfg)) * windows,
            float(kernel_bytes_per_window(cfg)) * windows)


def frontend_model_flops(cfg: dict) -> int:
    """The signed analog convolution over every window of one frame."""
    h_o, w_o, n, c = frontend_dims(cfg)
    return 2 * h_o * w_o * n * c


def conv_flops(h: int, w: int, c_in: int, c_out: int, kernel: int) -> int:
    """A stride-1 SAME conv stage on an ``h x w`` map (``2 * MACs``)."""
    return 2 * h * w * kernel * kernel * c_in * c_out


def dense_flops(d_in: int, d_out: int) -> int:
    return 2 * d_in * d_out


def model_flops_per_frame(cfg: dict, head_flops: int) -> int:
    return frontend_model_flops(cfg) + int(head_flops)
