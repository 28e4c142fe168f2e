"""The digital head's operations in a chip trace, for the head's per-layer
readers (``bench/metrics/head_device_ms.py``,
``bench/metrics/head_conv_roofline.py``).

An operation's name in a TPU trace's ``XLA Ops`` line is its HLO text
without metadata (``%fusion.20 = bf16[128,56,56,96]{...} fusion(...),
kind=kOutput, calls=%fused_computation``), so a JAX name scope does not
reach it.  What marks the head is what it computes: every convolution of
the head (and its Dense logits, which XLA lowers as a convolution) is an
output fusion, ``kind=kOutput``, with its bias, ReLU6, residual join or
global mean fused in, or a bare ``convolution``.  Compiled for a TPU v5e
at 128 cameras of 560x560 (``jax.jit(...).lower(...).compile()`` on a
described ``v5e:2x2`` topology), the gate's ``step_batch`` and the Pallas
frontend's executable hold no output fusion and no convolution, nested
computations included, while the ``fpca_mobilenetv2`` head patch runs 35
output fusions that hold all 52 of its convolutions (XLA fuses some
depthwise convs with the 1x1 conv beside them) and one ``kLoop`` fusion,
the patch of kept windows into the effective map, which is left out of the
head here, as are the weights' prefetch copies.
"""

from __future__ import annotations

import re

HEAD = re.compile(r"kind=kOutput|\sconvolution\(")


def head_seconds(trace) -> float:
    """Summed device time of the head's operations in the traced window."""
    return trace.op_seconds(HEAD)
