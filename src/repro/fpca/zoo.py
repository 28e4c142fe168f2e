"""Model zoo: the meta-architecture registry (``register_arch`` /
``build_model``).

d2go-style config-driven model construction: an architecture is a named
builder ``fn(cfg) -> FPCAModelProgram`` registered under a string name;
``build_model({"arch": name, ...})`` dispatches to it.  The built program is
stamped with ``arch=name`` so model-side telemetry (the ``fpca_model_*``
families in :mod:`repro.fpca.executable`) and ``fleet_report()`` break out
workloads per architecture.

Four architectures ship registered:

* ``"fpca_cnn"`` — the repo's original sequential classifier, *unchanged*:
  the builder constructs the exact same chain-head tuple as
  ``repro.configs.fpca_cnn.make_model_program``, so its ``signature()`` is
  byte-identical and every warm executable is shared (zero recompiles,
  pinned in ``tests/test_zoo.py``);
* ``"fpca_resnet"`` — a residual classifier over a
  :class:`repro.models.heads.HeadGraph` (conv trunk, post-add relu join);
* ``"fpca_detect"`` — a detection head: per-coarse-cell class scores + box
  regression (:class:`repro.models.heads.DetectSpec`), streaming per-tick
  :class:`repro.models.heads.Detections` through ``serve`` / ``run_segment``;
* ``"fpca_mobilenetv2"`` — P2M's published VWW network: MobileNetV2's
  inverted-residual bottleneck stack behind the in-pixel layer (grouped /
  depthwise :class:`repro.fpca.ConvSpec`, ``relu6``, residual joins, a
  global average pool).

``cfg`` keys every builder understands: ``spec`` (an
:class:`repro.core.mapping.FPCASpec` or kwargs mapping; defaults to the
repo config's ``FRONTEND_SPEC``), ``frontend`` (a full
:class:`repro.fpca.FPCAProgram`, or extra ``FPCAProgram`` kwargs such as
``gate``), ``input_scale``, ``n_classes``; per-arch knobs (``hidden``,
``width``, ``detect_kernel``) are documented on each builder.
"""

from __future__ import annotations

from typing import Any, Callable, Mapping

from repro.core.mapping import FPCASpec
from repro.fpca.program import (
    ConvSpec,
    DenseSpec,
    FPCAModelProgram,
    FPCAProgram,
    PoolSpec,
)
from repro.models.heads import AddSpec, DetectSpec, GlobalPoolSpec, HeadGraph, Node

__all__ = ["register_arch", "build_model", "available_archs"]

_ARCHS: dict[str, Callable[[Mapping], FPCAModelProgram]] = {}


def register_arch(name: str, *, overwrite: bool = False):
    """Decorator registering a builder ``fn(cfg) -> FPCAModelProgram`` under
    ``name``.  Duplicate names are an error unless ``overwrite=True`` —
    silently shadowing an architecture would silently change what a fleet
    serves."""
    if not name or not isinstance(name, str):
        raise ValueError("architecture name must be a non-empty string")

    def deco(fn: Callable[[Mapping], FPCAModelProgram]):
        if name in _ARCHS and not overwrite:
            raise ValueError(
                f"architecture {name!r} already registered; pass "
                f"overwrite=True to replace it"
            )
        _ARCHS[name] = fn
        return fn

    return deco


def available_archs() -> tuple[str, ...]:
    """Registered architecture names, sorted."""
    return tuple(sorted(_ARCHS))


def build_model(cfg: Mapping | None = None, **overrides) -> FPCAModelProgram:
    """Build the architecture named by ``cfg["arch"]`` (kwargs override cfg
    keys).  The returned program carries ``arch=name`` for telemetry; the
    signature is untouched by that stamp."""
    merged: dict[str, Any] = {**(dict(cfg) if cfg else {}), **overrides}
    if "arch" not in merged:
        raise KeyError(
            "build_model(cfg) needs an 'arch' key naming a registered "
            "architecture"
        )
    name = merged["arch"]
    builder = _ARCHS.get(name)
    if builder is None:
        raise KeyError(
            f"unknown architecture {name!r}; registered: "
            f"{list(available_archs())}"
        )
    model = builder(merged)
    if model.arch != name:
        model = model.replace(arch=name)
    return model


def _frontend(cfg: Mapping) -> FPCAProgram:
    fe = cfg.get("frontend")
    if isinstance(fe, FPCAProgram):
        return fe
    spec = cfg.get("spec")
    if spec is None:
        from repro.configs.fpca_cnn import FRONTEND_SPEC

        spec = FRONTEND_SPEC
    if isinstance(spec, Mapping):
        spec = FPCASpec(**spec)
    kw = dict(fe) if isinstance(fe, Mapping) else {}
    return FPCAProgram(spec=spec, **kw)


# ---------------------------------------------------------------------------
# Registered architectures
# ---------------------------------------------------------------------------

@register_arch("fpca_cnn")
def _build_fpca_cnn(cfg: Mapping) -> FPCAModelProgram:
    """The original sequential classifier.  Knobs: ``hidden`` (dense width),
    ``n_classes``, or a full ``head`` tuple.  The default head tuple equals
    ``repro.configs.fpca_cnn.HEAD`` — byte-identical signature, shared
    executables."""
    from repro.configs import fpca_cnn as defaults

    head = cfg.get("head")
    if head is None:
        hidden = int(cfg.get("hidden", defaults.N_HIDDEN))
        n_classes = int(cfg.get("n_classes", defaults.N_CLASSES))
        head = (DenseSpec(hidden, activation="relu"), DenseSpec(n_classes))
    return FPCAModelProgram(
        frontend=_frontend(cfg),
        head=tuple(head),
        input_scale=float(cfg.get("input_scale", 1.0)),
    )


@register_arch("fpca_resnet")
def _build_fpca_resnet(cfg: Mapping) -> FPCAModelProgram:
    """Residual classifier: SAME-conv stem, two-conv residual branch joined
    by a post-add relu, avg-pool, two dense stages.  Knobs: ``width`` (conv
    channels), ``hidden``, ``n_classes``."""
    width = int(cfg.get("width", 16))
    hidden = int(cfg.get("hidden", 32))
    n_classes = int(cfg.get("n_classes", 2))
    graph = HeadGraph(
        nodes=(
            Node("stem", ConvSpec(width, 3, padding="SAME"), ("input",)),
            Node("conv1", ConvSpec(width, 3, padding="SAME"), ("stem",)),
            Node("conv2",
                 ConvSpec(width, 3, padding="SAME", activation=None),
                 ("conv1",)),
            Node("join", AddSpec(activation="relu"), ("stem", "conv2")),
            Node("pool", PoolSpec(2, kind="avg"), ("join",)),
            Node("fc", DenseSpec(hidden, activation="relu"), ("pool",)),
            Node("logits", DenseSpec(n_classes), ("fc",)),
        ),
        output="logits",
    )
    return FPCAModelProgram(
        frontend=_frontend(cfg),
        head=graph,
        input_scale=float(cfg.get("input_scale", 1.0)),
    )


@register_arch("fpca_detect")
def _build_fpca_detect(cfg: Mapping) -> FPCAModelProgram:
    """Detection head: SAME-conv trunk then a :class:`DetectSpec` emitting
    ``n_classes`` class scores + 4 box channels per coarse cell of the
    frontend grid.  Knobs: ``width`` (trunk channels), ``n_classes``,
    ``detect_kernel`` (SAME conv size of the detect stage)."""
    width = int(cfg.get("width", 16))
    n_classes = int(cfg.get("n_classes", 2))
    graph = HeadGraph(
        nodes=(
            Node("trunk", ConvSpec(width, 3, padding="SAME"), ("input",)),
            Node("det",
                 DetectSpec(n_classes, kernel=int(cfg.get("detect_kernel", 1))),
                 ("trunk",)),
        ),
        output="det",
    )
    return FPCAModelProgram(
        frontend=_frontend(cfg),
        head=graph,
        input_scale=float(cfg.get("input_scale", 1.0)),
    )


# MobileNetV2 (Sandler et al., arXiv:1801.04381, Table 2) after its stem:
# (expansion t, output channels c, repeats n, first stride s) per sequence.
_MOBILENETV2_BLOCKS = (
    (1, 16, 1, 1),
    (6, 24, 2, 2),
    (6, 32, 3, 2),
    (6, 64, 4, 2),
    (6, 96, 3, 1),
    (6, 160, 3, 2),
    (6, 320, 1, 1),
)
_MOBILENETV2_LAST = 1280


@register_arch("fpca_mobilenetv2")
def _build_fpca_mobilenetv2(cfg: Mapping) -> FPCAModelProgram:
    """P2M's VWW network (arXiv:2203.04737): MobileNetV2 at width multiplier
    1.0 with its stem (3x3 stride-2 conv to 32 channels) replaced by the
    in-pixel layer, so the bottleneck stack starts from the frontend's
    count map.  Table 2's 17 inverted-residual blocks ``block_0`` ..
    ``block_16``: a 1x1 ``_expand`` conv to ``t * c_in`` channels with relu6
    (absent where ``t == 1``), a 3x3 ``_depthwise`` conv (stride 1 or 2)
    with relu6, a linear 1x1 ``_project`` conv, and an ``_add`` residual
    join where the stride is 1 and the width unchanged (10 of the 17);
    then ``conv_1`` (1x1 to 1280, relu6), a global average ``pool`` and
    Dense ``logits``.  Knobs: ``n_classes`` (default 2, VWW's person / no
    person) and ``input_scale``.

    Departures from the paper: BatchNorm is folded into the conv biases
    (inference); there is no dropout; the depthwise convs pad ``SAME``, so
    the stride-2 ones pad as the TF reference implementation does (PyTorch
    pads 1 on both sides; both give the same output size)."""
    frontend = _frontend(cfg)
    nodes = []
    x, c_in, i = "input", frontend.out_channels, 0
    for t, c, n, s in _MOBILENETV2_BLOCKS:
        for r in range(n):
            name, stride, hidden = f"block_{i}", s if r == 0 else 1, t * c_in
            y = x
            if t != 1:
                nodes.append(Node(f"{name}_expand",
                                  ConvSpec(hidden, 1, activation="relu6"), (y,)))
                y = f"{name}_expand"
            nodes.append(Node(f"{name}_depthwise",
                              ConvSpec(hidden, 3, stride=stride, padding="SAME",
                                       activation="relu6", groups=hidden), (y,)))
            nodes.append(Node(f"{name}_project", ConvSpec(c, 1, activation=None),
                              (f"{name}_depthwise",)))
            y = f"{name}_project"
            if stride == 1 and c_in == c:
                nodes.append(Node(f"{name}_add", AddSpec(), (x, y)))
                y = f"{name}_add"
            x, c_in, i = y, c, i + 1
    nodes += [
        Node("conv_1", ConvSpec(_MOBILENETV2_LAST, 1, activation="relu6"), (x,)),
        Node("pool", GlobalPoolSpec(), ("conv_1",)),
        Node("logits", DenseSpec(int(cfg.get("n_classes", 2))), ("pool",)),
    ]
    return FPCAModelProgram(
        frontend=frontend,
        head=HeadGraph(nodes=tuple(nodes), output="logits"),
        input_scale=float(cfg.get("input_scale", 1.0)),
    )
