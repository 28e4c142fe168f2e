"""Head graphs: the model-zoo IR for residual / multi-branch digital heads.

``FPCAModelProgram.head`` started as a linear tuple of stage specs — enough
for the paper's sequential VWW-class classifier, but not for the zoo
(:mod:`repro.fpca.zoo`): residual joins, branch concats and detection
outputs need a *graph*.  :class:`HeadGraph` is that IR:

* a tuple of named :class:`Node`\\ s, each applying one op to one or more
  named inputs (``"input"`` is the implicit frontend output);
* validated at construction — unique names, defined references, acyclic
  (Kahn toposort), geometry checked per node with precise messages;
* signature-versioned like the chain specs (:meth:`HeadGraph._sig_entries`
  extends the model signature under a ``"head_graph"`` tag, so chain-head
  signatures stay byte-identical);
* lowered to pure-jnp ops from :mod:`repro.models.layers`
  (:meth:`HeadGraph.apply` is the numerics contract the fused executables
  trace, exactly like ``FPCAModelProgram.apply_head``).

Graph-only ops live here: :class:`AddSpec` (elementwise residual join),
:class:`ConcatSpec` (channel concat), :class:`GlobalPoolSpec` (mean over
the whole final map, whatever the frame size) and :class:`DetectSpec`
(per-coarse-cell class scores + box regression).  A graph whose output
node is a :class:`DetectSpec` makes the model a *detection* workload: its raw
``(gh, gw, n_classes + 4)`` maps are split into :class:`Detections` at the
user-facing boundaries (``CompiledModel.run`` / ``stream`` /
``run_segment``).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import numpy as np

from repro.fpca.program import (
    ActivationSpec,
    ConvSpec,
    DenseSpec,
    PoolSpec,
    _apply_activation,
    _check_activation,
)

__all__ = [
    "AddSpec",
    "ConcatSpec",
    "GlobalPoolSpec",
    "DetectSpec",
    "Node",
    "HeadGraph",
    "Detections",
]

# Bump when the *meaning* of a graph signature entry changes (same contract
# as program._SIG_VERSION).
_GRAPH_SIG_VERSION = "repro.fpca.head_graph/1"

#: The implicit source node every graph reads: the frontend's SS-ADC counts
#: (scaled by ``input_scale``).  Reserved — no node may take this name.
INPUT = "input"


# ---------------------------------------------------------------------------
# Graph-only ops
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AddSpec:
    """Elementwise residual join: sums >= 2 same-shape inputs, then an
    optional activation (the classic post-add relu)."""

    activation: str | None = None

    def __post_init__(self) -> None:
        _check_activation(self.activation)

    def _sig(self) -> tuple:
        return ("add", self.activation or "")


@dataclasses.dataclass(frozen=True)
class ConcatSpec:
    """Channel-axis concat of >= 2 inputs with matching leading dims."""

    activation: str | None = None

    def __post_init__(self) -> None:
        _check_activation(self.activation)

    def _sig(self) -> tuple:
        return ("concat", self.activation or "")


@dataclasses.dataclass(frozen=True)
class GlobalPoolSpec:
    """Global average pool: the mean over the whole ``(h, w)`` map of its
    input, ``(h, w, c) -> (c,)``, so a classifier's last spatial stage
    reduces to one vector whatever the frame size left."""

    def _sig(self) -> tuple:
        return ("global_pool", "avg")


@dataclasses.dataclass(frozen=True)
class DetectSpec:
    """Per-coarse-cell detection output: ``n_classes`` class scores plus 4
    box-regression channels per spatial cell of its input — a ``kernel`` x
    ``kernel`` SAME-padded stride-1 conv emitting ``(gh, gw, n_classes + 4)``
    raw maps.  A graph ending in a DetectSpec makes the model's
    ``output_kind`` ``"detections"``; :class:`Detections` splits the raw map.
    """

    n_classes: int
    kernel: int = 1

    def __post_init__(self) -> None:
        if self.n_classes < 1:
            raise ValueError("detect n_classes must be >= 1")
        if self.kernel < 1:
            raise ValueError("detect kernel must be >= 1")

    @property
    def out_channels(self) -> int:
        return int(self.n_classes) + 4

    def _sig(self) -> tuple:
        return ("detect", int(self.n_classes), int(self.kernel))


_CHAIN_OPS = (ConvSpec, PoolSpec, GlobalPoolSpec, DenseSpec, ActivationSpec)
_JOIN_OPS = (AddSpec, ConcatSpec)
_PARAM_OPS = (ConvSpec, DenseSpec, DetectSpec)
_ALL_OPS = _CHAIN_OPS + _JOIN_OPS + (DetectSpec,)


@dataclasses.dataclass(frozen=True)
class Node:
    """One named graph stage: ``op`` applied to the values of ``inputs``.

    ``inputs`` name other nodes (or :data:`INPUT`).  Join ops
    (:class:`AddSpec` / :class:`ConcatSpec`) take >= 2 inputs; every other
    op takes exactly one.
    """

    name: str
    op: Any
    inputs: tuple[str, ...] = (INPUT,)

    def __post_init__(self) -> None:
        object.__setattr__(self, "inputs", tuple(self.inputs))
        if not self.name or not isinstance(self.name, str):
            raise ValueError("node name must be a non-empty string")
        if not isinstance(self.op, _ALL_OPS):
            raise TypeError(f"unknown head graph op {self.op!r}")
        if isinstance(self.op, _JOIN_OPS):
            if len(self.inputs) < 2:
                kind = "add" if isinstance(self.op, AddSpec) else "concat"
                raise ValueError(
                    f"node {self.name!r}: {kind} needs at least 2 inputs, "
                    f"got {len(self.inputs)}"
                )
        elif len(self.inputs) != 1:
            raise ValueError(
                f"node {self.name!r}: {type(self.op).__name__} takes exactly "
                f"1 input, got {len(self.inputs)}"
            )

    def _sig(self) -> tuple:
        return ("node", self.name, self.inputs, self.op._sig())


def _chain_out_shape(op: Any, cur: tuple[int, ...], where: str) -> tuple:
    """Output shape of one single-input op — the same geometry rules as
    ``FPCAModelProgram.head_shapes``, with node-name-prefixed errors."""
    if isinstance(op, ConvSpec):
        if len(cur) != 3:
            raise ValueError(
                f"{where}: conv needs a spatial (h, w, c) input, got shape "
                f"{cur}"
            )
        h, w, c = cur
        op.weight_shape(c, where)
        if op.padding == "SAME":
            return (-(-h // op.stride), -(-w // op.stride), op.out_channels)
        if op.kernel > h or op.kernel > w:
            raise ValueError(
                f"{where}: conv kernel {op.kernel} exceeds input {h}x{w}"
            )
        return ((h - op.kernel) // op.stride + 1,
                (w - op.kernel) // op.stride + 1, op.out_channels)
    if isinstance(op, DetectSpec):
        if len(cur) != 3:
            raise ValueError(
                f"{where}: detect needs a spatial (h, w, c) input, got shape "
                f"{cur}"
            )
        return (cur[0], cur[1], op.out_channels)
    if isinstance(op, PoolSpec):
        if len(cur) != 3:
            raise ValueError(
                f"{where}: pool needs a spatial (h, w, c) input, got shape "
                f"{cur}"
            )
        h, w, c = cur
        if op.size > h or op.size > w:
            raise ValueError(
                f"{where}: pool size {op.size} exceeds input {h}x{w}"
            )
        s = op.size if op.stride is None else op.stride
        return ((h - op.size) // s + 1, (w - op.size) // s + 1, c)
    if isinstance(op, GlobalPoolSpec):
        if len(cur) != 3:
            raise ValueError(
                f"{where}: global pool needs a spatial (h, w, c) input, got "
                f"shape {cur}"
            )
        return (cur[-1],)
    if isinstance(op, DenseSpec):
        return (op.features,)
    return tuple(cur)                       # ActivationSpec: shape-preserving


@dataclasses.dataclass(frozen=True)
class HeadGraph:
    """A validated DAG of head stages — the graph generalisation of the
    linear ``FPCAModelProgram.head`` tuple.

    Construction validates names / references / arity / acyclicity;
    :meth:`shapes` validates geometry against a concrete input shape (the
    frontend's ``out_shape``, checked by ``FPCAModelProgram.__post_init__``).
    The output node must be a :class:`DenseSpec` (class logits — the model
    stays a classifier) or a :class:`DetectSpec` (per-cell detections), so
    ``n_classes`` / ``output_kind`` are always well defined.

    Parameters are a dict keyed by node name (parameterized nodes only:
    conv / dense / detect), mirroring the chain head's one-dict-per-stage
    list; :meth:`init` / :meth:`bind` / :meth:`apply` are the graph
    counterparts of ``init_head`` / ``bind_head_params`` / ``apply_head``.
    """

    nodes: tuple
    output: str

    def __post_init__(self) -> None:
        object.__setattr__(self, "nodes", tuple(self.nodes))
        if not self.nodes:
            raise ValueError("HeadGraph needs at least one node")
        for n in self.nodes:
            if not isinstance(n, Node):
                raise TypeError(f"HeadGraph nodes must be Node instances, got {n!r}")
        seen: set[str] = set()
        for n in self.nodes:
            if n.name == INPUT:
                raise ValueError(
                    f"node name {INPUT!r} is reserved for the graph input"
                )
            if n.name in seen:
                raise ValueError(f"duplicate node name {n.name!r} in HeadGraph")
            seen.add(n.name)
        for n in self.nodes:
            for ref in n.inputs:
                if ref != INPUT and ref not in seen:
                    raise ValueError(
                        f"node {n.name!r} reads undefined input {ref!r}"
                    )
        if self.output not in seen:
            raise ValueError(
                f"output {self.output!r} is not a node in the graph"
            )
        if not isinstance(self._out_op, (DenseSpec, DetectSpec)):
            raise ValueError(
                "the graph output must be a DenseSpec (logits) or DetectSpec "
                "(detections) node"
            )
        self.toposort()                     # raises on cycles

    # -- structure -----------------------------------------------------------
    @property
    def _by_name(self) -> dict[str, Node]:
        by = self.__dict__.get("_by_name_cache")
        if by is None:
            by = {n.name: n for n in self.nodes}
            object.__setattr__(self, "_by_name_cache", by)
        return by

    @property
    def _out_op(self) -> Any:
        return self._by_name[self.output].op

    def toposort(self) -> tuple[Node, ...]:
        """Evaluation order (Kahn), deterministic by definition order."""
        order = self.__dict__.get("_topo_cache")
        if order is not None:
            return order
        deps = {
            n.name: {r for r in n.inputs if r != INPUT} for n in self.nodes
        }
        done: set[str] = set()
        out: list[Node] = []
        while len(done) < len(self.nodes):
            ready = [
                n for n in self.nodes
                if n.name not in done and not (deps[n.name] - done)
            ]
            if not ready:
                stuck = sorted(set(deps) - done)
                raise ValueError(f"HeadGraph has a cycle through nodes {stuck}")
            for n in ready:
                done.add(n.name)
                out.append(n)
        order = tuple(out)
        object.__setattr__(self, "_topo_cache", order)
        return order

    # -- geometry ------------------------------------------------------------
    def shapes(self, in_shape: tuple[int, ...]) -> dict[str, tuple[int, ...]]:
        """Per-node output shapes for a concrete input shape (validates join
        geometry with node-named errors)."""
        shapes: dict[str, tuple[int, ...]] = {
            INPUT: tuple(int(d) for d in in_shape)
        }
        for node in self.toposort():
            ins = [shapes[r] for r in node.inputs]
            op = node.op
            if isinstance(op, AddSpec):
                for s in ins[1:]:
                    if s != ins[0]:
                        raise ValueError(
                            f"node {node.name!r}: residual add needs matching "
                            f"input shapes, got {ins[0]} vs {s}"
                        )
                shapes[node.name] = ins[0]
            elif isinstance(op, ConcatSpec):
                lead = ins[0][:-1]
                for s in ins[1:]:
                    if len(s) != len(ins[0]) or s[:-1] != lead:
                        raise ValueError(
                            f"node {node.name!r}: concat needs matching "
                            f"leading dims, got {ins[0]} vs {s}"
                        )
                shapes[node.name] = lead + (sum(s[-1] for s in ins),)
            else:
                shapes[node.name] = _chain_out_shape(
                    op, ins[0], f"node {node.name!r}"
                )
        return shapes

    def out_shape(self, in_shape: tuple[int, ...]) -> tuple[int, ...]:
        return self.shapes(in_shape)[self.output]

    @property
    def output_kind(self) -> str:
        return (
            "detections" if isinstance(self._out_op, DetectSpec) else "logits"
        )

    @property
    def n_classes(self) -> int:
        op = self._out_op
        return int(op.n_classes if isinstance(op, DetectSpec) else op.features)

    # -- identity ------------------------------------------------------------
    def _sig_entries(self) -> tuple:
        """Versioned primitive entries for the model signature.  Node names,
        wiring and op specs are all compile-relevant; parameters are not."""
        return (
            (_GRAPH_SIG_VERSION,)
            + tuple(n._sig() for n in self.nodes)
            + (("output", self.output),)
        )

    # -- parameters ----------------------------------------------------------
    def _param_nodes(self) -> list[Node]:
        return [n for n in self.nodes if isinstance(n.op, _PARAM_OPS)]

    def _want_shapes(
        self, node: Node, shapes: dict[str, tuple[int, ...]]
    ) -> dict[str, tuple[int, ...]]:
        op, cur = node.op, shapes[node.inputs[0]]
        if isinstance(op, ConvSpec):
            return {"w": op.weight_shape(cur[-1], f"node {node.name!r}"),
                    "b": (op.out_channels,)}
        if isinstance(op, DetectSpec):
            c_out = op.out_channels
            return {"w": (c_out, op.kernel, op.kernel, cur[-1]),
                    "b": (c_out,)}
        d_in = 1
        for d in cur:
            d_in *= int(d)
        return {"w": (d_in, op.features), "b": (op.features,)}

    def init(self, key: jax.Array, in_shape: tuple[int, ...]) -> dict:
        """Fresh parameters: ``{node_name: {"w": ..., "b": ...}}`` for the
        parameterized nodes."""
        from repro.models.layers import init_conv2d, init_linear

        shapes = self.shapes(in_shape)
        nodes = self._param_nodes()
        keys = jax.random.split(key, max(len(nodes), 1))
        params: dict[str, dict] = {}
        for k, node in zip(keys, nodes):
            cur = shapes[node.inputs[0]]
            op = node.op
            if isinstance(op, (ConvSpec, DetectSpec)):
                params[node.name] = init_conv2d(
                    k, cur[-1], op.out_channels, op.kernel,
                    groups=getattr(op, "groups", 1),
                )
            else:
                d_in = 1
                for d in cur:
                    d_in *= int(d)
                params[node.name] = init_linear(k, d_in, op.features)
        return params

    def bind(self, params: Any, in_shape: tuple[int, ...]) -> dict:
        """Validate + coerce a graph parameter dict for serving (f32), the
        graph counterpart of ``FPCAModelProgram.bind_head_params``."""
        import jax.numpy as jnp

        if not isinstance(params, dict):
            raise ValueError(
                "graph head parameters must be a dict keyed by node name, "
                f"got {type(params).__name__}"
            )
        bound = {
            name: jax.tree_util.tree_map(
                lambda a: jnp.asarray(a, jnp.float32), dict(p)
            )
            for name, p in params.items()
        }
        want_names = {n.name for n in self._param_nodes()}
        if set(bound) != want_names:
            raise ValueError(
                f"graph head parameters keyed {sorted(bound)} do not match "
                f"parameterized nodes {sorted(want_names)}"
            )
        shapes = self.shapes(in_shape)
        for node in self._param_nodes():
            want = self._want_shapes(node, shapes)
            got = {k: tuple(v.shape) for k, v in bound[node.name].items()}
            if got != want:
                raise ValueError(
                    f"head node {node.name!r} ({type(node.op).__name__}): "
                    f"parameter shapes {got} do not match expected {want}"
                )
        return bound

    def apply(self, params: Any, x):
        """Evaluate the graph on a batch-leading input ``(b, h, w, c)`` —
        pure jnp ops, the numerics contract the fused executables trace.
        An unbatched ``(h, w, c)`` map is accepted too (the segment-seeding
        path feeds single effective maps, matching the chain-head MLPs
        which flatten either way)."""
        import jax.numpy as jnp

        from repro.models.layers import (
            avg_pool2d, conv2d, global_avg_pool2d, linear, max_pool2d,
        )

        if x.ndim == 3:
            return self.apply(params, x[None])[0]
        values: dict[str, Any] = {INPUT: x}
        for node in self.toposort():
            op = node.op
            ins = [values[r] for r in node.inputs]
            if isinstance(op, ConvSpec):
                y = _apply_activation(
                    op.activation,
                    conv2d(params[node.name], ins[0], op.stride, op.padding,
                           op.groups),
                )
            elif isinstance(op, DetectSpec):
                y = conv2d(params[node.name], ins[0], 1, "SAME")
            elif isinstance(op, PoolSpec):
                pool = max_pool2d if op.kind == "max" else avg_pool2d
                y = pool(ins[0], op.size, op.stride)
            elif isinstance(op, GlobalPoolSpec):
                y = global_avg_pool2d(ins[0])
            elif isinstance(op, DenseSpec):
                v = ins[0]
                if v.ndim > 2:
                    v = v.reshape(v.shape[0], -1)
                y = _apply_activation(op.activation, linear(params[node.name], v))
            elif isinstance(op, AddSpec):
                y = ins[0]
                for v in ins[1:]:
                    y = y + v
                y = _apply_activation(op.activation, y)
            elif isinstance(op, ConcatSpec):
                y = _apply_activation(
                    op.activation, jnp.concatenate(ins, axis=-1)
                )
            else:                           # ActivationSpec
                y = _apply_activation(op.fn, ins[0])
            values[node.name] = y
        return values[self.output]


# ---------------------------------------------------------------------------
# Detection output struct
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Detections:
    """Per-coarse-cell detections: class ``scores`` ``(..., gh, gw, C)`` and
    ``boxes`` ``(..., gh, gw, 4)``, split from one raw :class:`DetectSpec`
    map.  Holds whatever array type it was built from (device arrays stay
    lazy); host-side helpers realise on demand."""

    scores: Any
    boxes: Any

    @classmethod
    def from_raw(cls, raw, n_classes: int) -> "Detections":
        n = int(n_classes)
        if raw.shape[-1] != n + 4:
            raise ValueError(
                f"raw detection map has {raw.shape[-1]} channels, expected "
                f"n_classes + 4 = {n + 4}"
            )
        return cls(scores=raw[..., :n], boxes=raw[..., n:])

    @property
    def n_classes(self) -> int:
        return int(self.scores.shape[-1])

    @property
    def grid_shape(self) -> tuple[int, int]:
        return (int(self.scores.shape[-3]), int(self.scores.shape[-2]))

    def class_map(self) -> np.ndarray:
        """Argmax class index per cell, realised to host."""
        return np.argmax(np.asarray(self.scores), axis=-1)

    def top_k(self, k: int = 5) -> list[dict]:
        """Best ``k`` cells of an unbatched map by max class score: a list of
        ``{"cell": (gy, gx), "class": int, "score": float, "box": [4]}``."""
        s = np.asarray(self.scores)
        b = np.asarray(self.boxes)
        if s.ndim != 3:
            raise ValueError(
                f"top_k expects an unbatched (gh, gw, C) detection map, got "
                f"shape {s.shape}"
            )
        best = s.max(axis=-1)
        cls_idx = s.argmax(axis=-1)
        gw = best.shape[1]
        flat = best.ravel()
        order = np.argsort(flat)[::-1][: int(k)]
        boxes = b.reshape(-1, 4)
        return [
            {
                "cell": (int(i // gw), int(i % gw)),
                "class": int(cls_idx.ravel()[i]),
                "score": float(flat[i]),
                "box": [float(v) for v in boxes[i]],
            }
            for i in order
        ]
