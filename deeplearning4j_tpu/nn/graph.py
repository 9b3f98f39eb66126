"""ComputationGraph: arbitrary-DAG models (multi-input / multi-output).

Capability parity with the reference's nn/graph/ComputationGraph.java
(3,902 LoC: vertices:143, topologicalOrder:152, init:377, fit:857-1146,
calcBackpropGradients:1942, output:1754-1878), the conf classes under
nn/conf/graph/ (ElementWiseVertex, MergeVertex, StackVertex, UnstackVertex,
SubsetVertex, ScaleVertex, ShiftVertex, L2Vertex, L2NormalizeVertex,
ReshapeVertex, PreprocessorVertex, rnn/LastTimeStepVertex,
rnn/DuplicateToTimeSeriesVertex, rnn/ReverseTimeSeriesVertex) and
nn/conf/ComputationGraphConfiguration.java — re-designed TPU-first:

- One pure jitted train step over the whole DAG: forward walks the
  topological order once inside the trace, loss is the sum over all output
  heads, backward is autodiff of the whole step. The reference instead walks
  `GraphVertex.doForward/doBackward` objects with per-op JNI dispatch and
  hand-written epsilon accumulation at fan-in vertices — XLA's autodiff does
  that accumulation for free.
- Params are a dict {vertex_name: layer params}, not one flattened view
  split into per-vertex subsets (ComputationGraph.init:426-470).
- NHWC / [batch, time, feat] layouts throughout (TPU tiling), so MergeVertex
  is always a last-axis concat regardless of input kind.
"""

from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu import obs
from deeplearning4j_tpu.analysis import retrace_guard
from deeplearning4j_tpu.nn import aot
from deeplearning4j_tpu.nn.config import LayerConfig, layer_from_dict, _encode_value
from deeplearning4j_tpu.nn.input_type import InputType
from deeplearning4j_tpu.nn.layers.recurrent import BaseRecurrent
from deeplearning4j_tpu.nn.model import _cast_input, _cast_labels, _sig_dtype
from deeplearning4j_tpu.nn.step_program import StepReports, layer_scope
from deeplearning4j_tpu.nn.preprocessors import infer_preprocessor
from deeplearning4j_tpu.utils import bucketing
from deeplearning4j_tpu.train.updaters import (
    apply_gradient_normalization,
    make_updater,
    normalize_updater,
    scale_lr,
)

# ---------------------------------------------------------------------------
# Vertex configs
# ---------------------------------------------------------------------------

vertex_registry: Dict[str, type] = {}


def register_vertex(type_name: str):
    def deco(cls):
        cls._vtype_name = type_name
        vertex_registry[type_name] = cls
        return cls

    return deco


@dataclass
class GraphVertex:
    """Base for non-layer DAG nodes (nn/conf/graph/GraphVertex.java).

    Contract (all pure; list-valued inputs):
    - ``output_type(input_types) -> InputType``
    - ``init(key, input_types, dtype) -> params`` ({} default — most vertices
      are param-free)
    - ``apply(params, state, xs, *, train, rng, masks) -> (y, new_state)``
    - ``propagate_mask(masks, input_types) -> mask``
    """

    _vtype_name = "vertex"
    trainable = True
    l1 = 0.0
    l2 = 0.0
    updater = None

    def to_dict(self) -> dict:
        d = {"@vtype": self._vtype_name}
        for f in dataclasses.fields(self):
            d[f.name] = _encode_value(getattr(self, f.name))
        return d

    @staticmethod
    def from_dict(d: dict) -> "GraphVertex":
        tag = d.get("@vtype")
        if tag not in vertex_registry:
            raise ValueError(f"Unknown vertex type '{tag}'. Known: {sorted(vertex_registry)}")
        cls = vertex_registry[tag]
        names = {f.name for f in dataclasses.fields(cls)}
        kwargs = {k: v for k, v in d.items() if k in names}
        # JSON arrays -> tuples for shape-like fields
        kwargs = {k: tuple(v) if isinstance(v, list) else v for k, v in kwargs.items()}
        return cls(**kwargs)

    # -- contract defaults -------------------------------------------------
    def output_type(self, input_types: List[InputType]) -> InputType:
        return input_types[0]

    def init(self, key, input_types: List[InputType], dtype=jnp.float32):
        return {}

    def init_state(self, input_types: List[InputType]):
        return {}

    def apply(self, params, state, xs: List[jax.Array], *, train=False, rng=None, masks=None):
        raise NotImplementedError

    def propagate_mask(self, masks, input_types: List[InputType]):
        for m in masks or ():
            if m is not None:
                return m
        return None

    def regularization_penalty(self, params):
        return jnp.asarray(0.0, jnp.float32)


@register_vertex("merge")
@dataclass
class MergeVertex(GraphVertex):
    """Concat along the feature/channel axis (MergeVertex.java). NHWC makes
    this the last axis for every input kind."""

    def output_type(self, input_types):
        it0 = input_types[0]
        if it0.kind == "conv":
            return InputType.convolutional(
                it0.height, it0.width, sum(t.channels for t in input_types)
            )
        if it0.kind == "recurrent":
            return InputType.recurrent(sum(t.size for t in input_types), it0.timesteps)
        return InputType.feed_forward(sum(t.flat_size() for t in input_types))

    def apply(self, params, state, xs, *, train=False, rng=None, masks=None):
        return jnp.concatenate(xs, axis=-1), state


@register_vertex("elementwise")
@dataclass
class ElementWiseVertex(GraphVertex):
    """Pointwise add/subtract/product/average/max across inputs
    (ElementWiseVertex.java — the residual-connection workhorse)."""

    op: str = "add"

    def apply(self, params, state, xs, *, train=False, rng=None, masks=None):
        if self.op == "add":
            y = sum(xs[1:], xs[0])
        elif self.op == "subtract":
            y = xs[0] - xs[1]
        elif self.op == "product":
            y = xs[0]
            for x in xs[1:]:
                y = y * x
        elif self.op == "average":
            y = sum(xs[1:], xs[0]) / len(xs)
        elif self.op == "max":
            y = xs[0]
            for x in xs[1:]:
                y = jnp.maximum(y, x)
        else:
            raise ValueError(f"Unknown elementwise op '{self.op}'")
        return y, state


@register_vertex("stack")
@dataclass
class StackVertex(GraphVertex):
    """Concat along the batch axis (StackVertex.java) — used with Unstack for
    weight sharing across branches."""

    def apply(self, params, state, xs, *, train=False, rng=None, masks=None):
        return jnp.concatenate(xs, axis=0), state


@register_vertex("unstack")
@dataclass
class UnstackVertex(GraphVertex):
    """Slice batch segment ``from_index`` of ``stack_size`` equal parts
    (UnstackVertex.java)."""

    from_index: int = 0
    stack_size: int = 1

    def apply(self, params, state, xs, *, train=False, rng=None, masks=None):
        x = xs[0]
        step = x.shape[0] // self.stack_size
        return x[self.from_index * step : (self.from_index + 1) * step], state


@register_vertex("subset")
@dataclass
class SubsetVertex(GraphVertex):
    """Feature range [from_index, to_index] INCLUSIVE (SubsetVertex.java)."""

    from_index: int = 0
    to_index: int = 0

    def output_type(self, input_types):
        n = self.to_index - self.from_index + 1
        it = input_types[0]
        if it.kind == "recurrent":
            return InputType.recurrent(n, it.timesteps)
        if it.kind == "conv":
            return InputType.convolutional(it.height, it.width, n)
        return InputType.feed_forward(n)

    def apply(self, params, state, xs, *, train=False, rng=None, masks=None):
        return xs[0][..., self.from_index : self.to_index + 1], state


@register_vertex("scale")
@dataclass
class ScaleVertex(GraphVertex):
    scale: float = 1.0

    def apply(self, params, state, xs, *, train=False, rng=None, masks=None):
        return xs[0] * self.scale, state


@register_vertex("shift")
@dataclass
class ShiftVertex(GraphVertex):
    shift: float = 0.0

    def apply(self, params, state, xs, *, train=False, rng=None, masks=None):
        return xs[0] + self.shift, state


@register_vertex("l2")
@dataclass
class L2Vertex(GraphVertex):
    """Pairwise L2 distance of two inputs -> [batch, 1] (L2Vertex.java, used
    by triplet-loss nets like FaceNet)."""

    eps: float = 1e-8

    def output_type(self, input_types):
        return InputType.feed_forward(1)

    def apply(self, params, state, xs, *, train=False, rng=None, masks=None):
        a = xs[0].reshape(xs[0].shape[0], -1)
        b = xs[1].reshape(xs[1].shape[0], -1)
        d = jnp.sqrt(jnp.sum((a - b) ** 2, axis=-1, keepdims=True) + self.eps)
        return d, state


@register_vertex("l2normalize")
@dataclass
class L2NormalizeVertex(GraphVertex):
    """x / ||x||_2 over all non-batch axes (L2NormalizeVertex.java)."""

    eps: float = 1e-8

    def apply(self, params, state, xs, *, train=False, rng=None, masks=None):
        x = xs[0]
        axes = tuple(range(1, x.ndim))
        norm = jnp.sqrt(jnp.sum(x * x, axis=axes, keepdims=True) + self.eps)
        return x / norm, state


@register_vertex("reshape")
@dataclass
class ReshapeVertex(GraphVertex):
    """Reshape to ``shape`` (batch axis = -1 allowed) (ReshapeVertex.java)."""

    shape: Tuple[int, ...] = ()
    output: Optional[dict] = None  # explicit InputType dict for shape inference

    def output_type(self, input_types):
        if self.output is not None:
            return InputType.from_dict(dict(self.output))
        s = [d for d in self.shape if d != -1]
        if len(s) == 1:
            return InputType.feed_forward(s[0])
        if len(s) == 3:
            return InputType.convolutional(s[0], s[1], s[2])
        if len(s) == 2:
            return InputType.recurrent(s[1], s[0])
        return input_types[0]

    def apply(self, params, state, xs, *, train=False, rng=None, masks=None):
        return xs[0].reshape(self.shape), state


@register_vertex("preprocessor")
@dataclass
class PreprocessorVertex(GraphVertex):
    """Wraps any param-free LayerConfig (the preprocessors) as a DAG node
    (PreprocessorVertex.java)."""

    preprocessor: Any = None

    def output_type(self, input_types):
        return self.preprocessor.output_type(input_types[0])

    def apply(self, params, state, xs, *, train=False, rng=None, masks=None):
        y, _ = self.preprocessor.apply({}, {}, xs[0], train=train, rng=rng,
                                       mask=masks[0] if masks else None)
        return y, state

    def to_dict(self):
        return {"@vtype": self._vtype_name, "preprocessor": self.preprocessor.to_dict()}

    @staticmethod
    def _decode(d):
        return PreprocessorVertex(preprocessor=layer_from_dict(d["preprocessor"]))


@register_vertex("last_time_step")
@dataclass
class LastTimeStepVertex(GraphVertex):
    """[b,t,f] -> [b,f]: last time step, or last UNMASKED step when the named
    network input has a mask (rnn/LastTimeStepVertex.java)."""

    mask_input: Optional[str] = None

    def output_type(self, input_types):
        return InputType.feed_forward(input_types[0].size)

    def apply(self, params, state, xs, *, train=False, rng=None, masks=None):
        x = xs[0]
        m = masks[0] if masks else None
        if m is None:
            return x[:, -1, :], state
        # last index where mask==1 (handles left-padded/ALIGN_END masks)
        T = x.shape[1]
        rev = jnp.flip(m > 0, axis=1)
        idx = (T - 1 - jnp.argmax(rev, axis=1)).astype(jnp.int32)
        return jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0, :], state

    def propagate_mask(self, masks, input_types):
        return None


@register_vertex("duplicate_to_time_series")
@dataclass
class DuplicateToTimeSeriesVertex(GraphVertex):
    """[b,f] -> [b,t,f], t taken from the second runtime input (the reference
    names a network input; here the builder wires that input's activation in
    as input #2 so t is known inside the trace)
    (rnn/DuplicateToTimeSeriesVertex.java)."""

    def output_type(self, input_types):
        t = input_types[1].timesteps if len(input_types) > 1 else None
        return InputType.recurrent(input_types[0].flat_size(), t)

    def apply(self, params, state, xs, *, train=False, rng=None, masks=None):
        x, ref = xs[0], xs[1]
        return jnp.broadcast_to(x[:, None, :], (x.shape[0], ref.shape[1], x.shape[-1])), state

    def propagate_mask(self, masks, input_types):
        return masks[1] if masks and len(masks) > 1 else None


@register_vertex("reverse_time_series")
@dataclass
class ReverseTimeSeriesVertex(GraphVertex):
    """Reverse the time axis; with a mask, only the valid prefix is reversed
    (rnn/ReverseTimeSeriesVertex.java)."""

    def apply(self, params, state, xs, *, train=False, rng=None, masks=None):
        x = xs[0]
        m = masks[0] if masks else None
        if m is None:
            return x[:, ::-1, :], state
        lengths = jnp.sum(m > 0, axis=1).astype(jnp.int32)  # [b]
        t = x.shape[1]
        # index j -> (len-1-j) for j < len, else j (padding stays in place)
        j = jnp.arange(t)[None, :]
        idx = jnp.where(j < lengths[:, None], lengths[:, None] - 1 - j, j)
        return jnp.take_along_axis(x, idx[:, :, None], axis=1), state


# ---------------------------------------------------------------------------
# Configuration + builder
# ---------------------------------------------------------------------------


@dataclass
class VertexSpec:
    """One DAG node: a LayerConfig or a GraphVertex plus its input names."""

    config: Any
    inputs: Tuple[str, ...]

    def is_layer(self) -> bool:
        return isinstance(self.config, LayerConfig)


@dataclass
class ComputationGraphConfiguration:
    """DAG config (ComputationGraphConfiguration.java, 928 LoC). JSON
    round-trip is the long-lived artifact contract (SURVEY §5.6)."""

    inputs: Tuple[str, ...] = ()
    input_types: Dict[str, InputType] = field(default_factory=dict)
    vertices: Dict[str, VertexSpec] = field(default_factory=dict)  # insertion-ordered
    outputs: Tuple[str, ...] = ()
    seed: int = 12345
    updater: Any = "sgd"
    dtype: str = "float32"
    # Truncated BPTT over the DAG (ComputationGraph.java:950,1179
    # doTruncatedBPTT): "standard" | "tbptt". Forward/backward chunk length
    # unified, like the MLN path.
    backprop_type: str = "standard"
    tbptt_fwd_length: int = 20
    tbptt_back_length: int = 20

    # -- serde -------------------------------------------------------------
    def to_dict(self) -> dict:
        return {
            "format": "deeplearning4j_tpu/ComputationGraphConfiguration",
            "version": 1,
            "inputs": list(self.inputs),
            "input_types": {k: v.to_dict() for k, v in self.input_types.items()},
            "vertices": [
                {
                    "name": name,
                    "inputs": list(spec.inputs),
                    ("layer" if spec.is_layer() else "vertex"): spec.config.to_dict(),
                }
                for name, spec in self.vertices.items()
            ],
            "outputs": list(self.outputs),
            "seed": self.seed,
            "updater": _encode_value(self.updater),
            "dtype": self.dtype,
            "backprop_type": self.backprop_type,
            "tbptt_fwd_length": self.tbptt_fwd_length,
            "tbptt_back_length": self.tbptt_back_length,
        }

    def to_json(self, **kw) -> str:
        return json.dumps(self.to_dict(), **kw)

    @staticmethod
    def from_dict(d: dict) -> "ComputationGraphConfiguration":
        vertices: Dict[str, VertexSpec] = {}
        for v in d["vertices"]:
            if "layer" in v:
                cfg = layer_from_dict(v["layer"])
            elif v["vertex"].get("@vtype") == "preprocessor":
                cfg = PreprocessorVertex._decode(v["vertex"])
            else:
                cfg = GraphVertex.from_dict(v["vertex"])
            vertices[v["name"]] = VertexSpec(cfg, tuple(v["inputs"]))
        return ComputationGraphConfiguration(
            inputs=tuple(d["inputs"]),
            input_types={k: InputType.from_dict(t) for k, t in d["input_types"].items()},
            vertices=vertices,
            outputs=tuple(d["outputs"]),
            seed=d.get("seed", 12345),
            updater=d.get("updater", "sgd"),
            dtype=d.get("dtype", "float32"),
            backprop_type=d.get("backprop_type", "standard"),
            tbptt_fwd_length=d.get("tbptt_fwd_length", 20),
            tbptt_back_length=d.get("tbptt_back_length", 20),
        )

    @staticmethod
    def from_json(s: str) -> "ComputationGraphConfiguration":
        return ComputationGraphConfiguration.from_dict(json.loads(s))

    def to_yaml(self) -> str:
        """YAML twin of to_json (ComputationGraphConfiguration.toYaml)."""
        from deeplearning4j_tpu.nn.config import yaml_dump

        return yaml_dump(self.to_dict())

    @staticmethod
    def from_yaml(s: str) -> "ComputationGraphConfiguration":
        from deeplearning4j_tpu.nn.config import yaml_load

        return ComputationGraphConfiguration.from_dict(yaml_load(s))

    @staticmethod
    def builder() -> "GraphBuilder":
        return GraphBuilder()


class GraphBuilder:
    """Fluent DAG builder (ComputationGraphConfiguration.GraphBuilder)."""

    def __init__(self):
        self._inputs: List[str] = []
        self._input_types: Dict[str, InputType] = {}
        self._vertices: Dict[str, VertexSpec] = {}
        self._outputs: List[str] = []
        self._seed = 12345
        self._updater: Any = "sgd"
        self._dtype = "float32"
        self._backprop_type = "standard"
        self._tbptt_length = 20

    def add_inputs(self, *names: str) -> "GraphBuilder":
        self._inputs.extend(names)
        return self

    def set_input_types(self, *types: InputType) -> "GraphBuilder":
        if len(types) != len(self._inputs):
            raise ValueError("set_input_types: one InputType per declared input")
        self._input_types = dict(zip(self._inputs, types))
        return self

    def add_layer(self, name: str, layer: LayerConfig, *inputs: str) -> "GraphBuilder":
        return self.add_vertex(name, layer, *inputs)

    def add_vertex(self, name: str, v: Any, *inputs: str) -> "GraphBuilder":
        if name in self._vertices or name in self._inputs:
            raise ValueError(f"Duplicate vertex name '{name}'")
        known = set(self._inputs) | set(self._vertices)
        for i in inputs:
            if i not in known:
                raise ValueError(f"Vertex '{name}' input '{i}' is not defined (yet)")
        self._vertices[name] = VertexSpec(v, tuple(inputs))
        return self

    def set_outputs(self, *names: str) -> "GraphBuilder":
        self._outputs = list(names)
        return self

    def seed(self, s: int) -> "GraphBuilder":
        self._seed = s
        return self

    def updater(self, u: Any) -> "GraphBuilder":
        self._updater = u
        return self

    def dtype(self, d: str) -> "GraphBuilder":
        self._dtype = d
        return self

    def tbptt(self, length: int) -> "GraphBuilder":
        """Enable truncated BPTT with the given chunk length
        (GraphBuilder.backpropType(TruncatedBPTT) + tBPTT{Forward,Backward}Length)."""
        self._backprop_type = "tbptt"
        self._tbptt_length = length
        return self

    def build(self) -> ComputationGraphConfiguration:
        if not self._inputs:
            raise ValueError("ComputationGraph needs at least one input")
        if not self._outputs:
            raise ValueError("ComputationGraph needs at least one output")
        for o in self._outputs:
            if o not in self._vertices:
                raise ValueError(f"Output '{o}' is not a vertex")
        if set(self._input_types) != set(self._inputs):
            raise ValueError("set_input_types is required (one per input)")
        return ComputationGraphConfiguration(
            inputs=tuple(self._inputs),
            input_types=self._input_types,
            vertices=self._vertices,
            outputs=tuple(self._outputs),
            seed=self._seed,
            updater=self._updater,
            dtype=self._dtype,
            backprop_type=self._backprop_type,
            tbptt_fwd_length=self._tbptt_length,
            tbptt_back_length=self._tbptt_length,
        )


# ---------------------------------------------------------------------------
# Runtime model
# ---------------------------------------------------------------------------


@dataclass
class _RuntimeVertex:
    name: str
    spec: VertexSpec
    inputs: Tuple[str, ...]
    pre: Optional[LayerConfig]          # auto-inserted preprocessor (layer vertices)
    input_types: List[InputType]        # per runtime input, post-preprocessor
    out_type: InputType
    config: Any                          # resolved (n_in inferred) layer/vertex


def _tbptt_slice_t(x, sl, T, kind):
    """tBPTT time-axis chunking rule for one array.

    feat: inputs DECLARED recurrent chunk on axis 1 — [B,T,F] float streams
    and [B,T] integer token-id streams alike (kind=="feat_td"); statics pass
    whole — in particular a static 3-D side input whose middle dim happens
    to equal T (kind=="feat") must NOT be silently time-chunked.
    label: [B,T,C] one-hot or [B,T] sparse-integer. mask: [B,T]."""
    if x is None:
        return None
    nd = np.ndim(x)
    if nd == 3 and x.shape[1] == T and kind in ("feat_td", "label", "mask"):
        return x[:, sl]
    if nd == 2 and x.shape[1] == T:
        if kind in ("mask", "feat_td") or (
                kind == "label" and np.dtype(_sig_dtype(x)).kind in "iu"):
            return x[:, sl]
    return x


def _toposort(conf: ComputationGraphConfiguration) -> List[str]:
    """Kahn's algorithm over vertex names (ComputationGraph.topologicalOrder
    equivalent, computed once at build)."""
    indeg = {n: 0 for n in conf.vertices}
    dependents: Dict[str, List[str]] = {n: [] for n in conf.vertices}
    for name, spec in conf.vertices.items():
        for i in spec.inputs:
            if i in conf.vertices:
                indeg[name] += 1
                dependents[i].append(name)
    ready = [n for n, d in indeg.items() if d == 0]
    order: List[str] = []
    while ready:
        n = ready.pop(0)
        order.append(n)
        for m in dependents[n]:
            indeg[m] -= 1
            if indeg[m] == 0:
                ready.append(m)
    if len(order) != len(conf.vertices):
        cyc = sorted(set(conf.vertices) - set(order))
        raise ValueError(f"Graph has a cycle involving: {cyc}")
    return order


class ComputationGraph:
    """Stateful facade over pure jitted DAG functions; API mirrors the
    reference ComputationGraph (init/fit/output/score/evaluate)."""

    def __init__(self, conf: ComputationGraphConfiguration):
        self.conf = conf
        self.dtype = jnp.dtype(conf.dtype)
        self._resolve()
        self.params: Optional[dict] = None
        self.state: Optional[dict] = None
        self.opt_state: Optional[dict] = None
        self.iteration = 0
        self.epoch = 0
        self.batch_in_epoch = 0
        self._rng = jax.random.PRNGKey(conf.seed)
        self._step_fn = None
        self._tbptt_step_fn = None
        self._output_fn = None
        self._rnn_carries: Optional[dict] = None
        self.listeners: list = []
        self.divergence_guard = None
        self._lr_scale = 1.0
        self._pending_residuals = None

    # -- resolution --------------------------------------------------------
    def _resolve(self):
        conf = self.conf
        self.topo_order = _toposort(conf)
        types: Dict[str, InputType] = dict(conf.input_types)
        self.rt: Dict[str, _RuntimeVertex] = {}
        for name in self.topo_order:
            spec = conf.vertices[name]
            in_types = [types[i] for i in spec.inputs]
            pre = None
            cfg = spec.config
            if spec.is_layer():
                if len(spec.inputs) != 1:
                    raise ValueError(f"Layer vertex '{name}' must have exactly one input")
                pre = infer_preprocessor(in_types[0], cfg)
                if pre is not None:
                    in_types = [pre.output_type(in_types[0])]
                if hasattr(cfg, "with_n_in"):
                    cfg = cfg.with_n_in(cfg.infer_n_in(in_types[0]))
                out_t = cfg.output_type(in_types[0])
            else:
                out_t = cfg.output_type(in_types)
            types[name] = out_t
            self.rt[name] = _RuntimeVertex(
                name=name, spec=spec, inputs=spec.inputs, pre=pre,
                input_types=in_types, out_type=out_t, config=cfg,
            )
        self.vertex_types = types
        self.output_types = [types[o] for o in conf.outputs]
        # layer vertices with a time-stepped carry: tBPTT chunking and
        # rnnTimeStep streaming thread state through exactly these
        # (ComputationGraph.java rnnActivateUsingStoredState:1334)
        self._carry_vertices = [
            name for name in self.topo_order
            if self.rt[name].spec.is_layer()
            and isinstance(self.rt[name].config, BaseRecurrent)
            and getattr(self.rt[name].config, "SUPPORTS_CARRY", False)
        ]
        # wrapper layers holding an inner RNN (Bidirectional, MaskZero,
        # LastTimeStep): no carry channel — streaming/tBPTT would silently
        # reset their inner state every call, so those paths refuse them
        # (the reference's Bidirectional rnnTimeStep likewise throws)
        self._wrapped_rnn_vertices = [
            name for name in self.topo_order
            if self.rt[name].spec.is_layer()
            and getattr(self.rt[name].config, "rnn", None) is not None
        ]
        self._loss_vertices = [
            o for o in conf.outputs if hasattr(self.rt[o].config, "score")
        ]
        if not self._loss_vertices:
            self._loss_vertices = []  # inference-only graph is allowed
        # Stack/Unstack split or join the BATCH axis into fixed segments —
        # padding rows would land in the wrong branch, so batch bucketing
        # (output()) must stay off for these graphs
        self._has_batch_vertices = any(
            isinstance(self.rt[name].config, (StackVertex, UnstackVertex))
            for name in self.topo_order)

    # -- init --------------------------------------------------------------
    def init(self, seed: Optional[int] = None) -> "ComputationGraph":
        key = jax.random.PRNGKey(self.conf.seed if seed is None else seed)
        keys = jax.random.split(key, max(len(self.topo_order), 1))
        self.params, self.state = {}, {}
        for k, name in zip(keys, self.topo_order):
            v = self.rt[name]
            if v.spec.is_layer():
                self.params[name] = v.config.init(k, v.input_types[0], self.dtype)
                self.state[name] = v.config.init_state(v.input_types[0])
            else:
                self.params[name] = v.config.init(k, v.input_types, self.dtype)
                self.state[name] = v.config.init_state(v.input_types)
        self._build_updaters()
        self.opt_state = {
            name: u.init(self.params[name]) for name, u in self._updaters.items()
        }
        self.iteration = 0
        self.epoch = 0
        return self

    def _build_updaters(self):
        # _lr_scale is the divergence-guard rollback backoff (resilience.py)
        scale = float(getattr(self, "_lr_scale", 1.0))
        default = scale_lr(self.conf.updater, scale)
        self._updaters = {}
        for name in self.topo_order:
            cfg = self.rt[name].config
            if not getattr(cfg, "trainable", True):
                self._updaters[name] = make_updater("noop")
            elif getattr(cfg, "updater", None) is not None:
                self._updaters[name] = make_updater(scale_lr(cfg.updater, scale))
            else:
                self._updaters[name] = make_updater(default)

    def _clear_compiled(self):
        """Drop compiled step closures (updaters or divergence-guard config
        changed — both are baked into the trace). AOT-warmed step
        executables are stale for the same reason; the output path is
        untouched (inference doesn't trace updaters or guards)."""
        self._step_fn = None
        self._tbptt_step_fn = None
        self._chain_step_fn = None
        aot.clear_sites(self, ("cg.step", "cg.step.tbptt"))

    def set_divergence_guard(self, guard) -> "ComputationGraph":
        """Install a train/resilience.DivergenceGuard (None to remove).
        Clears compiled step caches: the skip_batch policy's select is traced
        into the step executable."""
        self.divergence_guard = guard
        self._clear_compiled()
        runner = getattr(self, "_dp_runner", None)
        if runner is not None:
            runner.rebuild_step()
        return self

    def num_params(self) -> int:
        return sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(self.params))

    # -- forward -----------------------------------------------------------
    def _forward(self, params, state, inputs: Dict[str, jax.Array], *, train, rngs,
                 masks: Optional[Dict[str, Any]] = None, stop_at: Optional[set] = None,
                 collect: bool = False, ex_weight=None, carries: Optional[dict] = None,
                 deterministic: bool = False):
        """Walk topo order. Returns (acts, new_state, mask_acts, new_carries).

        ``stop_at``: vertex names whose activation should be the PRE-output
        value for loss heads — loss vertices are applied outside (score needs
        the pre-activation input, mirroring MLN's upto=n-1 walk).
        ``ex_weight``: per-example [B] validity weight consumed only by layer
        vertices declaring CONSUMES_EXAMPLE_WEIGHT (BatchNorm excludes
        zero-weighted ParallelWrapper padding rows from batch statistics —
        same channel as MultiLayerNetwork._forward).
        ``carries``: {vertex_name: rnn carry} for the vertices in
        self._carry_vertices — when given, recurrent layer vertices run
        ``apply_seq`` from the supplied carry and the final carries are
        returned (the doTruncatedBPTT / rnnActivateUsingStoredState channel).
        ``deterministic`` (score(train=True) path): rng-drawing vertices
        (dropout / weight noise) run in eval mode while normalization keeps
        batch statistics — same contract as MultiLayerNetwork._forward.
        """
        acts: Dict[str, jax.Array] = dict(inputs)
        mask_acts: Dict[str, Any] = dict(masks or {})
        for n in self.conf.inputs:
            mask_acts.setdefault(n, None)
        new_carries = dict(carries) if carries is not None else None
        new_state = {}
        for i, name in enumerate(self.topo_order):
            v = self.rt[name]
            xs = [acts[i_] for i_ in v.inputs]
            in_masks = [mask_acts.get(i_) for i_ in v.inputs]
            rng = rngs[i] if rngs is not None else None
            if stop_at and name in stop_at:
                # loss head: keep the input activation (post-preprocessor)
                x = xs[0]
                m = in_masks[0]
                if v.pre is not None:
                    x, _ = v.pre.apply({}, {}, x, train=train, rng=None, mask=m)
                    m = v.pre.propagate_mask(m, self.vertex_types[v.inputs[0]])
                acts[name] = x
                mask_acts[name] = m
                new_state[name] = state[name]
                continue
            vtrain = train and not (
                deterministic and getattr(v.config, "uses_rng", lambda: False)())
            with layer_scope(v.config, name):
                if v.spec.is_layer():
                    x, m = xs[0], in_masks[0]
                    it = self.vertex_types[v.inputs[0]] if v.inputs[0] in self.vertex_types \
                        else self.conf.input_types[v.inputs[0]]
                    if v.pre is not None:
                        x, _ = v.pre.apply({}, {}, x, train=vtrain, rng=None, mask=m)
                        m = v.pre.propagate_mask(m, it)
                        it = v.input_types[0]
                    p_v = params[name]
                    if vtrain and v.config.weight_noise and rng is not None:
                        p_v = v.config.maybe_weight_noise(
                            p_v, vtrain, jax.random.fold_in(rng, 0x5EED)
                        )
                    if new_carries is not None and name in new_carries:
                        x2 = v.config.maybe_dropout_input(x, vtrain, rng)
                        y, c = v.config.apply_seq(p_v, x2, new_carries[name], m)
                        new_carries[name] = c
                        ns = state[name]
                    elif ex_weight is not None and getattr(v.config, "CONSUMES_EXAMPLE_WEIGHT", False):
                        y, ns = v.config.apply(p_v, state[name], x, train=vtrain,
                                               rng=rng, mask=m, ex_weight=ex_weight)
                    else:
                        y, ns = v.config.apply(p_v, state[name], x,
                                               train=vtrain, rng=rng, mask=m)
                    mask_acts[name] = v.config.propagate_mask(m, it)
                else:
                    # mask_input: vertex reads the mask of a NAMED input instead
                    # of its propagated one (rnn/LastTimeStepVertex.java semantics)
                    ms = getattr(v.config, "mask_input", None)
                    if ms is not None:
                        in_masks = [mask_acts.get(ms)] + in_masks[1:]
                    y, ns = v.config.apply(params[name], state[name], xs,
                                           train=vtrain, rng=rng, masks=in_masks)
                    mask_acts[name] = v.config.propagate_mask(in_masks, v.input_types)
            acts[name] = y
            new_state[name] = ns
        return acts, new_state, mask_acts, new_carries

    # -- loss --------------------------------------------------------------
    def _loss(self, params, state, inputs, labels, fmasks, lmasks, rngs, train=True,
              ex_weight=None, carries=None, deterministic=False):
        stop = set(self._loss_vertices)
        acts, new_state, mask_acts, new_carries = self._forward(
            params, state, inputs, train=train, rngs=rngs, masks=fmasks, stop_at=stop,
            ex_weight=ex_weight, carries=carries, deterministic=deterministic,
        )
        total = jnp.asarray(0.0, jnp.float32)
        with jax.named_scope("loss"):
            for i, oname in enumerate(self.conf.outputs):
                if oname not in stop:
                    continue
                v = self.rt[oname]
                y = labels[i] if isinstance(labels, (tuple, list)) else labels
                lm = None
                if lmasks is not None:
                    lm = lmasks[i] if isinstance(lmasks, (tuple, list)) else lmasks
                if lm is None:
                    lm = mask_acts.get(oname)
                with layer_scope(v.config, oname):
                    total = total + v.config.score(
                        params[oname], acts[oname], y, mask=lm, average=True)
            for name in self.topo_order:
                v = self.rt[name]
                total = total + v.config.regularization_penalty(params[name])
        return total, (new_state, new_carries)

    # -- jitted step -------------------------------------------------------
    def _make_step(self, with_carries: bool = False):
        from deeplearning4j_tpu.nn.step_program import StepProgram

        site = "cg.step.tbptt" if with_carries else "cg.step"
        return StepProgram(self._make_step_body(with_carries), site,
                           model=self, hits_site="cg.fit")

    def _make_step_body(self, with_carries: bool = False, grad_exchange=None):
        """The pure training-step closure. ``grad_exchange`` (a
        ``parallel.grads.GradExchange``) replaces the per-vertex update loop
        with an explicit cross-replica exchange — same contract as
        ``MultiLayerNetwork._step_body``: opt_state slot becomes
        ``(opt_state, residuals)``, loss/state are replica-means, the
        signature and return arity stay unchanged."""
        from deeplearning4j_tpu.train import resilience

        order = self.topo_order
        # divergence-guard skip_batch: the accept/reject select is traced
        # INTO the step (device-side; no extra host sync)
        guard = getattr(self, "divergence_guard", None)
        g_skip = bool(guard is not None and guard.policy == "skip_batch")
        g_limit = None if guard is None else guard.spike_limit
        # gradient-accumulation micro-batch count, baked at step-build time
        # (policy shared with MultiLayerNetwork — nn/model.py)
        from deeplearning4j_tpu.nn.model import (
            _accum_applicable, _accum_value_and_grad, _grad_accum_from_env)

        accum = _grad_accum_from_env()

        def step(params, opt_state, state, it, rng, inputs, labels, fmasks, lmasks,
                 carries, ex_weight=None):
            # python body runs once per trace → counts actual compiles
            bucketing.telemetry().record_trace(
                "cg.step", np.shape(next(iter(inputs.values()))))
            if grad_exchange is not None:
                opt_state, residuals = opt_state
            batch = (inputs, labels, fmasks, lmasks, ex_weight)
            if not with_carries and _accum_applicable(accum, batch):
                # DL4J_TPU_GRAD_ACCUM: scan over micro-batches, average the
                # grads, run the (single) update/exchange below on the mean —
                # grad_exchange therefore still exchanges ONCE per step
                def make_loss_fn(mb, st, k):
                    in_i, lab_i, fm_i, lm_i, ew_i = mb
                    rngs_i = list(jax.random.split(k, len(order)))

                    def loss_fn(p):
                        return self._loss(p, st, in_i, lab_i, fm_i, lm_i,
                                          rngs_i, ex_weight=ew_i, carries=None)

                    return loss_fn

                loss, new_state, grads = _accum_value_and_grad(
                    accum, params, state, batch, rng, make_loss_fn)
                new_carries = None
            else:
                rngs = list(jax.random.split(rng, len(order)))

                def loss_fn(p):
                    return self._loss(p, state, inputs, labels, fmasks, lmasks,
                                      rngs, ex_weight=ex_weight,
                                      carries=carries if with_carries else None)

                ((loss, (new_state, new_carries)), grads) = jax.value_and_grad(
                    loss_fn, has_aux=True)(params)
            if grad_exchange is not None:
                loss = grad_exchange.mean_loss(loss)
                new_state = grad_exchange.mean_state(new_state)
                new_params, new_opt, new_res = grad_exchange.update(
                    grads, params, opt_state, residuals, it)
                if g_skip:
                    # loss is already the replica mean → ok is replicated
                    ok = resilience.guard_ok(loss, g_limit)
                    new_params = resilience.guard_select(ok, new_params, params)
                    new_opt = resilience.guard_select(ok, new_opt, opt_state)
                    new_res = resilience.guard_select(ok, new_res, residuals)
                    new_state = resilience.guard_select(ok, new_state, state)
                return (new_params, (new_opt, new_res), new_state,
                        new_carries, loss)
            new_params, new_opt = self._update_params(
                params, opt_state, grads, it)
            if g_skip:
                ok = resilience.guard_ok(loss, g_limit)
                new_params = resilience.guard_select(ok, new_params, params)
                new_opt = resilience.guard_select(ok, new_opt, opt_state)
                new_state = resilience.guard_select(ok, new_state, state)
            return new_params, new_opt, new_state, new_carries, loss

        return step

    def _update_params(self, params, opt_state, grads, it):
        """Per-vertex optimizer update (normalization → updater →
        constraints) of the fused step body, under the scope ``update``."""
        order = self.topo_order
        updaters = self._updaters
        new_params, new_opt = {}, {}
        for name in order:
            g = grads[name]
            if not g:
                new_params[name] = params[name]
                new_opt[name] = opt_state[name]
                continue
            cfg = self.rt[name].config
            with jax.named_scope("update"), layer_scope(cfg, name):
                gn = getattr(cfg, "gradient_normalization", None)
                if gn:
                    g = apply_gradient_normalization(
                        gn, getattr(cfg, "gradient_normalization_threshold", 1.0), g
                    )
                upd, ns = updaters[name].update(g, opt_state[name], params[name], it)
                p_new = jax.tree_util.tree_map(
                    lambda p, d: p - d, params[name], upd
                )
                if getattr(cfg, "constraints", None):
                    from deeplearning4j_tpu.nn.constraints import apply_constraints

                    p_new = apply_constraints(cfg, p_new)
            new_params[name] = p_new
            new_opt[name] = ns
        return new_params, new_opt

    def _get_step_fn(self, with_carries: bool):
        if with_carries:
            if self._tbptt_step_fn is None:
                self._tbptt_step_fn = self._make_step(True)
            return self._tbptt_step_fn
        if self._step_fn is None:
            self._step_fn = self._make_step(False)
        return self._step_fn

    # -- chained steps (K per dispatch; mirrors MultiLayerNetwork) ---------
    def _chain_k(self) -> int:
        """Steps chained per dispatch in fit()'s hot loop (0 = per-step);
        policy shared with MultiLayerNetwork (_chain_k_from_env)."""
        from deeplearning4j_tpu.nn.model import _chain_k_from_env

        uses_rng = any(self.rt[n].config.uses_rng() for n in self.topo_order
                       if hasattr(self.rt[n].config, "uses_rng"))
        return _chain_k_from_env(uses_rng, self.num_params())

    def _make_chain_step(self):
        body = self._make_step_body()

        def chain(params, opt_state, state, it0, rng, inputs_k, labels_k):
            def scan_body(carry, inp):
                p, o, s, i = carry
                xs, ys = inp
                k = jax.random.fold_in(rng, i)
                p, o, s, _, loss = body(p, o, s, it0 + i, k, xs, ys,
                                        None, None, {})
                return (p, o, s, i + 1), loss

            (p, o, s, _), losses = jax.lax.scan(
                scan_body,
                (params, opt_state, state, jnp.asarray(0, jnp.int32)),
                (inputs_k, labels_k))
            return p, o, s, losses

        from deeplearning4j_tpu.nn.step_program import StepProgram

        # aot_wrap=False: chained dispatch bypasses the AOT warm dispatcher;
        # the StepProgram still runs the lazy cost-exemplar harvest
        return StepProgram(chain, "cg.chain", aot_wrap=False)

    def _get_chain_step(self):
        if getattr(self, "_chain_step_fn", None) is None:
            self._chain_step_fn = self._make_chain_step()
        return self._chain_step_fn

    def _initial_carries(self, batch: int) -> dict:
        if self._wrapped_rnn_vertices:
            raise NotImplementedError(
                "tBPTT / rnn_time_step cannot thread state through wrapper "
                f"RNN vertices {self._wrapped_rnn_vertices}: their inner RNN "
                "has no carry channel and would silently reset each chunk. "
                "Use the bare recurrent layer, or full-sequence calls.")
        return {
            name: self.rt[name].config.initial_carry(batch, self.dtype)
            for name in self._carry_vertices
        }

    def _time_distributed_inputs(self):
        """Input names whose InputType is recurrent — the time axis to chunk
        in tBPTT, decided from the declared types, not array rank (2-D
        integer token-id sequences are time-distributed too)."""
        return [n for n in self.conf.inputs
                if self.conf.input_types[n].kind == "recurrent"]

    # -- data normalization ------------------------------------------------
    def _norm_multi(self, v, n) -> Optional[Tuple]:
        """Normalize features/labels/masks to an n-tuple of arrays (or None)."""
        if v is None:
            return None
        if isinstance(v, (tuple, list)):
            return tuple(
                _cast_input(x, self.dtype) for x in v
            )
        return (_cast_input(v, self.dtype),) + (None,) * (n - 1) if n > 1 else (
            _cast_input(v, self.dtype),
        )

    def _as_multi_batch(self, batch):
        """Accept (x, y), (x, y, fmask, lmask) with array-or-tuple members, a
        dict, or a MultiDataSet/DataSet object — the MultiDataSet surface."""
        if hasattr(batch, "as_tuple"):
            batch = batch.as_tuple()
        if isinstance(batch, dict):
            f, l = batch["features"], batch.get("labels")
            fm, lm = batch.get("features_mask"), batch.get("labels_mask")
        else:
            f = batch[0]
            l = batch[1] if len(batch) > 1 else None
            fm = batch[2] if len(batch) > 2 else None
            lm = batch[3] if len(batch) > 3 else None
        ni, no = len(self.conf.inputs), len(self.conf.outputs)
        return (
            self._norm_multi(f, ni),
            self._norm_multi(l, no),
            self._norm_multi(fm, ni),
            self._norm_multi(lm, no),
        )

    def _input_dict(self, features: Tuple) -> Dict[str, jax.Array]:
        return dict(zip(self.conf.inputs, features))

    def _mask_dict(self, fmasks: Optional[Tuple]) -> Optional[Dict[str, Any]]:
        if fmasks is None:
            return None
        return dict(zip(self.conf.inputs, fmasks))

    # -- training ----------------------------------------------------------
    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        return self

    def fit(self, data, epochs: int = 1, batch_size: Optional[int] = None,
            resume_from=None):
        """Train on a MultiDataSet batch, an iterable of batches, or a
        callable returning a fresh iterable per epoch.

        ``resume_from``: a CheckpointListener directory — restore the newest
        VALID checkpoint and continue; ``epochs`` becomes the TOTAL budget
        and the interrupted epoch skips its already-consumed batches (same
        contract as MultiLayerNetwork.fit; docs/ROBUSTNESS.md)."""
        from deeplearning4j_tpu.train import resilience
        from deeplearning4j_tpu.train.listeners import close_listeners

        if self.params is None:
            self.init()
        resume_skip = 0
        if resume_from is not None:
            if resilience.resume(self, resume_from) is not None:
                resume_skip = int(getattr(self, "batch_in_epoch", 0))
                epochs = max(epochs - self.epoch, 0)
        guard = getattr(self, "divergence_guard", None)
        if aot.enabled():
            # time-to-first-step becomes a warm-path number: compile (or
            # reuse a bundle-restored executable for) the first batch's step
            # signature before the epoch loop dispatches. Mirrors the
            # per-epoch tbptt/chain gating below for epoch 0.
            _tbptt0 = (self.conf.backprop_type == "tbptt"
                       and bool(self._time_distributed_inputs()))
            _chain0 = (self._chain_k()
                       if not (self.listeners or _tbptt0) and guard is None
                       else 0)
            if not _tbptt0 and _chain0 <= 1:
                aot.warm_fit(self, data, batch_size)
        reports = StepReports(
            self, "cg", {name: v.config for name, v in self.rt.items()},
            overlap=guard is None)
        try:
            for _ in range(epochs):
                skip_n, resume_skip = resume_skip, 0
                self.batch_in_epoch = skip_n
                for l in self.listeners:
                    l.on_epoch_start(self, self.epoch)
                source = data() if callable(data) else data
                tbptt = (self.conf.backprop_type == "tbptt"
                         and bool(self._time_distributed_inputs()))
                chain_k = (self._chain_k()
                           if not (self.listeners or tbptt) and guard is None
                           else 0)
                buf: list = []
                # pad every batch (incl. the partial tail) to ONE row count
                # with a uniform ew/lmask calling convention → one compiled
                # step (mirrors MultiLayerNetwork.fit); the chained path
                # needs bare (f, l) batches, so it opts out
                pad_target = (self._fit_pad_target_multi(source, batch_size)
                              if chain_k <= 1 and not tbptt
                              and bucketing.bucketing_enabled() else None)

                def flush(full: bool):
                    # full K-groups go out as ONE dispatch; tails use the
                    # per-step path (a different K = a fresh compile)
                    if not buf:
                        return
                    with obs.span("cg.fit_batch", batches=len(buf)):
                        if full and len(buf) > 1:
                            self._fit_chained(buf)
                        else:
                            for bf, bl in buf:
                                self.fit_batch((bf, bl, None, None))
                    buf.clear()

                def batches():
                    it = self._iter_multi(source, batch_size)
                    # resume: already-consumed batches of the interrupted
                    # epoch are skipped HERE, without touching the RNG (the
                    # restored key is already past them)
                    for _ in range(skip_n):
                        if next(it, None) is None:
                            return
                    for f, l, fm, lm in it:
                        # real-row count taken HERE, before padding, so the
                        # fit loop never syncs ew back from device to learn it
                        n = len(f[0])
                        if pad_target is not None:
                            yield bucketing.pad_fit_multi(
                                f, l, fm, lm, pad_target, site="cg.fit") + (n,)
                        else:
                            yield (f, l, fm, lm, None, n)

                stream = batches()
                from deeplearning4j_tpu.nn.model import (
                    _batch_sig, _device_prefetch_enabled)
                if _device_prefetch_enabled():
                    # overlap next batch's host→device transfer with this
                    # step's compute (double buffering); AFTER padding,
                    # which is host-side
                    from deeplearning4j_tpu.datasets.iterator import prefetch_to_device

                    stream = prefetch_to_device(stream)
                stream = iter(stream)
                while True:
                    # one loop turn = one cg.iter span (mirrors
                    # MultiLayerNetwork.fit)
                    step_no = self.iteration
                    with obs.span("cg.iter", step=step_no):
                        with obs.span("cg.feed"):
                            item = next(stream, None)
                        if item is None:
                            reports.flush()
                            break
                        f, l, fm, lm, ew, n_real = item
                        batch = (f, l, fm, lm)
                        chainable = (
                            chain_k > 1 and fm is None and lm is None
                            and l is not None and all(y is not None for y in l)
                            and (not buf or _batch_sig(f + l)
                                 == _batch_sig(buf[0][0] + buf[0][1]))
                        )
                        if chainable:
                            buf.append((f, l))
                            self.batch_in_epoch += 1
                            if len(buf) == chain_k:
                                flush(True)
                            continue
                        flush(False)
                        reports.hold()
                        with obs.span("cg.fit_batch"):
                            if tbptt:
                                score = self._fit_tbptt(*batch)
                            else:
                                score = self.fit_batch(batch, ew=ew)
                        self.batch_in_epoch += 1
                        if guard is not None:
                            guard.observe(self, score)
                        if self.listeners:
                            # n_real came from the pre-padding host side of the
                            # stream
                            reports.step(score, step_no, n_real)
                flush(False)
                if guard is not None:
                    guard.flush(self)
                for l in self.listeners:
                    l.on_epoch_end(self, self.epoch)
                self.epoch += 1
        except Exception:
            # mirrors MultiLayerNetwork.fit: a step dispatched and not
            # reported yet is reported before the loop's exception goes on
            reports.flush_quietly()
            raise
        finally:
            # a run ending inside a ProfilerListener [start, stop) window
            # (normally or via an exception/chaos preempt) must not leak an
            # open jax.profiler trace
            close_listeners(self.listeners)
        return self

    def _is_single_multibatch(self, data) -> bool:
        """True when ``data`` is ONE in-memory MultiDataSet-like batch (not an
        iterable of batches). Disambiguation uses the model's input arity: a
        single batch's features must be one array (1-input nets) or a tuple
        of exactly len(inputs) arrays."""
        def _is_arr(v):
            return isinstance(v, (np.ndarray, jax.Array)) or hasattr(v, "__array__")

        ni = len(self.conf.inputs)

        def _features_like(f):
            if _is_arr(f):
                return ni == 1
            return (
                isinstance(f, (tuple, list))
                and len(f) == ni
                and all(_is_arr(e) for e in f)
            )

        return (isinstance(data, dict)
                or (isinstance(data, (tuple, list)) and 2 <= len(data) <= 4
                    and _features_like(data[0])))

    def _fit_pad_target_multi(self, data, batch_size) -> Optional[int]:
        """Uniform per-batch row count for fit() over one in-memory batch
        source, or None (mirrors model._fit_pad_target: only worth padding
        when minibatching leaves a partial tail that would otherwise trace a
        second training executable)."""
        if batch_size is None:
            return None
        if hasattr(data, "as_tuple"):
            data = data.as_tuple()
        if self._is_single_multibatch(data):
            f, _, _, _ = self._as_multi_batch(data)
            n = f[0].shape[0]
            if n > batch_size and n % batch_size != 0:
                return batch_size
        return None

    def _iter_multi(self, data, batch_size):
        """Yield MultiDataSet batches. A bare (features, labels) pair of
        arrays/tuples is minibatched when batch_size is given."""
        if hasattr(data, "as_tuple"):  # datasets.DataSet / MultiDataSet
            data = data.as_tuple()

        if self._is_single_multibatch(data):
            f, l, fm, lm = self._as_multi_batch(data)
            n = f[0].shape[0]
            if batch_size is None or batch_size >= n:
                yield (f, l, fm, lm)
                return
            sl_t = lambda t, s: tuple(x[s] if x is not None else None for x in t) if t else None
            for i in range(0, n, batch_size):
                s = slice(i, min(i + batch_size, n))
                yield (sl_t(f, s), sl_t(l, s), sl_t(fm, s), sl_t(lm, s))
            return
        for b in data:
            yield self._as_multi_batch(b)

    def fit_batch(self, batch, ew=None):
        """One jitted step on one (already normalized or raw) batch.
        ``ew``: optional per-example validity weight (ParallelWrapper
        padding) consumed by batch-coupled layer vertices — see _forward."""
        if isinstance(batch, tuple) and len(batch) == 4 and isinstance(batch[0], tuple) \
                and all(x is None or isinstance(x, (jax.Array, np.ndarray))
                        for x in batch[0]):
            f, l, fm, lm = batch
        else:
            f, l, fm, lm = self._as_multi_batch(batch)
        from deeplearning4j_tpu.train import resilience

        chaos = resilience.active_chaos()
        if chaos is not None:
            chaos.maybe_preempt(self.iteration)
            chaos.maybe_slow(self.iteration)
            f = chaos.maybe_nan_batch(self.iteration, f)
        step = self._get_step_fn(False)
        # dispatch() runs the step, then the retrace-guard check the program
        # owns: traces land at cg.step (inside the jitted body), bucket
        # traffic lands at cg.fit (pad_fit_multi) — the guard joins the two
        self.params, self.opt_state, self.state, _, loss = step.dispatch(
            self.params, self.opt_state, self.state,
            jnp.asarray(self.iteration, jnp.int32), self._next_rng(),
            self._input_dict(f), l, self._mask_dict(fm), lm, {},
            ex_weight=jnp.asarray(ew, self.dtype) if ew is not None else None,
        )
        self.iteration += 1
        return loss

    def _fit_tbptt(self, f, l, fm, lm):
        """Truncated BPTT over the DAG (ComputationGraph.java:950,1179
        doTruncatedBPTT): chunk the time axis of every recurrent input (and
        time-distributed labels/masks), carry RNN-vertex state across chunks
        with stopped gradients. Static ([B,F]) inputs are re-fed whole to
        every chunk — the DuplicateToTimeSeriesVertex use case."""
        from deeplearning4j_tpu.train import resilience

        chaos = resilience.active_chaos()
        if chaos is not None:
            chaos.maybe_preempt(self.iteration)
            chaos.maybe_slow(self.iteration)
        step = self._get_step_fn(True)
        td_inputs = set(self._time_distributed_inputs())
        T = max(x.shape[1] for n, x in zip(self.conf.inputs, f) if n in td_inputs)
        L = self.conf.tbptt_fwd_length
        B = f[0].shape[0]
        carries = self._initial_carries(B)

        slice_t = lambda x, sl, kind: _tbptt_slice_t(x, sl, T, kind)
        total, nchunks = 0.0, 0
        for t0 in range(0, T, L):
            sl = slice(t0, min(t0 + L, T))
            fc = tuple(
                _cast_input(slice_t(x, sl, "feat_td" if n in td_inputs else "feat"),
                            self.dtype)
                for n, x in zip(self.conf.inputs, f))
            lc = tuple(_cast_labels(slice_t(y, sl, "label"), self.dtype)
                       for y in l) if l is not None else None
            fmc = tuple(jnp.asarray(slice_t(m, sl, "mask"), self.dtype)
                        if m is not None else None
                        for m in fm) if fm is not None else None
            lmc = tuple(jnp.asarray(slice_t(m, sl, "mask"), self.dtype)
                        if m is not None else None
                        for m in lm) if lm is not None else None
            self.params, self.opt_state, self.state, carries, loss = step(
                self.params, self.opt_state, self.state,
                jnp.asarray(self.iteration, jnp.int32), self._next_rng(),
                self._input_dict(fc), lc, self._mask_dict(fmc), lmc, carries,
            )
            # truncation is structural: each chunk is its own jitted step, so
            # the concrete carry arrays carry values, never gradients
            total = total + loss
            nchunks += 1
            self.iteration += 1
        return total / max(nchunks, 1)

    def _fit_chained(self, buf) -> None:
        """One dispatch covering len(buf) train steps (lax.scan of the
        step body over stacked batches; mirrors MultiLayerNetwork)."""
        chain = self._get_chain_step()
        ni, no = len(self.conf.inputs), len(self.conf.outputs)
        fk = tuple(jnp.stack([b[0][i] for b in buf]) for i in range(ni))
        lk = tuple(jnp.stack([b[1][i] for b in buf]) for i in range(no))
        self.params, self.opt_state, self.state, _ = chain(
            self.params, self.opt_state, self.state,
            jnp.asarray(self.iteration, jnp.int32), self._next_rng(),
            self._input_dict(fk), lk)
        self.iteration += len(buf)

    def _next_rng(self):
        self._rng, k = jax.random.split(self._rng)
        return k

    # -- inference ---------------------------------------------------------
    def _get_output_fn(self):
        """The jitted inference entry point, AOT-wrapped so warmup
        (``nn/aot.py``) can pre-compile every ladder bucket and bundle
        restore can install persisted executables."""
        if self._output_fn is None:
            def fwd(params, state, inputs, masks):
                # python body runs once per trace → counts actual compiles
                bucketing.telemetry().record_trace(
                    "cg.output", np.shape(next(iter(inputs.values()))))
                acts, _, _, _ = self._forward(params, state, inputs, train=False,
                                              rngs=None, masks=masks)
                return tuple(acts[o] for o in self.conf.outputs)

            from deeplearning4j_tpu.nn.step_program import StepProgram

            self._output_fn = StepProgram(
                fwd, "cg.output", model=self, donate_argnums=())
        return self._output_fn

    def output(self, *xs, fmasks=None):
        """Outputs of all output vertices (ComputationGraph.output:1754).
        Returns a single array when the graph has one output.

        Batch rows are padded up to the shared bucket ladder before dispatch
        (and sliced back off) so mixed caller batch sizes share one compiled
        executable per bucket; skipped for graphs with Stack/Unstack
        vertices, whose batch-axis arithmetic padding would corrupt.
        Disable via DL4J_TPU_BUCKETING=0."""
        if len(xs) == 1 and isinstance(xs[0], (tuple, list)):
            xs = tuple(xs[0])
        feats = tuple(_cast_input(x, self.dtype) for x in xs)
        fm = self._norm_multi(fmasks, len(self.conf.inputs)) if fmasks is not None else None
        self._get_output_fn()
        n = feats[0].shape[0] if feats else 0
        # dispatch() opens the cg.output span
        if (bucketing.bucketing_enabled() and n > 0
                and not self._has_batch_vertices):
            target = bucketing.bucket_size(n)
            bucketing.telemetry().record_hit("cg.output", n, target)
            if target > n:
                feats = tuple(bucketing.pad_rows_zero(x, target) for x in feats)
                if fm is not None:
                    fm = tuple(bucketing.pad_rows_zero(m, target)
                               if m is not None else None for m in fm)
                outs = self._output_fn.dispatch(
                    self.params, self.state, self._input_dict(feats),
                    self._mask_dict(fm))
                outs = tuple(bucketing.unpad(o, n) for o in outs)
                return outs[0] if len(outs) == 1 else outs
        outs = self._output_fn.dispatch(
            self.params, self.state, self._input_dict(feats),
            self._mask_dict(fm))
        return outs[0] if len(outs) == 1 else outs

    # -- streaming RNN inference (ComputationGraph.rnnTimeStep:2718) -------
    def rnn_time_step(self, *xs):
        """Feed one or more timesteps per recurrent input, carrying RNN-vertex
        state between calls (rnnTimeStep:2718-2800 /
        rnnActivateUsingStoredState:1334). A 2-D array for a recurrent input
        means a single timestep; outputs are squeezed back to 2-D in that
        case. Static inputs pass [B,F] unchanged."""
        if len(xs) == 1 and isinstance(xs[0], (tuple, list)):
            xs = tuple(xs[0])
        feats, squeeze = [], False
        for name, x in zip(self.conf.inputs, xs):
            x = _cast_input(x, self.dtype)
            if self.conf.input_types[name].kind == "recurrent":
                if jnp.issubdtype(x.dtype, jnp.integer):
                    # token-id stream: full input is [B,T]; [B] = one step
                    if x.ndim == 1:
                        x = x[:, None]
                        squeeze = True
                elif x.ndim == 2:
                    x = x[:, None, :]
                    squeeze = True
            feats.append(x)
        B = feats[0].shape[0]
        leaves = (jax.tree_util.tree_leaves(self._rnn_carries)
                  if self._rnn_carries is not None else [])
        if self._rnn_carries is None or (leaves and leaves[0].shape[0] != B):
            self._rnn_carries = self._initial_carries(B)
        acts, _, _, self._rnn_carries = self._forward(
            self.params, self.state, self._input_dict(tuple(feats)),
            train=False, rngs=None, carries=self._rnn_carries)
        outs = tuple(
            a[:, 0, :] if squeeze and a.ndim == 3 and a.shape[1] == 1 else a
            for a in (acts[o] for o in self.conf.outputs)
        )
        return outs[0] if len(outs) == 1 else outs

    def rnn_clear_previous_state(self):
        self._rnn_carries = None

    def score(self, batch, train: bool = False) -> float:
        """Average loss on a batch. ``train=True`` scores with training-mode
        statistics (BatchNorm uses the batch's own mean/var, not the running
        estimates) while dropout / weight noise stay disabled — deterministic;
        see MultiLayerNetwork.score."""
        f, l, fm, lm = self._as_multi_batch(batch)
        loss, _ = self._loss(self.params, self.state, self._input_dict(f), l,
                             self._mask_dict(fm), lm, rngs=None, train=train,
                             deterministic=True)
        return float(loss)

    def evaluate(self, data, batch_size: Optional[int] = None, top_n: int = 1):
        """Single-output classification evaluation."""
        from deeplearning4j_tpu.eval import Evaluation

        ev = Evaluation(top_n=top_n)
        for f, l, fm, lm in self._iter_multi(data, batch_size):
            preds = self.output(*f, fmasks=fm)
            y = l[0] if isinstance(l, tuple) else l
            m = lm[0] if isinstance(lm, (tuple, list)) and lm else None
            ev.eval(np.asarray(y), np.asarray(preds), mask=np.asarray(m) if m is not None else None)
        return ev

    # -- misc --------------------------------------------------------------
    def clone(self) -> "ComputationGraph":
        m = ComputationGraph(self.conf)
        if self.params is not None:
            m.init()
            copy = lambda t: jax.tree_util.tree_map(jnp.copy, t)
            m.params = copy(self.params)
            m.state = copy(self.state)
            m.opt_state = copy(self.opt_state)
            m.iteration = self.iteration
            m.epoch = self.epoch
        return m

    def summary(self) -> str:
        lines = [f"{'name':<24} {'type':<24} {'inputs':<30} {'output':<22} {'params':<10}"]
        for name in self.topo_order:
            v = self.rt[name]
            tname = getattr(v.config, "_type_name", getattr(v.config, "_vtype_name", "?"))
            n = (
                sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(self.params[name]))
                if self.params is not None else "?"
            )
            lines.append(
                f"{name:<24} {tname:<24} {','.join(v.inputs)[:30]:<30} "
                f"{str(v.out_type.batch_shape())[:22]:<22} {n:<10}"
            )
        lines.append(f"Total params: {self.num_params() if self.params is not None else '?'}")
        return "\n".join(lines)
