"""Graph-level convolution compiler: spec list -> layer IR -> pass pipeline
-> one deployable, serializable NetworkPlan.

Before this module the paper's section-4 deployment insight (transform
filters once offline, run inference with zero per-call transform work) was
scattered across six ad-hoc entry points (plan_conv2d, plan_separable_block,
plan_inverted_residual, plan_conv1d, plan_depthwise_conv1d, plan_cnn /
plan_stem), each with its own plan class and apply signature, and the
fusion decisions (dw+pw -> one kernel) were hand-written branches inside
models/cnn.py:plan_cnn. This module is the compiler those entry points
become shims over:

  * `LayerIR` -- a declarative graph node (conv2d / conv1d / pool / concat /
    add / dense / ...). `lower()` turns the models/cnn.py spec lists (and
    the models/audio.py stem) into IR; SeparableConv and InvertedResidual
    specs lower to their *unfused* conv chains.
  * the pass pipeline `lower -> fuse -> place -> bind`:
      - `fuse` is registry-aware pattern rewriting over the IR: a depthwise
        conv followed 1:1 by a pointwise 1x1 rewrites to a `separable`
        node (SeparableBlockPlan -- the fused streamed kernel where the
        capability matches, the composed pair otherwise), and the
        expand -> depthwise -> linear-project [-> residual add] chain
        rewrites to an `inverted_residual` node. No model file hand-codes a
        fusion decision anymore; new fusions are new patterns here.
      - `place` maps the caller's global algorithm request onto each node
        via capability-registry queries (the per-layer fallback the paper's
        mixed policy needs).
      - `bind` builds the concrete LayerPlan objects (all per-layer
        decisions + the one-time filter transforms) and collects the
        epilogue constants (biases, dense weights).
  * `compile(params, graph, *, res, ...) -> NetworkPlan` -- the one entry
    point. NetworkPlan executes the graph (`apply`), renders the per-layer
    algorithm table (`describe`, same markdown generator as the registry's
    README table), and round-trips to disk (`save`/`load`): the artifact
    holds the pre-transformed execution-domain weights plus every per-layer
    algorithm decision under a versioned header, so a second process starts
    warm -- no re-planning, no re-measuring, no filter-transform ops. A
    header mismatch (format/version, dtype, layout, capability-registry
    fingerprint) refuses with an actionable error instead of silently
    recomputing.
"""

from __future__ import annotations

import dataclasses
import hashlib
import itertools
import json
import os
import time
import zipfile
from typing import Any, Iterator, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import partition as _partition
from repro.core import plan as _plan
from repro.core import registry
from repro.kernels import runtime as _kernel_runtime
from repro.obs import trace as _obs_trace

ARTIFACT_FORMAT = "repro.network_plan"
#: node attrs passed to a plan's apply (the rest are in the plan)
_EPILOGUE_ATTRS = ("activation", "inner_activation")
# v2: conv layer metas gained the fft/winograd_f63 algorithms plus N-way
# autotune evidence (winner/winner_tile and per-contender timings); v1
# readers would mis-plan those layers, so the version gates them out.
# v3: the header carries per-array sha256 digests and load() verifies every
# array against them, so silent storage corruption (bit rot, truncated
# copies) raises ArtifactMismatchError -- and triggers the serving layer's
# recompile-in-place path -- instead of producing wrong outputs. A v2
# artifact has no digests to verify, so the version gates it out.
# v4: the header carries the network-level compute_dtype policy and conv
# plan metas may store reduced-precision (bf16/int8) transform-domain
# filters plus their per-output-channel dequantization scale arrays. A v3
# reader would drop the scales and serve un-dequantized int8 outputs, so
# the version gates it out.
# v5: the header carries the partition record (mesh kind/axis/shard count
# plus the spatial walk's per-node modes, halos and re-scatter points), and
# partitioned plans are bound at shard-LOCAL geometry -- a v4 reader would
# apply those plans to global-shape inputs and fail or mis-shape, so the
# version gates it out. Warm starts restore the recorded partitioning
# without re-deciding; the device mesh itself is never serialized (attach
# one with with_mesh() / compile(mesh=)).
ARTIFACT_VERSION = 5

#: IR ops that bind to a LayerPlan (everything else is structural/XLA-only).
PLAN_OPS = ("conv2d", "conv1d", "separable", "inverted_residual")


_DEPRECATION_WARNED: set[str] = set()


def warn_deprecated(api: str, replacement: str) -> None:
    """Emit ONE actionable DeprecationWarning per legacy entry point per
    process (the legacy plan_* shims call this on their way into
    compile())."""
    if api in _DEPRECATION_WARNED:
        return
    _DEPRECATION_WARNED.add(api)
    import warnings
    warnings.warn(
        f"{api} is deprecated; use {replacement} -- the compile() API "
        f"subsumes it (fusion passes, per-layer placement, and "
        f"NetworkPlan.save/load deployment artifacts).",
        DeprecationWarning, stacklevel=3)


class ArtifactMismatchError(ValueError):
    """A saved NetworkPlan artifact cannot be loaded by this build: wrong
    format/version, stale capability registry, dtype/layout mismatch, or an
    array that fails its recorded sha256 integrity digest (storage
    corruption). The message states the mismatch and the fix (recompile +
    save)."""


class LayerExecutionError(RuntimeError):
    """One graph node's executor raised during NetworkPlan.apply. Carries
    `node_id` so a supervisor (repro.runtime.serve) can re-place exactly the
    failing layer onto a fallback executor; the original exception is
    chained as __cause__. Only raised when apply(annotate_errors=True)."""

    def __init__(self, node_id: str, cause: BaseException):
        super().__init__(f"layer {node_id!r} failed: {cause!r}")
        self.node_id = node_id


def _meta_compute_dtypes(meta: dict) -> tuple[tuple[str, str], ...]:
    """(executor, compute_dtype) leaves of one plan meta, recursing through
    the block kinds (separable / inverted residual hold nested conv metas).
    Feeds the dtype-mismatch refusal's per-layer enumeration."""
    kind = meta.get("kind")
    if kind == "conv2d":
        return ((meta.get("algorithm", "?"),
                 meta.get("compute_dtype", "float32")),)
    if kind == "separable":
        if meta.get("mode") == "fused_pallas":
            return (("separable_streamed", "float32"),)
        return (_meta_compute_dtypes(meta["dw"])
                + _meta_compute_dtypes(meta["pw"]))
    if kind == "inverted_residual":
        out = ()
        if meta.get("expand") is not None:
            out += _meta_compute_dtypes(meta["expand"])
        return out + _meta_compute_dtypes(meta["sep"])
    return ()


def _artifact_dtype_report(header: dict) -> str:
    """Per-layer enumeration for dtype-mismatch refusals: each layer's
    on-disk transform-domain compute dtype(s) next to what THIS build's
    capability registry declares its executor(s) can run -- so the caller
    sees at a glance which layers a recompile at the expected precision
    would actually change."""
    lines = []
    for nid, meta in header.get("plans", {}).items():
        leaves = _meta_compute_dtypes(meta)
        if not leaves:
            continue
        part = ", ".join(
            f"{ex}={cd}"
            f"(registry: {'/'.join(registry.compute_dtypes_for(ex))})"
            for ex, cd in leaves)
        lines.append(f"{nid}[{part}]")
    return "; ".join(lines)


def _array_digest(a: np.ndarray) -> str:
    """sha256 over dtype + shape + raw bytes of one artifact array -- the
    per-array integrity record save() writes and load() verifies."""
    a = np.ascontiguousarray(a)
    h = hashlib.sha256()
    h.update(f"{a.dtype}:{a.shape}".encode())
    h.update(a.tobytes())
    return h.hexdigest()


# ---------------------------------------------------------------------------
# Layer IR
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LayerIR:
    """One node of the layer IR: an op name, graph edges (`inputs` name
    producer nodes), and op attributes (filter geometry, activation,
    parameter paths into the params pytree). The graph is a tuple of nodes
    in topological order whose first node is the single `input` and whose
    last node is the network output."""

    id: str
    op: str                    # input | conv2d | conv1d | separable |
                               # inverted_residual | pool | concat | add |
                               # global_avg_pool | dense
    inputs: tuple[str, ...] = ()
    attrs: Mapping[str, Any] = dataclasses.field(default_factory=dict)
    block: str | None = None   # origin spec name; fusion rewrites name the
                               # fused node after the shared block


def _is_ir(graph) -> bool:
    return (len(graph) > 0
            and all(isinstance(n, LayerIR) for n in graph))


# ---------------------------------------------------------------------------
# lower: models/cnn.py spec lists -> IR
# ---------------------------------------------------------------------------

def lower(specs: Sequence, c_in: int = 3) -> tuple[LayerIR, ...]:
    """Lower a models/cnn.py spec list to the layer IR. Composite specs
    (SeparableConv, InvertedResidual) lower to their UNFUSED conv chains --
    reconstituting the fused execution units is the fuse pass's job, so
    fusion is a graph rewrite, not a property of the input format. Channel
    counts are tracked through the walk (they determine depthwise groups
    and residual feasibility); spatial shapes are inferred later."""
    from repro.models import cnn as _cnn

    nodes = [LayerIR(id="input", op="input")]
    counter = itertools.count()

    def uid(prefix: str) -> str:
        return f"{prefix}_{next(counter)}"

    def conv_node(nid, head, *, kh, kw, c_out, stride, padding, groups,
                  depthwise, activation, w_path, b_path, block):
        nodes.append(LayerIR(
            id=nid, op="conv2d", inputs=(head,),
            attrs=dict(kh=kh, kw=kw, c_out=c_out, stride=(stride, stride),
                       padding=padding, groups=groups, depthwise=depthwise,
                       activation=activation, w_path=w_path, b_path=b_path),
            block=block))
        return nid

    def walk(specs, head: str, c: int) -> tuple[str, int]:
        for spec in specs:
            if isinstance(spec, _cnn.Conv):
                head = conv_node(
                    spec.name, head, kh=spec.kh, kw=spec.kw,
                    c_out=spec.c_out, stride=spec.stride,
                    padding=spec.padding, groups=spec.groups,
                    depthwise=spec.groups > 1 and spec.groups == c,
                    activation=spec.act, w_path=(spec.name, "w"),
                    b_path=(spec.name, "b"), block=spec.name)
                c = spec.c_out
            elif isinstance(spec, _cnn.SeparableConv):
                head = conv_node(
                    f"{spec.name}.dw", head, kh=spec.k, kw=spec.k, c_out=c,
                    stride=spec.stride, padding=spec.padding, groups=c,
                    depthwise=True, activation="relu",
                    w_path=(spec.name, "dw", "w"),
                    b_path=(spec.name, "dw", "b"), block=spec.name)
                head = conv_node(
                    f"{spec.name}.pw", head, kh=1, kw=1, c_out=spec.c_out,
                    stride=1, padding="SAME", groups=1, depthwise=False,
                    activation="relu", w_path=(spec.name, "pw", "w"),
                    b_path=(spec.name, "pw", "b"), block=spec.name)
                c = spec.c_out
            elif isinstance(spec, _cnn.InvertedResidual):
                src = head
                ce = c * spec.expand
                if spec.expand != 1:
                    head = conv_node(
                        f"{spec.name}.exp", head, kh=1, kw=1, c_out=ce,
                        stride=1, padding="SAME", groups=1, depthwise=False,
                        activation="relu6", w_path=(spec.name, "exp", "w"),
                        b_path=(spec.name, "exp", "b"), block=spec.name)
                head = conv_node(
                    f"{spec.name}.dw", head, kh=spec.k, kw=spec.k, c_out=ce,
                    stride=spec.stride, padding="SAME", groups=ce,
                    depthwise=True, activation="relu6",
                    w_path=(spec.name, "dw", "w"),
                    b_path=(spec.name, "dw", "b"), block=spec.name)
                head = conv_node(
                    f"{spec.name}.pw", head, kh=1, kw=1, c_out=spec.c_out,
                    stride=1, padding="SAME", groups=1, depthwise=False,
                    activation="none", w_path=(spec.name, "pw", "w"),
                    b_path=(spec.name, "pw", "b"), block=spec.name)
                if spec.stride == 1 and c == spec.c_out:
                    add_id = f"{spec.name}.add"
                    nodes.append(LayerIR(id=add_id, op="add",
                                         inputs=(src, head),
                                         block=spec.name))
                    head = add_id
                c = spec.c_out
            elif isinstance(spec, _cnn.Pool):
                pid = uid("pool")
                nodes.append(LayerIR(
                    id=pid, op="pool", inputs=(head,),
                    attrs=dict(kind=spec.kind, k=spec.k, stride=spec.stride,
                               padding=spec.padding)))
                head = pid
            elif isinstance(spec, _cnn.Concat):
                tails, c_total = [], 0
                for br in spec.branches:
                    tail, cb = walk(br, head, c)
                    tails.append(tail)
                    c_total += cb
                cid = uid("concat")
                nodes.append(LayerIR(id=cid, op="concat",
                                     inputs=tuple(tails)))
                head, c = cid, c_total
            elif isinstance(spec, _cnn.GlobalAvgPool):
                gid = uid("gap")
                nodes.append(LayerIR(id=gid, op="global_avg_pool",
                                     inputs=(head,)))
                head = gid
            elif isinstance(spec, _cnn.Dense):
                nodes.append(LayerIR(
                    id=spec.name, op="dense", inputs=(head,),
                    attrs=dict(n_out=spec.n_out, relu=spec.relu,
                               w_path=(spec.name, "w"))))
                head, c = spec.name, spec.n_out
            else:
                raise TypeError(
                    f"cannot lower spec {spec!r}; expected one of the "
                    f"models.cnn layer specs or a pre-lowered LayerIR graph")
        return head, c

    walk(specs, "input", c_in)
    return tuple(nodes)


# ---------------------------------------------------------------------------
# shape inference
# ---------------------------------------------------------------------------

def _out_size(size: int, k: int, stride: int, padding: str) -> int:
    if padding == "SAME":
        return -(-size // stride)
    return (size - k) // stride + 1


def infer_shapes(graph: Sequence[LayerIR],
                 input_shape: Sequence[int]) -> dict[str, tuple[int, ...]]:
    """Output shape of every node, walking the graph once."""
    shapes: dict[str, tuple[int, ...]] = {}
    for node in graph:
        a = node.attrs
        if node.op == "input":
            shapes[node.id] = tuple(input_shape)
            continue
        ins = [shapes[i] for i in node.inputs]
        s = ins[0]
        if node.op == "conv2d":
            n, h, w, _ = s
            shapes[node.id] = (
                n, _out_size(h, a["kh"], a["stride"][0], a["padding"]),
                _out_size(w, a["kw"], a["stride"][1], a["padding"]),
                a["c_out"])
        elif node.op in ("separable", "inverted_residual"):
            n, h, w, _ = s
            shapes[node.id] = (
                n, _out_size(h, a["k"], a["stride"][0], a["padding"]),
                _out_size(w, a["k"], a["stride"][1], a["padding"]),
                a["c_out"])
        elif node.op == "conv1d":
            b, t, _ = s
            shapes[node.id] = (
                b, _out_size(t, a["k"], a["stride"], a["padding"]),
                a["c_out"])
        elif node.op == "pool":
            n, h, w, c = s
            shapes[node.id] = (
                n, _out_size(h, a["k"], a["stride"], a["padding"]),
                _out_size(w, a["k"], a["stride"], a["padding"]), c)
        elif node.op == "concat":
            shapes[node.id] = s[:-1] + (sum(i[-1] for i in ins),)
        elif node.op == "add":
            shapes[node.id] = s
        elif node.op == "global_avg_pool":
            shapes[node.id] = (s[0], s[-1])
        elif node.op == "dense":
            shapes[node.id] = (s[0], a["n_out"])
        else:
            raise ValueError(f"unknown IR op {node.op!r} ({node.id})")
    return shapes


# ---------------------------------------------------------------------------
# fuse: registry-aware pattern rewrites
# ---------------------------------------------------------------------------

def _consumers(graph: Sequence[LayerIR]) -> dict[str, list[str]]:
    cons: dict[str, list[str]] = {n.id: [] for n in graph}
    for n in graph:
        for i in n.inputs:
            cons[i].append(n.id)
    return cons


def _rewrite(graph, remove: set, replace: dict) -> tuple[LayerIR, ...]:
    """Drop `remove` nodes, swap pattern tails for their fused nodes, and
    rewire edges that referenced a swapped tail."""
    rename = {old: new.id for old, new in replace.items()}
    out = []
    for n in graph:
        if n.id in remove:
            continue
        n = replace.get(n.id, n)
        out.append(dataclasses.replace(
            n, inputs=tuple(rename.get(i, i) for i in n.inputs)))
    return tuple(out)


def _fused_name(tail: LayerIR, parts: list[LayerIR]) -> str:
    blocks = {p.block for p in parts}
    if len(blocks) == 1 and tail.block:
        return tail.block
    return "+".join(p.id for p in parts if p.op == "conv2d")


def _fuse_inverted_residual(graph: Sequence[LayerIR]) -> tuple[LayerIR, ...]:
    """Pattern: [1x1 expand conv (act)] -> kxk depthwise (same act, mult 1)
    -> 1x1 linear projection [-> residual add with the chain input], each
    intermediate consumed exactly once => one `inverted_residual` node
    (bound to plan_inverted_residual: the dw+project pair rides the
    separable-block machinery, fusing to a single streamed kernel where the
    capability registry covers it)."""
    by_id = {n.id: n for n in graph}
    cons = _consumers(graph)
    remove: set[str] = set()
    replace: dict[str, LayerIR] = {}
    for pw in graph:
        if pw.op != "conv2d" or pw.id in remove:
            continue
        pa = pw.attrs
        if not (pa["kh"] == pa["kw"] == 1 and pa["groups"] == 1
                and tuple(pa["stride"]) == (1, 1)
                and pa["activation"] == "none"):
            continue
        dw = by_id.get(pw.inputs[0])
        if (dw is None or dw.op != "conv2d"
                or not dw.attrs.get("depthwise")
                or dw.attrs["kh"] != dw.attrs["kw"]
                or dw.attrs["c_out"] != dw.attrs["groups"]   # multiplier 1
                or cons[dw.id] != [pw.id] or dw.id in remove):
            continue
        head = dw.inputs[0]
        exp = by_id.get(head)
        exp_node = None
        if (exp is not None and exp.op == "conv2d" and exp.id not in remove
                and exp.attrs["kh"] == exp.attrs["kw"] == 1
                and exp.attrs["groups"] == 1
                and tuple(exp.attrs["stride"]) == (1, 1)
                and exp.attrs["activation"] == dw.attrs["activation"]
                and cons[exp.id] == [dw.id]):
            exp_node = exp
            head = exp.inputs[0]
        tail, residual = pw, False
        if len(cons[pw.id]) == 1:
            cand = by_id[cons[pw.id][0]]
            if cand.op == "add" and set(cand.inputs) == {head, pw.id}:
                tail, residual = cand, True
        parts = ([exp_node] if exp_node else []) + [dw, pw]
        attrs = dict(
            k=dw.attrs["kh"], stride=tuple(dw.attrs["stride"]),
            padding=dw.attrs["padding"], c_out=pa["c_out"],
            activation=dw.attrs["activation"], residual=residual,
            exp_w=exp_node.attrs["w_path"] if exp_node else None,
            exp_b=exp_node.attrs["b_path"] if exp_node else None,
            dw_w=dw.attrs["w_path"], dw_b=dw.attrs["b_path"],
            pw_w=pw.attrs["w_path"], pw_b=pw.attrs["b_path"])
        fused = LayerIR(id=_fused_name(tail, parts), op="inverted_residual",
                        inputs=(head,), attrs=attrs,
                        block=tail.block or dw.block)
        replace[tail.id] = fused
        remove |= {p.id for p in parts} - {tail.id}
    return _rewrite(graph, remove, replace) if replace else tuple(graph)


def _fuse_separable(graph: Sequence[LayerIR]) -> tuple[LayerIR, ...]:
    """Pattern: kxk depthwise conv consumed exactly once by a stride-1
    dense 1x1 conv => one `separable` node (bound to plan_separable_block:
    the fused streamed kernel where the registry capability matches --
    stride 1, suitable k, multiplier 1 -- and the composed pair otherwise,
    so the rewrite is always semantics-preserving)."""
    by_id = {n.id: n for n in graph}
    cons = _consumers(graph)
    remove: set[str] = set()
    replace: dict[str, LayerIR] = {}
    for pw in graph:
        if pw.op != "conv2d" or pw.id in remove:
            continue
        pa = pw.attrs
        if not (pa["kh"] == pa["kw"] == 1 and pa["groups"] == 1
                and tuple(pa["stride"]) == (1, 1)):
            continue
        dw = by_id.get(pw.inputs[0])
        if (dw is None or dw.op != "conv2d"
                or not dw.attrs.get("depthwise")
                or dw.attrs["kh"] != dw.attrs["kw"]
                or cons[dw.id] != [pw.id] or dw.id in remove):
            continue
        attrs = dict(
            k=dw.attrs["kh"], stride=tuple(dw.attrs["stride"]),
            padding=dw.attrs["padding"], c_out=pa["c_out"],
            inner_activation=dw.attrs["activation"],
            activation=pa["activation"],
            dw_w=dw.attrs["w_path"], dw_b=dw.attrs["b_path"],
            pw_w=pa["w_path"], pw_b=pa["b_path"])
        fused = LayerIR(id=_fused_name(pw, [dw, pw]), op="separable",
                        inputs=dw.inputs, attrs=attrs,
                        block=pw.block or dw.block)
        replace[pw.id] = fused
        remove.add(dw.id)
    return _rewrite(graph, remove, replace) if replace else tuple(graph)


#: The fusion pass pipeline, most specific pattern first (the inverted
#: residual's linear-projection chain would otherwise be half-claimed by the
#: generic separable rewrite).
FUSION_PASSES = (_fuse_inverted_residual, _fuse_separable)


def fuse(graph: Sequence[LayerIR]) -> tuple[LayerIR, ...]:
    """Run the registered fusion rewrites over the IR."""
    for p in FUSION_PASSES:
        graph = p(graph)
    return tuple(graph)


# ---------------------------------------------------------------------------
# place: per-node algorithm decisions (registry queries)
# ---------------------------------------------------------------------------

def place(graph: Sequence[LayerIR], shapes: dict[str, tuple[int, ...]],
          algorithm: str = "auto",
          compute_dtype: str = "float32") -> dict[str, dict]:
    """Map the global algorithm request onto each plan-bearing node. A
    forced family falls back to im2col on layers its executors do not cover
    (the paper's mixed policy applied to a forced setting) -- a capability-
    registry query, exactly like the legacy models/cnn.py:_layer_algorithm.
    The same per-layer fallback applies to a reduced compute_dtype: a conv
    layer none of whose covering executors declare the dtype is placed back
    at fp32 instead of refusing the whole network. Block nodes (separable /
    inverted residual) keep the family request: their plan builders run
    their own capability-aware internal placement (fused streamed kernel vs
    composed sub-plans)."""
    placements: dict[str, dict] = {}
    for node in graph:
        if node.op not in PLAN_OPS:
            continue
        a = node.attrs
        if node.op == "conv2d":
            c_in = shapes[node.inputs[0]][-1]
            groups = c_in if a.get("depthwise") else a["groups"]
            q = registry.as_query(a["kh"], a["kw"], tuple(a["stride"]),
                                  groups=groups, c_in=c_in, c_out=a["c_out"])
            alg = (algorithm if registry.supported(algorithm, q)
                   else "im2col")
            cd = compute_dtype
            if cd != "float32":
                fam = None if alg in ("auto", "auto_tuned") else alg
                if not any(cd in cap.compute_dtypes
                           for cap in registry.matching(q, fam)):
                    cd = "float32"
            placements[node.id] = {"algorithm": alg, "groups": groups,
                                   "compute_dtype": cd}
        else:
            placements[node.id] = {"algorithm": algorithm,
                                   "compute_dtype": compute_dtype}
    return placements


# ---------------------------------------------------------------------------
# bind: build the LayerPlans + epilogue constants
# ---------------------------------------------------------------------------

def _param(params, path):
    v = params
    for k in path:
        v = v[k]
    return v


def bind(graph: Sequence[LayerIR], shapes: dict[str, tuple[int, ...]],
         placements: dict[str, dict], params, *,
         dtype=None) -> tuple[dict, dict]:
    """Build one LayerPlan per plan-bearing node (every per-layer decision
    and every filter transform happens here, once) and collect the epilogue
    constants (biases, dense weights) the graph executor feeds them."""
    plans: dict[str, Any] = {}
    consts: dict[str, jax.Array] = {}

    def const(nid, tag, path):
        if path is not None:
            consts[f"{nid}.{tag}"] = jnp.asarray(_param(params, path))

    for node in graph:
        a = node.attrs
        in_shape = shapes[node.inputs[0]] if node.inputs else None
        if node.op == "conv2d":
            pl = placements[node.id]
            plans[node.id] = _plan.plan_conv2d(
                in_shape, _param(params, a["w_path"]),
                stride=tuple(a["stride"]), padding=a["padding"],
                groups=pl["groups"], algorithm=pl["algorithm"], dtype=dtype,
                compute_dtype=pl.get("compute_dtype", "float32"))
            const(node.id, "b", a.get("b_path"))
        elif node.op == "separable":
            pl = placements[node.id]
            plans[node.id] = _plan.plan_separable_block(
                in_shape, _param(params, a["dw_w"]),
                _param(params, a["pw_w"]), stride=tuple(a["stride"]),
                padding=a["padding"],
                algorithm=pl["algorithm"], dtype=dtype,
                compute_dtype=pl.get("compute_dtype", "float32"))
            const(node.id, "b_dw", a.get("dw_b"))
            const(node.id, "b_pw", a.get("pw_b"))
        elif node.op == "inverted_residual":
            pl = placements[node.id]
            p = _plan.plan_inverted_residual(
                in_shape,
                _param(params, a["exp_w"]) if a.get("exp_w") else None,
                _param(params, a["dw_w"]), _param(params, a["pw_w"]),
                stride=tuple(a["stride"]), padding=a["padding"],
                algorithm=pl["algorithm"], dtype=dtype,
                compute_dtype=pl.get("compute_dtype", "float32"))
            if p.residual != a["residual"]:
                # the graph is the source of truth for the skip edge (a
                # hand-built IR may omit the add even where shapes allow it)
                p = dataclasses.replace(p, residual=a["residual"])
            plans[node.id] = p
            const(node.id, "b_exp", a.get("exp_b"))
            const(node.id, "b_dw", a.get("dw_b"))
            const(node.id, "b_pw", a.get("pw_b"))
        elif node.op == "conv1d":
            plans[node.id] = _plan.plan_conv1d(
                in_shape, _param(params, a["w_path"]), stride=a["stride"],
                padding=a["padding"],
                algorithm=placements[node.id]["algorithm"])
            const(node.id, "b", a.get("b_path"))
        elif node.op == "dense":
            const(node.id, "w", a["w_path"])
    return plans, consts


# ---------------------------------------------------------------------------
# NetworkPlan: the compiled, executable, serializable network
# ---------------------------------------------------------------------------

def _pool_apply(x, a):
    from repro.models.layers import pool2d
    return pool2d(x, a["kind"], a["k"], a["stride"], a["padding"])


#: attrs keys that are tuples in memory but lists in the JSON header.
_TUPLE_ATTRS = ("stride", "w_path", "b_path", "dw_w", "dw_b", "pw_w",
                "pw_b", "exp_w", "exp_b")


def _node_to_json(n: LayerIR) -> dict:
    attrs = {k: (list(v) if isinstance(v, tuple) else v)
             for k, v in n.attrs.items()}
    return {"id": n.id, "op": n.op, "inputs": list(n.inputs),
            "attrs": attrs, "block": n.block}


def _node_from_json(d: dict) -> LayerIR:
    attrs = dict(d["attrs"])
    for k in _TUPLE_ATTRS:
        if isinstance(attrs.get(k), list):
            attrs[k] = tuple(attrs[k])
    return LayerIR(id=d["id"], op=d["op"], inputs=tuple(d["inputs"]),
                   attrs=attrs, block=d.get("block"))


def _static_key(obj) -> Any:
    """A hashable key of a bound plan's static content: arrays by shape
    and dtype, its build time left out."""
    if isinstance(obj, (jax.Array, np.ndarray)):
        return (obj.shape, str(obj.dtype))
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return (type(obj).__name__,) + tuple(
            (f.name, _static_key(getattr(obj, f.name)))
            for f in dataclasses.fields(obj) if f.name != "build_time_s")
    if isinstance(obj, (list, tuple)):
        return tuple(map(_static_key, obj))
    if isinstance(obj, dict):
        return tuple(sorted((k, _static_key(v)) for k, v in obj.items()))
    try:
        hash(obj)
    except TypeError:
        return repr(obj)
    return obj


def _plan_weight_arrays(p) -> list[jax.Array]:
    """The execution-domain weight arrays a bound LayerPlan holds (what
    plan build materializes; benchmarks block_until_ready on these)."""
    if isinstance(p, _plan.ConvPlan) or isinstance(
            p, _plan.DepthwiseConv1DPlan):
        scale = getattr(p, "scale", None)
        return [p.u] if scale is None else [p.u, scale]
    if isinstance(p, _plan.SeparableBlockPlan):
        if p.mode == "fused_pallas":
            return [p.u_dw, p.u_pw]
        return _plan_weight_arrays(p.dw) + _plan_weight_arrays(p.pw)
    if isinstance(p, _plan.InvertedResidualPlan):
        out = _plan_weight_arrays(p.sep)
        if p.expand is not None:
            out = _plan_weight_arrays(p.expand) + out
        return out
    if isinstance(p, _plan.Conv1DPlan):
        if p.mode in ("as2d", "im2col"):
            return _plan_weight_arrays(p.inner)
        return [a for s in p.subplans for a in _plan_weight_arrays(s)]
    raise TypeError(f"not a LayerPlan: {type(p)!r}")


@dataclasses.dataclass
class NetworkPlan:
    """A compiled network: the layer IR, one bound LayerPlan per
    plan-bearing node, and the epilogue constants. apply(x) executes the
    graph with zero per-call filter-transform or geometry work; save/load
    round-trips the whole thing (pre-transformed weights + per-layer
    algorithm decisions) through a versioned artifact -- the paper's
    ship-transformed-weights deployment path.

    Also behaves as a read-only mapping from layer name to its bound plan
    (`net["conv1"]`, `net.values()`, ...) for compatibility with the
    pre-compiler plan_cnn dict."""

    graph: tuple[LayerIR, ...]
    plans: dict[str, Any]
    consts: dict[str, jax.Array]
    input_shape: tuple[int, ...]
    algorithm: str
    dtype: str
    compute_dtype: str = "float32"     # requested transform-domain policy;
                                       # per-layer outcomes (fallbacks, the
                                       # auto_tuned race) live in each
                                       # plan's describe()
    build_time_s: float = 0.0
    params_digest: str | None = None   # digest of the raw params the plan
                                       # was compiled from; compile(artifact=)
                                       # refuses to warm-start from weights
                                       # that have since changed
    partition: dict | None = None      # partition record (see
                                       # core/partition.py); plans are bound
                                       # at shard-local geometry when
                                       # num_shards > 1. Persisted in the
                                       # artifact header.
    mesh: Any = dataclasses.field(default=None, repr=False, compare=False)
                                       # live jax.sharding.Mesh; NEVER
                                       # serialized -- load() leaves it None,
                                       # with_mesh() re-attaches one.

    # ---- execution -------------------------------------------------------

    def __call__(self, x: jax.Array) -> jax.Array:
        return self.apply(x)

    def is_sharded(self) -> bool:
        return (self.partition is not None
                and self.partition.get("num_shards", 1) > 1)

    def with_mesh(self, mesh) -> "NetworkPlan":
        """Attach a device mesh to a partitioned plan (artifacts do not
        serialize meshes). Validates the mesh's partition axis against the
        recorded shard count; returns self."""
        if self.partition is None:
            raise ValueError(
                "this NetworkPlan was compiled without a partition; "
                "recompile with compile(mesh=...) to shard it")
        axis, n = _partition.mesh_num_shards(mesh)
        want = self.partition["num_shards"]
        if self.is_sharded() and (axis != self.partition["axis"]
                                  or n != want):
            raise ValueError(
                f"mesh axis {axis!r} x{n} does not match the recorded "
                f"partition ({self.partition['axis']!r} x{want}); build a "
                f"matching mesh (launch.mesh.make_data_mesh({want})) or "
                f"recompile with mesh=")
        self.mesh = mesh
        self.invalidate_executables()
        return self

    def invalidate_executables(self) -> None:
        """Drop cached jitted/sharded callables. Anything that swaps a
        bound plan object (replace_layer, the fault-injection harness)
        must call this, or a jitted program keeps executing the old
        closure."""
        self.__dict__.pop("_sharded_fn", None)

    def _sharded_callable(self):
        fn = self.__dict__.get("_sharded_fn")
        if fn is None:
            fn = _partition.build_sharded_fn(self)
            self.__dict__["_sharded_fn"] = fn
        return fn

    def apply(self, x: jax.Array, *, layer_hook=None,
              annotate_errors: bool = False) -> jax.Array:
        """Execute the graph. `layer_hook(node_id, seconds)` is called after
        every plan-bearing node with its synchronous wall time (the result
        is block_until_ready'd first -- eager-mode only; do not jit an apply
        with a hook installed). `annotate_errors=True` wraps any exception a
        node raises in LayerExecutionError carrying the node id, so a
        serving supervisor can re-place exactly the failing layer.

        A plan compiled with a partition over >1 shards routes through the
        jitted shard_map program instead of the eager walk (hooks and error
        annotation need the single-logical-device plan)."""
        if self.is_sharded():
            if layer_hook is not None or annotate_errors:
                raise ValueError(
                    "layer_hook / annotate_errors need the eager "
                    "single-device walk, but this plan is partitioned "
                    f"({self.partition['kind']} x"
                    f"{self.partition['num_shards']}); compile without "
                    "mesh= for supervised execution")
            if self.mesh is None:
                raise ValueError(
                    f"this NetworkPlan records a {self.partition['kind']} "
                    f"partition over {self.partition['num_shards']} shards "
                    f"but no mesh is attached (artifacts never serialize "
                    f"device meshes); call "
                    f".with_mesh(launch.mesh.make_data_mesh("
                    f"{self.partition['num_shards']})) first")
            return self._sharded_callable()(x)
        return self._eval_graph(x, layer_hook=layer_hook,
                                annotate_errors=annotate_errors)

    def _eval_graph(self, x: jax.Array, *, layer_hook=None,
                    annotate_errors: bool = False) -> jax.Array:
        """The eager graph walk (also the shard_map body of a data-parallel
        partition, where each shard evaluates its local batch)."""
        # Liveness: drop each activation after its last consumer runs, so
        # eager execution holds only the live frontier (as the spec-walk
        # interpreter did), not every feature map of the whole network.
        remaining = {nid: len(cons)
                     for nid, cons in _consumers(self.graph).items()}
        env = {"input": x}
        c = self.consts
        for node in self.graph[1:]:
            a = node.attrs
            v = env[node.inputs[0]] if node.inputs else None
            t0 = (time.perf_counter()
                  if layer_hook is not None and node.id in self.plans
                  else None)
            try:
                y = self._eval_node(node, a, v, env, c)
            except Exception as e:
                if annotate_errors and not isinstance(e, LayerExecutionError):
                    raise LayerExecutionError(node.id, e) from e
                raise
            if t0 is not None:
                jax.block_until_ready(y)
                layer_hook(node.id, time.perf_counter() - t0)
            env[node.id] = y
            for i in node.inputs:
                remaining[i] -= 1
                if remaining[i] == 0:
                    del env[i]
        return env[self.graph[-1].id]

    def _eval_node(self, node, a, v, env, c):
        """One node under its named scope "<op>:<id>", which names the
        node's ops in the HLO and its Pallas kernels (kernels.runtime)."""
        with _kernel_runtime.node_scope(node.op, node.id,
                                        self._kernel_labels().get(node.id)):
            return self._eval_op(node, a, v, env, c)

    def _kernel_labels(self) -> dict[str, str]:
        """Node id -> the label its Pallas kernels are named by: the ids,
        joined by "-", of every node with the same op, input shape, plan
        (arrays by shape and dtype), epilogue and constants -- nodes whose
        kernels are one computation, which JAX then traces and lowers once,
        as it does unnamed. Kept until a bound plan changes."""
        token = tuple(map(id, self.plans.values()))
        cached = self.__dict__.get("_labels")
        if cached is None or cached[0] != token:
            shapes = infer_shapes(self.graph, self.input_shape)
            alike: dict[Any, list[str]] = {}
            for node in self.graph:
                if node.id not in self.plans:
                    continue
                key = (node.op, shapes[node.inputs[0]],
                       _static_key(self.plans[node.id]),
                       tuple(node.attrs.get(k) for k in _EPILOGUE_ATTRS),
                       tuple(sorted(k[len(node.id):] for k in self.consts
                                    if k.startswith(node.id + "."))))
                alike.setdefault(key, []).append(node.id)
            cached = (token, {nid: "-".join(ids) for ids in alike.values()
                              for nid in ids})
            self.__dict__["_labels"] = cached
        return cached[1]

    def _eval_op(self, node, a, v, env, c):
            if node.op == "conv2d":
                y = self.plans[node.id].apply(
                    v, bias=c.get(f"{node.id}.b"),
                    activation=a["activation"])
            elif node.op == "separable":
                y = self.plans[node.id].apply(
                    v, bias_dw=c.get(f"{node.id}.b_dw"),
                    bias_pw=c.get(f"{node.id}.b_pw"),
                    inner_activation=a["inner_activation"],
                    activation=a["activation"])
            elif node.op == "inverted_residual":
                y = self.plans[node.id].apply(
                    v, bias_exp=c.get(f"{node.id}.b_exp"),
                    bias_dw=c.get(f"{node.id}.b_dw"),
                    bias_pw=c.get(f"{node.id}.b_pw"),
                    activation=a["activation"])
            elif node.op == "conv1d":
                y = self.plans[node.id].apply(
                    v, bias=c.get(f"{node.id}.b"),
                    activation=a["activation"])
            elif node.op == "pool":
                y = _pool_apply(v, a)
            elif node.op == "concat":
                y = jnp.concatenate([env[i] for i in node.inputs], axis=-1)
            elif node.op == "add":
                y = env[node.inputs[0]] + env[node.inputs[1]]
            elif node.op == "global_avg_pool":
                y = jnp.mean(v, axis=(1, 2))
            elif node.op == "dense":
                from repro.models.layers import dense_head
                y = dense_head(v, c[f"{node.id}.w"], a["relu"])
            else:
                raise ValueError(f"unknown IR op {node.op!r} ({node.id})")
            return y

    @property
    def out_shape(self) -> tuple[int, ...]:
        return infer_shapes(self.graph, self.input_shape)[self.graph[-1].id]

    def weight_arrays(self) -> list[jax.Array]:
        """Every bound execution-domain array (plan weights + epilogue
        constants) -- jax.block_until_ready(net.weight_arrays()) fences the
        whole plan build."""
        out = [a for p in self.plans.values()
               for a in _plan_weight_arrays(p)]
        return out + list(self.consts.values())

    def replace_layer(self, node_id: str, params, *,
                      algorithm: str = "im2col",
                      compute_dtype: str = "float32") -> Any:
        """Re-place ONE plan-bearing node onto a different algorithm family
        (and/or transform-domain compute dtype) and re-bind its plan (and
        epilogue constants) from the raw params -- the serving supervisor's
        degrade path when a layer's executor misbehaves, and its precision
        promotion path when a reduced-precision layer trips the accuracy
        probe (compute_dtype="float32" is the always-safe landing spot).
        The replacement is a capability-registry placement, exactly like
        compile-time place(): an algorithm the registry does not cover for
        this layer raises the registry's resolution error. Returns the
        freshly bound plan. `params` must be the pytree the network was
        compiled from (checked against params_digest when the plan carries
        one)."""
        if self.is_sharded():
            raise ValueError(
                "replace_layer operates on single-logical-device plans "
                f"(this one is partitioned {self.partition['kind']} x"
                f"{self.partition['num_shards']}); supervisor repairs run "
                "on the unsharded plan, which is then recompiled with "
                "mesh= if sharding should resume")
        by_id = {n.id: n for n in self.graph}
        node = by_id.get(node_id)
        if node is None or node.op not in PLAN_OPS:
            raise ValueError(
                f"{node_id!r} is not a plan-bearing node; replaceable "
                f"layers: {sorted(self.plans)}")
        if self.params_digest is not None \
                and params_digest(params) != self.params_digest:
            raise ValueError(
                "params do not match the weights this NetworkPlan was "
                "compiled from (params_digest mismatch); re-placement from "
                "foreign weights would silently change the served model")
        shapes = infer_shapes(self.graph, self.input_shape)
        a = node.attrs
        if node.op == "conv2d":
            c_in = shapes[node.inputs[0]][-1]
            groups = c_in if a.get("depthwise") else a["groups"]
            q = registry.as_query(a["kh"], a["kw"], tuple(a["stride"]),
                                  groups=groups, c_in=c_in, c_out=a["c_out"])
            if not registry.supported(algorithm, q):
                raise registry.resolution_error(algorithm, q)
            placement = {"algorithm": algorithm, "groups": groups,
                         "compute_dtype": compute_dtype}
        else:
            placement = {"algorithm": algorithm,
                         "compute_dtype": compute_dtype}
        plans, consts = bind((node,), shapes, {node_id: placement}, params,
                             dtype=self.dtype)
        self.plans.update(plans)
        self.consts.update(consts)
        self.invalidate_executables()
        return self.plans[node_id]

    # ---- mapping compatibility (the old plan_cnn dict interface) ---------

    def __getitem__(self, key: str):
        return self.plans[key]

    def get(self, key: str, default=None):
        return self.plans.get(key, default)

    def __contains__(self, key: str) -> bool:
        return key in self.plans

    def __iter__(self) -> Iterator[str]:
        return iter(self.plans)

    def __len__(self) -> int:
        return len(self.plans)

    def keys(self):
        return self.plans.keys()

    def values(self):
        return self.plans.values()

    def items(self):
        return self.plans.items()

    # ---- describe --------------------------------------------------------

    def describe(self) -> str:
        """The per-layer algorithm table, rendered through the SAME
        markdown generator as the registry's README capability table
        (repro.core.registry.markdown_table) -- drift-tested."""
        shapes = infer_shapes(self.graph, self.input_shape)
        rows = []
        for node in self.graph:
            if node.id not in self.plans:
                continue
            d = self.plans[node.id].describe()
            rows.append((node.id, d["kind"], f"`{d['executor']}`",
                         d["filter"], d["stride"], d["groups"], d["tile"],
                         d.get("compute_dtype", "float32"),
                         d.get("decision", "static"),
                         "x".join(map(str, shapes[node.id]))))
        return registry.markdown_table(
            ["layer", "kind", "executor", "filter", "stride", "groups",
             "tile", "compute", "decision", "output"], rows)

    # ---- serialization ---------------------------------------------------

    def save(self, path: str) -> None:
        """Serialize the compiled network: a versioned JSON header (graph,
        per-layer plan metas, dtype/layout/registry-fingerprint cache keys)
        plus every execution-domain weight array, in one .npz file. A
        second process NetworkPlan.load()s this and starts warm: no
        re-planning, no re-measuring, no filter-transform work."""
        header = {
            "format": ARTIFACT_FORMAT,
            "version": ARTIFACT_VERSION,
            "registry_fingerprint": registry.fingerprint(),
            "jax_version": jax.__version__,
            "dtype": self.dtype,
            "compute_dtype": self.compute_dtype,
            "layout": "NHWC",
            "input_shape": list(self.input_shape),
            "algorithm": self.algorithm,
            "params_digest": self.params_digest,
            "partition": self.partition,
            "graph": [_node_to_json(n) for n in self.graph],
            "plans": {},
        }
        arrays: dict[str, np.ndarray] = {}
        for nid, p in self.plans.items():
            meta, arr = p.to_artifact()
            header["plans"][nid] = meta
            for k, v in arr.items():
                arrays[f"plan:{nid}:{k}"] = v
        for k, v in self.consts.items():
            arrays[f"const:{k}"] = np.asarray(v)
        # Per-array integrity digests: load() re-hashes every array against
        # these, so silent corruption between save and load is detected
        # instead of silently serving wrong outputs.
        header["checksums"] = {k: _array_digest(v) for k, v in arrays.items()}
        arrays["__header__"] = np.array(json.dumps(header))
        # atomic emit: a crash mid-write must never leave a truncated file
        # at the final path (a corrupt artifact would poison every later
        # warm start until manually deleted).
        tmp = f"{path}.tmp.{os.getpid()}"
        try:
            with open(tmp, "wb") as f:
                np.savez(f, **arrays)
            os.replace(tmp, path)
        finally:
            if os.path.exists(tmp):
                os.remove(tmp)

    @classmethod
    def load(cls, path: str, *, expect_dtype=None,
             expect_layout: str | None = None,
             _record: bool = True) -> "NetworkPlan":
        """Load a saved artifact. Refuses -- with the mismatch and the fix
        spelled out -- when the header does not match this build: wrong
        format or version, a capability registry whose fingerprint changed
        since the plan was compiled (its per-layer executor decisions may
        be stale), or a dtype/layout other than the caller expects.
        Successful loads count as artifact hits in plan_cache_info()
        (compile(artifact=) passes _record=False and does its own
        one-hit-or-one-miss accounting per warm-start attempt)."""
        fix = ("; recompile with repro.core.compile.compile(...) and "
               "save() a fresh artifact")

        def refuse(msg: str) -> ArtifactMismatchError:
            if _record:
                _plan.record_artifact_load(False)
            return ArtifactMismatchError(msg + fix)

        with np.load(path, allow_pickle=False) as data:
            if "__header__" not in data:
                raise refuse(f"{path} is not a serialized NetworkPlan "
                             f"(no header)")
            header = json.loads(str(data["__header__"][()]))
            if header.get("format") != ARTIFACT_FORMAT:
                raise refuse(
                    f"{path} has format {header.get('format')!r}, expected "
                    f"{ARTIFACT_FORMAT!r}")
            if header.get("version") != ARTIFACT_VERSION:
                raise refuse(
                    f"{path} is artifact version {header.get('version')}, "
                    f"this build reads version {ARTIFACT_VERSION}")
            if header.get("registry_fingerprint") != registry.fingerprint():
                raise refuse(
                    f"{path} was compiled against capability registry "
                    f"{header.get('registry_fingerprint')}, but this "
                    f"build's registry is {registry.fingerprint()} -- the "
                    f"saved per-layer executor decisions may be stale")
            if expect_dtype is not None and str(
                    jnp.dtype(expect_dtype)) != header.get("dtype"):
                report = _artifact_dtype_report(header)
                raise refuse(
                    f"{path} holds {header.get('dtype')} weights, caller "
                    f"expects {jnp.dtype(expect_dtype)}"
                    + (f"; per-layer transform-domain compute dtypes on "
                       f"disk vs this registry: {report}" if report else ""))
            if header.get("layout") not in registry.LAYOUTS or (
                    expect_layout is not None
                    and expect_layout != header.get("layout")):
                raise refuse(
                    f"{path} uses layout {header.get('layout')!r}, "
                    f"expected {expect_layout or '/'.join(registry.LAYOUTS)}")
            checksums = header.get("checksums", {})
            payload = [k for k in data.files if k != "__header__"]
            missing = sorted(set(checksums) - set(payload))
            if missing:
                raise refuse(
                    f"{path} is missing array(s) {missing} recorded in its "
                    f"integrity header -- the artifact is truncated or "
                    f"corrupt")
            for k in payload:
                expect = checksums.get(k)
                if expect is None or _array_digest(data[k]) != expect:
                    raise refuse(
                        f"{path} array {k!r} fails its sha256 integrity "
                        f"digest -- the artifact is corrupt on disk")
            graph = tuple(_node_from_json(d) for d in header["graph"])
            plans = {}
            for nid, meta in header["plans"].items():
                arrays = {k.split(":", 2)[2]: data[k] for k in data.files
                          if k.startswith(f"plan:{nid}:")}
                plans[nid] = _plan.plan_from_artifact(meta, arrays)
            consts = {k[len("const:"):]: jnp.asarray(data[k])
                      for k in data.files if k.startswith("const:")}
        if _record:
            _plan.record_artifact_load(True)
        return cls(graph=graph, plans=plans, consts=consts,
                   input_shape=tuple(header["input_shape"]),
                   algorithm=header["algorithm"], dtype=header["dtype"],
                   compute_dtype=header.get("compute_dtype", "float32"),
                   params_digest=header.get("params_digest"),
                   partition=header.get("partition"))


def verify_artifact(path: str) -> list[str]:
    """Integrity-check a saved NetworkPlan artifact against its per-array
    sha256 digests WITHOUT loading it as a plan. Returns the names of the
    offending arrays (missing from the file, or failing their digest), or
    `["__header__"]` when the file itself is unreadable / has no integrity
    header -- an empty list means the artifact is intact. The serving
    supervisor runs this to decide between 'executor bug' (artifact intact,
    re-place the layer) and 'corrupt artifact' (recompile in place)."""
    try:
        with np.load(path, allow_pickle=False) as data:
            if "__header__" not in data:
                return ["__header__"]
            header = json.loads(str(data["__header__"][()]))
            checksums = header.get("checksums")
            if not isinstance(checksums, dict):
                return ["__header__"]
            payload = [k for k in data.files if k != "__header__"]
            bad = sorted(set(checksums) - set(payload))
            for k in payload:
                expect = checksums.get(k)
                if expect is None or _array_digest(data[k]) != expect:
                    bad.append(k)
            return bad
    except _ARTIFACT_FALLBACK_ERRORS:
        return ["__header__"]


# ---------------------------------------------------------------------------
# compile: the entry point
# ---------------------------------------------------------------------------

def params_digest(params) -> str:
    """Order-independent digest of a params pytree (dict-of-dicts of
    arrays): key paths + shapes + raw bytes. compile(artifact=) stamps this
    into the artifact and refuses to warm-start from an artifact whose
    weights no longer match the params in hand (e.g. after retraining)."""
    h = hashlib.sha256()

    def walk(node, prefix):
        if isinstance(node, Mapping):
            for k in sorted(node):
                walk(node[k], f"{prefix}/{k}")
            return
        a = np.asarray(node)
        h.update(f"{prefix}:{a.dtype}:{a.shape}".encode())
        h.update(np.ascontiguousarray(a).tobytes())

    walk(params, "")
    return h.hexdigest()[:16]


#: Errors a warm-start attempt treats as "artifact unusable, recompile":
#: header mismatches, plus anything a truncated / corrupt / foreign file
#: can raise out of np.load or the header parse. Genuine bugs (TypeError,
#: AssertionError, ...) still propagate.
_ARTIFACT_FALLBACK_ERRORS = (ArtifactMismatchError, OSError, EOFError,
                             KeyError, ValueError, zipfile.BadZipFile,
                             json.JSONDecodeError)


def _try_load_artifact(path: str, *, input_shape, algorithm, digest: str,
                       dtype=None,
                       compute_dtype: str = "float32",
                       mesh=None, partition: str | None = None
                       ) -> "NetworkPlan | None":
    """The compile(artifact=) warm-start attempt: load without counting,
    then validate the artifact against THIS call's arguments -- input
    shape, algorithm request, params digest, compute_dtype policy, the
    partition request (kind + shard count vs the recorded record), and
    (when explicitly requested) dtype -- so a stale artifact (different
    resolution, different policy, retrained weights, other precision or
    mesh shape) recompiles instead of silently serving old decisions.
    A partition-matched artifact gets the caller's mesh attached; its
    recorded modes/halos are used verbatim (no re-deciding). Returns
    None when the artifact is unusable; the caller does the one-miss
    accounting."""
    try:
        loaded = NetworkPlan.load(path, _record=False)
    except _ARTIFACT_FALLBACK_ERRORS:
        return None
    if (loaded.input_shape != tuple(input_shape)
            or loaded.algorithm != algorithm
            or loaded.params_digest != digest
            or loaded.compute_dtype != compute_dtype
            or (dtype is not None
                and loaded.dtype != str(jnp.dtype(dtype)))):
        return None
    part = loaded.partition
    if mesh is None:
        if part is not None:
            return None
    else:
        axis, n = _partition.mesh_num_shards(mesh)
        want_kind = partition or "data"
        if (part is None or part["kind"] != want_kind
                or part["axis"] != axis
                or part.get("requested_shards", part["num_shards"]) != n):
            return None
        loaded.mesh = mesh
    return loaded


def _plans_dtype(plans: dict) -> str:
    for p in plans.values():
        spec = getattr(p, "spec", None)
        if spec is not None and getattr(spec, "dtype", None):
            return spec.dtype
        inner = getattr(p, "inner", None) or getattr(p, "expand", None) \
            or getattr(p, "sep", None)
        if inner is not None:
            d = _plans_dtype({"_": inner})
            if d:
                return d
    return "float32"


def _bind_partitioned(ir, shapes, placements, params, part: dict,
                      dtype) -> tuple[dict, dict]:
    """bind() under a partition record: data-parallel plans bind at the
    local batch; spatial halo-mode plans bind VALID at their exchanged
    local strip; full-mode (re-gathered) nodes bind at the global shape."""
    if part["kind"] == "data":
        return bind(ir, _partition.local_bind_shapes(part, shapes),
                    placements, params, dtype=dtype)
    plans: dict[str, Any] = {}
    consts: dict[str, jax.Array] = {}
    modes = part["modes"]
    for node in ir:
        if not node.inputs:
            continue
        if node.op in PLAN_OPS and modes.get(node.id) == "halo":
            node_v = dataclasses.replace(
                node, attrs={**node.attrs, "padding": "VALID"})
            in_shape = _partition.spatial_halo_in_shape(part, node, shapes)
            p, cs = bind((node_v,), {node.inputs[0]: in_shape}, placements,
                         params, dtype=dtype)
        elif node.op in PLAN_OPS or node.op == "dense":
            p, cs = bind((node,), {node.inputs[0]: shapes[node.inputs[0]]},
                         placements, params, dtype=dtype)
        else:
            continue
        plans.update(p)
        consts.update(cs)
    return plans, consts


def compile(params, graph, *, res: int | None = None, c_in: int = 3,
            batch: int = 1, algorithm: str = "auto",
            input_shape: Sequence[int] | None = None, dtype=None,
            compute_dtype: str = "float32",
            artifact: str | None = None,
            mesh=None, partition: str | None = None) -> NetworkPlan:
    """Compile a network description into one NetworkPlan.

    `graph` is either a models/cnn.py spec list (lowered to the layer IR
    here) or a pre-lowered tuple of LayerIR nodes (e.g.
    models/audio.py:stem_graph). The pass pipeline runs
    lower -> fuse -> place -> bind: composite blocks are reconstituted by
    registry-aware pattern rewrites (dw+pw -> separable,
    expand+dw+project[+residual] -> inverted residual), each node gets its
    algorithm via capability-registry queries, and every per-layer decision
    plus every filter transform happens exactly once, here.

    `res` describes an image network's (batch, res, res, c_in) input;
    sequence networks pass `input_shape` instead. `algorithm` is the global
    request (plan.ALGORITHMS); uncovered layers fall back to im2col, the
    paper's mixed policy.

    `compute_dtype` is the network-level transform-domain precision policy
    ("float32" / "bfloat16" / "int8"): reduced dtypes quantize/cast each
    conv layer's transform-domain filter at bind time (per-output-channel
    scales folded into the epilogue); layers whose covering executors do
    not declare the dtype are placed back at fp32, the same per-layer
    fallback shape as the algorithm request. The policy is persisted in
    the artifact header, and a warm start requires it to match.

    With `artifact=path`, compile() first tries NetworkPlan.load(path) and
    validates the artifact against THIS call (input shape, algorithm,
    params digest, partition request) -- a usable artifact is the warm
    start (one artifact hit in plan_cache_info()); a missing, corrupt,
    header-mismatched, or argument-stale artifact falls back to a cold
    compile whose result is saved back to `path` (one artifact miss).

    With `mesh=` (a jax.sharding.Mesh), the plan executes sharded over the
    mesh's "data" axis: `partition="data"` (the default) shards the batch
    dim with weights replicated; `partition="spatial"` splits H across
    devices with per-layer halo exchange / re-gather decisions recorded in
    the plan's partition record (core/partition.py). Indivisible batches
    or heights degrade to a replicated single-logical-device plan with the
    reason recorded -- never an error. The record persists in version-5
    artifacts so warm starts restore the partitioning without re-deciding;
    the mesh itself is re-attached per process (it never serializes).
    """
    t0 = time.perf_counter()
    if partition is not None:
        if mesh is None:
            raise ValueError(
                f"partition={partition!r} needs mesh= (a jax.sharding.Mesh "
                f"with a 'data' axis; see launch.mesh.make_data_mesh)")
        if partition not in ("data", "spatial"):
            raise ValueError(f"unknown partition {partition!r}; expected "
                             f"'data' or 'spatial'")
    if input_shape is None:
        if res is None:
            raise ValueError("compile() needs res= (image networks, "
                             "input (batch, res, res, c_in)) or "
                             "input_shape=")
        input_shape = (batch, res, res, c_in)
    input_shape = tuple(input_shape)
    if algorithm not in _plan.ALGORITHMS:
        raise ValueError(f"unknown algorithm {algorithm!r}; expected one "
                         f"of {_plan.ALGORITHMS}")
    compute_dtype = str(jnp.dtype(compute_dtype))
    if compute_dtype not in registry.COMPUTE_DTYPES:
        raise ValueError(f"unknown compute_dtype {compute_dtype!r}; "
                         f"expected one of {registry.COMPUTE_DTYPES}")
    digest = params_digest(params) if artifact is not None else None
    if artifact is not None and os.path.exists(artifact):
        with _obs_trace.span("compile.artifact_load", path=artifact):
            loaded = _try_load_artifact(
                artifact, input_shape=input_shape, algorithm=algorithm,
                digest=digest, dtype=dtype, compute_dtype=compute_dtype,
                mesh=mesh, partition=partition)
        if loaded is not None:
            _plan.record_artifact_load(True)
            return loaded
    with _obs_trace.span("compile.lower"):
        ir = tuple(graph) if _is_ir(graph) else lower(graph,
                                                      c_in=input_shape[-1])
    with _obs_trace.span("compile.fuse") as _sp:
        ir = fuse(ir)
        _sp.set(nodes=len(ir))
    with _obs_trace.span("compile.infer_shapes"):
        shapes = infer_shapes(ir, input_shape)
    with _obs_trace.span("compile.place", algorithm=algorithm):
        placements = place(ir, shapes, algorithm, compute_dtype)
    part = None
    if mesh is not None:
        with _obs_trace.span("compile.decide_partition"):
            axis, n = _partition.mesh_num_shards(mesh)
            part = _partition.decide_partition(ir, shapes, n,
                                               partition or "data", axis)
    with _obs_trace.span("compile.bind",
                         partitioned=bool(part
                                          and part["num_shards"] > 1)):
        if part is not None and part["num_shards"] > 1:
            plans, consts = _bind_partitioned(ir, shapes, placements,
                                              params, part, dtype)
        else:
            plans, consts = bind(ir, shapes, placements, params,
                                 dtype=dtype)
    net = NetworkPlan(
        graph=ir, plans=plans, consts=consts, input_shape=input_shape,
        algorithm=algorithm,
        dtype=str(jnp.dtype(dtype)) if dtype else _plans_dtype(plans),
        compute_dtype=compute_dtype,
        build_time_s=time.perf_counter() - t0, params_digest=digest,
        partition=part, mesh=mesh)
    if artifact is not None:
        _plan.record_artifact_load(False)
        with _obs_trace.span("compile.artifact_save", path=artifact):
            net.save(artifact)
    return net
