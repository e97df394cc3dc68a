"""TensorFlow GraphDef/SavedModel → graph IR importer.

Parity target: ``nd4j/samediff-import/samediff-import-tensorflow``
(``TFFrameworkImporter``/``OpMappingRegistry``; beta era
``org.nd4j.imports.graphmapper.tf.TFGraphMapper``) — scoped, as SURVEY.md
§7 M5 prescribes, to the op set of a frozen BERT encoder plus the common
CNN/MLP ops.  Import produces our ``SameDiff`` IR; execution is then one
jitted XLA program (no per-op interpretation).

Works on FROZEN graphs (variables folded to Const — use
``tf.python.framework.convert_to_constants.convert_variables_to_constants_v2``);
the importer turns large float Consts into trainable VARIABLEs so an
imported model can be fine-tuned directly (the SameDiff
``TrainingConfig`` flow).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

import numpy as np

from deeplearning4j_tpu.autodiff.samediff import OpNode, SameDiff, SDVariable

# Ops imported as identity/stop_gradient nodes (kept as real nodes so
# graph outputs named after them stay fetchable).
_PASSTHROUGH = {"Identity": "identity", "StopGradient": "stop_gradient",
                "PreventGradient": "stop_gradient",
                "CheckNumerics": "identity", "Snapshot": "identity",
                "EnsureShape": "identity"}
_SKIP = {"NoOp", "Assert", "Placeholder"}

# TF op -> (registry op, attr translator) for 1:1 cases.
_SIMPLE: Dict[str, str] = {
    "Add": "add", "AddV2": "add", "Sub": "sub", "Mul": "mul",
    "RealDiv": "div", "Div": "div", "FloorDiv": "floordiv",
    "FloorMod": "mod", "Pow": "pow", "Maximum": "maximum",
    "Minimum": "minimum", "SquaredDifference": "squared_difference",
    "Neg": "neg", "Abs": "abs", "Sign": "sign", "Exp": "exp", "Log": "log",
    "Log1p": "log1p", "Sqrt": "sqrt", "Rsqrt": "rsqrt", "Square": "square",
    "Reciprocal": "reciprocal", "Floor": "floor", "Ceil": "ceil",
    "Round": "round", "Sin": "sin", "Cos": "cos", "Tan": "tan",
    "Tanh": "tanh", "Sigmoid": "sigmoid", "Erf": "erf", "Erfc": "erfc",
    "Relu": "relu",
    "Relu6": "relu6", "Elu": "elu", "Selu": "selu", "Softplus": "softplus",
    "Softsign": "softsign", "LogicalNot": "logical_not",
    "Equal": "equal", "NotEqual": "not_equal", "Greater": "greater",
    "Less": "less", "GreaterEqual": "greater_equal",
    "LessEqual": "less_equal", "LogicalAnd": "logical_and",
    "LogicalOr": "logical_or", "BiasAdd": "bias_add",
    "Softmax": "softmax", "LogSoftmax": "log_softmax",
    "Shape": "shape", "Size": "size", "Rank": "rank",
    "Reshape": "reshape", "ZerosLike": "zeros_like",
    "OnesLike": "ones_like", "GatherNd": "gather_nd", "IsNan": "isnan",
    "IsInf": "isinf", "BroadcastTo": "broadcast_to", "Fill": "fill",
    # round-3 breadth
    "Asin": "asin", "Acos": "acos", "Atan": "atan", "Atan2": "atan2",
    "Sinh": "sinh", "Cosh": "cosh", "Asinh": "asinh", "Acosh": "acosh",
    "Atanh": "atanh", "Expm1": "expm1", "Rint": "rint",
    "IsFinite": "isfinite", "Lgamma": "lgamma", "Digamma": "digamma",
    "Xlogy": "xlogy", "Xdivy": "xdivy", "LogicalXor": "logical_xor",
    "AddN": "add_n", "L2Loss": "l2_loss",
    "ClipByValue": "clip_by_value", "InvertPermutation":
    "invert_permutation", "TensorScatterUpdate": "tensor_scatter_update",
    "TensorScatterAdd": "tensor_scatter_add",
    "MatrixInverse": "matrix_inverse", "Cholesky": "cholesky",
    "MatrixDeterminant": "matrix_determinant",
    "MatrixDiagPart": "matrix_diag_part",
    "ReverseV2": "reverse", "Roll": "roll",
}

_MIN_VAR_SIZE = 2  # float consts with >= this many elements -> VARIABLE


def _default_trainable_filter(name: str, value: np.ndarray) -> bool:
    """Which frozen float consts become trainable VARIABLEs.

    The heuristic (any float const with >= _MIN_VAR_SIZE elements) is
    deliberately inclusive — frozen graphs fold ALL weights to Const and
    there is no other signal.  Callers fine-tuning a graph where that
    over-promotes (e.g. normalization tables that must stay frozen) pass
    an explicit ``trainable_filter(name, value) -> bool`` to
    ``import_graph_def``/``import_frozen_pb`` instead."""
    return (np.issubdtype(value.dtype, np.floating)
            and value.size >= _MIN_VAR_SIZE)


def _tf_attr(node, name, default=None):
    if name not in node.attr:
        return default
    a = node.attr[name]
    kind = a.WhichOneof("value")
    if kind == "i":
        return int(a.i)
    if kind == "f":
        return float(a.f)
    if kind == "b":
        return bool(a.b)
    if kind == "s":
        return a.s.decode()
    if kind == "type":
        from tensorflow.python.framework import dtypes
        return dtypes.as_dtype(a.type).as_numpy_dtype.__name__
    if kind == "shape":
        return [d.size for d in a.shape.dim]
    if kind == "list":
        if a.list.i:
            return [int(v) for v in a.list.i]
        if a.list.f:
            return [float(v) for v in a.list.f]
        if a.list.s:
            return [v.decode() for v in a.list.s]
        return []
    if kind == "tensor":
        from tensorflow.python.framework import tensor_util
        return tensor_util.MakeNdarray(a.tensor)
    return default


class _Importer:
    def __init__(self, graph_def, trainable_consts: bool = True,
                 trainable_filter: Optional[Callable] = None,
                 library=None):
        self.gd = graph_def
        # NESTED control flow: a FuncGraph's GraphDef has an empty
        # library, so sub-importers inherit the ROOT graph's library to
        # resolve inner StatelessWhile/If function names.
        self.library = library if library is not None else \
            graph_def.library
        self.sd = SameDiff.create()
        self.trainable_consts = trainable_consts
        self.trainable_filter = trainable_filter or _default_trainable_filter
        # name -> SDVariable for every produced tensor ("node" and "node:i")
        self.tensors: Dict[str, SDVariable] = {}
        self.const_values: Dict[str, np.ndarray] = {}
        # "node:i" refs consumed anywhere (aux-output usage detection)
        self.consumed_refs = {
            i.split("^")[-1] for n in graph_def.node for i in n.input}

    # -- plumbing ------------------------------------------------------
    def _resolve(self, ref: str) -> SDVariable:
        ref = ref.split("^")[-1]
        if ref.endswith(":0"):
            ref = ref[:-2]
        v = self.tensors.get(ref)
        if v is None:
            raise KeyError(f"Input tensor {ref!r} not yet produced "
                           "(graph not topologically ordered?)")
        return v

    def _const_of(self, var: SDVariable) -> np.ndarray:
        """Host value of a Const input (axes, perms, shapes...)."""
        val = self.const_values.get(var.name)
        if val is None:
            raise ValueError(
                f"{var.name!r} must be a constant at import time")
        return val

    def _aux(self, name: str, op_name: str, inputs: List[SDVariable],
             **attrs) -> SDVariable:
        """Emit a synthetic helper op (layout transposes etc.) whose
        output name does NOT shadow a TF node name."""
        out = self.sd._unique(name)
        self.sd.ops.append(OpNode(op_name, [v.name for v in inputs],
                                  [out], attrs))
        v = self.sd._register(out, "ARRAY")
        self.tensors[out] = v
        return v

    def _emit(self, node, op_name: str, inputs: List[SDVariable],
              n_out: int = 1, **attrs):
        outs = [node.name if i == 0 else f"{node.name}:{i}"
                for i in range(n_out)]
        self.sd.ops.append(OpNode(op_name, [v.name for v in inputs], outs,
                                  attrs))
        out_vars = [self.sd._register(o, "ARRAY") for o in outs]
        for o, v in zip(outs, out_vars):
            self.tensors[o] = v
        self.tensors[node.name] = out_vars[0]
        return out_vars

    # -- node handlers -------------------------------------------------
    def _handle_const(self, node):
        val = _tf_attr(node, "value")
        name = node.name
        big_float = (self.trainable_consts and val is not None
                     and self.trainable_filter(name, np.asarray(val)))
        if big_float:
            v = self.sd.var(name, np.asarray(val))
        else:
            v = self.sd.constant(name, np.asarray(val))
            self.const_values[v.name] = np.asarray(val)
        assert v.name == name, f"duplicate TF node name {name}"
        self.tensors[name] = v

    def _handle_placeholder(self, node):
        shape = _tf_attr(node, "shape")
        dtype = _tf_attr(node, "dtype", "float32")
        v = self.sd.placeholder(node.name, shape, dtype)
        self.tensors[node.name] = v

    def _handle(self, node):
        op = node.op
        ins = [self._resolve(i) for i in node.input
               if not i.startswith("^")]
        if op == "Const":
            return self._handle_const(node)
        if op == "Placeholder" or op == "PlaceholderWithDefault":
            return self._handle_placeholder(node)
        if op in _SKIP:
            return
        if op in _PASSTHROUGH:
            return self._emit(node, _PASSTHROUGH[op], ins[:1])
        if op in _SIMPLE:
            return self._emit(node, _SIMPLE[op], ins)

        # -- ops with attr/input-signature translation --
        if op == "MatMul":
            return self._emit(node, "matmul", ins,
                              transpose_a=_tf_attr(node, "transpose_a", False),
                              transpose_b=_tf_attr(node, "transpose_b", False))
        if op in ("BatchMatMul", "BatchMatMulV2", "BatchMatMulV3"):
            return self._emit(node, "matmul", ins,
                              transpose_a=_tf_attr(node, "adj_x", False),
                              transpose_b=_tf_attr(node, "adj_y", False))
        if op == "Einsum":
            return self._emit(node, "einsum", ins,
                              equation=_tf_attr(node, "equation"))
        if op in ("Mean", "Sum", "Max", "Min", "Prod", "Any", "All"):
            # Axes ride as a graph INPUT: if runtime-computed from shape
            # metadata they constant-fold at trace time (static shapes).
            return self._emit(
                node, f"reduce_{op.lower()}", ins[:2],
                keep_dims=_tf_attr(node, "keep_dims", False))
        if op in ("ArgMax", "ArgMin"):
            axis = int(np.asarray(self._const_of(ins[1])).reshape(())) \
                if len(ins) > 1 else -1
            return self._emit(node, op.lower(), ins[:1], axis=axis)
        if op == "Cast":
            return self._emit(node, "cast", ins,
                              dtype=_tf_attr(node, "DstT", "float32"))
        if op == "Transpose":
            return self._emit(node, "transpose", ins[:2])
        if op == "ExpandDims":
            return self._emit(node, "expand_dims", ins[:2])
        if op == "Squeeze":
            dims = _tf_attr(node, "squeeze_dims") or None
            return self._emit(node, "squeeze", ins, axis=dims)
        if op in ("ConcatV2", "Concat"):
            if op == "Concat":  # axis FIRST in legacy Concat
                axis_var, parts = ins[0], ins[1:]
            else:               # axis LAST in ConcatV2
                axis_var, parts = ins[-1], ins[:-1]
            axis = int(np.asarray(self._const_of(axis_var)).reshape(()))
            return self._emit(node, "concat", parts, axis=axis)
        if op == "Pack":
            return self._emit(node, "pack", ins,
                              axis=_tf_attr(node, "axis", 0))
        if op == "Unpack":
            n = _tf_attr(node, "num")
            return self._emit(node, "unstack", ins, n_out=n,
                              axis=_tf_attr(node, "axis", 0), num=n)
        if op == "Split":
            n = _tf_attr(node, "num_split")
            axis = int(np.asarray(self._const_of(ins[0])).reshape(()))
            return self._emit(node, "split", ins[1:], n_out=n,
                              num_split=n, axis=axis)
        if op == "Tile":
            return self._emit(node, "tile", ins[:2])
        if op == "Slice":
            return self._emit(node, "slice", ins)
        if op == "StridedSlice":
            return self._emit(
                node, "strided_slice", ins,
                begin_mask=_tf_attr(node, "begin_mask", 0),
                end_mask=_tf_attr(node, "end_mask", 0),
                ellipsis_mask=_tf_attr(node, "ellipsis_mask", 0),
                new_axis_mask=_tf_attr(node, "new_axis_mask", 0),
                shrink_axis_mask=_tf_attr(node, "shrink_axis_mask", 0))
        if op in ("GatherV2", "Gather", "ResourceGather"):
            axis = 0
            if op == "GatherV2" and len(ins) > 2:
                axis = int(np.asarray(self._const_of(ins[2])).reshape(()))
            return self._emit(node, "gather", ins[:2], axis=axis,
                              batch_dims=_tf_attr(node, "batch_dims", 0))
        if op == "OneHot":
            depth = int(np.asarray(self._const_of(ins[1])).reshape(()))
            on = float(np.asarray(self._const_of(ins[2])).reshape(()))
            off = float(np.asarray(self._const_of(ins[3])).reshape(()))
            return self._emit(node, "one_hot", ins[:1], depth=depth,
                              on_value=on, off_value=off,
                              axis=_tf_attr(node, "axis", -1))
        if op == "Range":
            return self._emit(node, "range", ins)
        if op in ("Cumsum", "Cumprod"):
            axis = int(np.asarray(self._const_of(ins[1])).reshape(()))
            return self._emit(node, op.lower(), ins[:1], axis=axis,
                              exclusive=_tf_attr(node, "exclusive", False),
                              reverse=_tf_attr(node, "reverse", False))
        if op in ("Pad", "PadV2"):
            cv = 0.0
            if op == "PadV2" and len(ins) > 2:
                cv = float(np.asarray(self._const_of(ins[2])).reshape(()))
            return self._emit(node, "pad", ins[:2], constant_value=cv)
        if op == "MirrorPad":
            return self._emit(node, "mirror_pad", ins[:2],
                              mode=_tf_attr(node, "mode", "REFLECT"))
        if op in ("Select", "SelectV2"):
            return self._emit(node, "select", ins)
        if op == "Conv2D":
            strides = _tf_attr(node, "strides", [1, 1, 1, 1])
            dil = _tf_attr(node, "dilations", [1, 1, 1, 1])
            pad = _tf_attr(node, "padding", "SAME")
            if _tf_attr(node, "data_format", "NHWC") == "NCHW":
                # XLA convs are NHWC-native here: transpose in, conv,
                # transpose back so downstream NCHW consumers see NCHW.
                x = self._aux(node.name + "/nhwc_in", "transpose",
                              [ins[0]], perm=(0, 2, 3, 1))
                y = self._aux(node.name + "/nhwc_out", "conv2d",
                              [x, ins[1]], strides=strides[2:4],
                              padding=pad, dilations=dil[2:4])
                return self._emit(node, "transpose", [y],
                                  perm=(0, 3, 1, 2))
            return self._emit(node, "conv2d", ins,
                              strides=strides[1:3], padding=pad,
                              dilations=dil[1:3])
        if op in ("MaxPool", "AvgPool"):
            k = _tf_attr(node, "ksize", [1, 2, 2, 1])
            s = _tf_attr(node, "strides", [1, 2, 2, 1])
            pool = f"{op[:-4].lower()}_pool"
            pad = _tf_attr(node, "padding", "VALID")
            if _tf_attr(node, "data_format", "NHWC") == "NCHW":
                x = self._aux(node.name + "/nhwc_in", "transpose",
                              [ins[0]], perm=(0, 2, 3, 1))
                y = self._aux(node.name + "/nhwc_out", pool, [x],
                              ksize=k[2:4], strides=s[2:4], padding=pad)
                return self._emit(node, "transpose", [y],
                                  perm=(0, 3, 1, 2))
            return self._emit(node, pool, ins, ksize=k[1:3],
                              strides=s[1:3], padding=pad)
        if op in ("FusedBatchNorm", "FusedBatchNormV2",
                  "FusedBatchNormV3"):
            # Inference-frozen BN: (x, scale, offset, mean, var) -> y.
            # Outputs 1..5 (batch mean/var, reserves) only exist in
            # TRAINING graphs — refuse loudly if anything consumes them
            # rather than silently miswiring (round-2 review weak item 3).
            aux = [f"{node.name}:{i}" for i in range(1, 6)]
            used = sorted(a for a in aux if a in self.consumed_refs)
            if used:
                raise NotImplementedError(
                    f"{op} node {node.name!r}: training outputs {used} "
                    "are consumed — import supports inference-frozen BN "
                    "only (freeze the graph for inference first)")
            eps = _tf_attr(node, "epsilon", 1e-3)
            if _tf_attr(node, "data_format", "NHWC") == "NCHW":
                x = self._aux(node.name + "/nhwc_in", "transpose",
                              [ins[0]], perm=(0, 2, 3, 1))
                y = self._aux(node.name + "/nhwc_out",
                              "fused_batch_norm", [x] + ins[1:5],
                              eps=eps)
                return self._emit(node, "transpose", [y],
                                  perm=(0, 3, 1, 2))
            return self._emit(node, "fused_batch_norm", ins, n_out=1,
                              eps=eps)
        if op == "TopKV2":
            k = int(np.asarray(self._const_of(ins[1])).reshape(()))
            return self._emit(node, "top_k", ins[:1], n_out=2, k=k,
                              sorted=_tf_attr(node, "sorted", True))
        if op == "MatrixBandPart":
            return self._emit(node, "matrix_band_part", ins)
        if op in ("MatrixDiagPartV2", "MatrixDiagPartV3"):
            k = int(np.asarray(self._const_of(ins[1])).reshape(()))
            if k != 0:
                raise NotImplementedError(f"{op} with k={k}")
            return self._emit(node, "matrix_diag_part", ins[:1])
        if op in ("DepthToSpace", "SpaceToDepth"):
            if _tf_attr(node, "data_format", "NHWC") != "NHWC":
                raise NotImplementedError(f"{op} non-NHWC")
            name = ("depth_to_space" if op == "DepthToSpace"
                    else "space_to_depth")
            return self._emit(node, name, ins,
                              block_size=_tf_attr(node, "block_size", 2))
        if op == "SpaceToBatchND":
            return self._emit(node, "space_to_batch_nd", ins)
        if op == "BatchToSpaceND":
            return self._emit(node, "batch_to_space_nd", ins)
        if op in ("ResizeBilinear", "ResizeNearestNeighbor"):
            if _tf_attr(node, "align_corners", False):
                raise NotImplementedError(f"{op} align_corners=True")
            name = ("resize_bilinear" if op == "ResizeBilinear"
                    else "resize_nearest")
            return self._emit(node, name, ins, half_pixel_centers=_tf_attr(
                node, "half_pixel_centers", True))
        if op == "LeakyRelu":
            return self._emit(node, "leaky_relu", ins,
                              alpha=_tf_attr(node, "alpha", 0.2))
        if op == "DepthwiseConv2dNative":
            if _tf_attr(node, "data_format", "NHWC") != "NHWC":
                raise NotImplementedError("NCHW DepthwiseConv2d")
            s = _tf_attr(node, "strides", [1, 1, 1, 1])
            d = _tf_attr(node, "dilations", [1, 1, 1, 1])
            return self._emit(node, "depthwise_conv2d", ins,
                              strides=s[1:3],
                              padding=_tf_attr(node, "padding", "SAME"),
                              dilations=d[1:3])
        if op == "Conv2DBackpropInput":
            # (input_sizes, filter, out_backprop): input_sizes pins the
            # reconstructed spatial shape (odd sizes under SAME/stride>1)
            if _tf_attr(node, "data_format", "NHWC") != "NHWC":
                raise NotImplementedError("NCHW Conv2DBackpropInput")
            s = _tf_attr(node, "strides", [1, 1, 1, 1])
            sizes = [int(v) for v in
                     np.asarray(self._const_of(ins[0])).reshape(-1)]
            return self._emit(node, "conv2d_transpose",
                              [ins[2], ins[1]], strides=s[1:3],
                              padding=_tf_attr(node, "padding", "SAME"),
                              output_shape=sizes)
        if op == "Conv3D":
            s = _tf_attr(node, "strides", [1, 1, 1, 1, 1])
            d = _tf_attr(node, "dilations", [1, 1, 1, 1, 1])
            return self._emit(node, "conv3d", ins, strides=s[1:4],
                              padding=_tf_attr(node, "padding", "SAME"),
                              dilations=d[1:4])
        if op in ("MaxPool3D", "AvgPool3D"):
            k = _tf_attr(node, "ksize", [1, 2, 2, 2, 1])
            s = _tf_attr(node, "strides", [1, 2, 2, 2, 1])
            return self._emit(node, f"{op[:-6].lower()}_pool3d", ins,
                              ksize=k[1:4], strides=s[1:4],
                              padding=_tf_attr(node, "padding", "VALID"))
        if op == "LRN":
            return self._emit(
                node, "lrn", ins,
                depth_radius=_tf_attr(node, "depth_radius", 5),
                bias=_tf_attr(node, "bias", 1.0),
                alpha=_tf_attr(node, "alpha", 1.0),
                beta=_tf_attr(node, "beta", 0.5))
        if op == "SoftmaxCrossEntropyWithLogits":
            return self._emit(
                node, "softmax_cross_entropy_with_logits_v2", ins,
                n_out=2)
        if op == "SparseSoftmaxCrossEntropyWithLogits":
            return self._emit(
                node, "sparse_softmax_cross_entropy_with_logits_v2",
                ins, n_out=2)
        if op == "MatrixTriangularSolve":
            return self._emit(node, "matrix_triangular_solve", ins,
                              lower=_tf_attr(node, "lower", True),
                              adjoint=_tf_attr(node, "adjoint", False))
        if op in ("UnsortedSegmentSum", "UnsortedSegmentMean",
                  "UnsortedSegmentMax"):
            name = {"UnsortedSegmentSum": "unsorted_segment_sum",
                    "UnsortedSegmentMean": "unsorted_segment_mean",
                    "UnsortedSegmentMax": "unsorted_segment_max"}[op]
            n_seg = int(np.asarray(self._const_of(ins[2])).reshape(()))
            return self._emit(node, name, ins[:2], num_segments=n_seg)
        if op == "LSTMBlockCell":
            return self._emit(
                node, "lstm_block_cell", ins, n_out=7,
                forget_bias=_tf_attr(node, "forget_bias", 1.0),
                cell_clip=_tf_attr(node, "cell_clip", 3.0),
                use_peephole=_tf_attr(node, "use_peephole", False),
                gate_order="icfo")
        if op in ("BlockLSTM", "BlockLSTMV2"):
            v2 = op == "BlockLSTMV2"
            return self._emit(
                node, "block_lstm", ins, n_out=7,
                forget_bias=(0.0 if v2
                             else _tf_attr(node, "forget_bias", 1.0)),
                cell_clip=_tf_attr(node, "cell_clip",
                                   0.0 if v2 else 3.0),
                use_peephole=_tf_attr(node, "use_peephole", False),
                gate_order="ifco" if v2 else "icfo")
        if op == "GRUBlockCell":
            return self._emit(node, "gru_block_cell", ins, n_out=4)
        if op in ("StatelessWhile", "While"):
            cond_sd = self._import_function(node.attr["cond"].func.name)
            body_sd = self._import_function(node.attr["body"].func.name)
            return self._emit(node, "while_loop", ins, n_out=len(ins),
                              cond=cond_sd, body=body_sd)
        if op in ("StatelessIf", "If"):
            then_sd = self._import_function(
                node.attr["then_branch"].func.name)
            else_sd = self._import_function(
                node.attr["else_branch"].func.name)
            n_out = len(node.attr["Tout"].list.type) or 1
            return self._emit(node, "cond", ins, n_out=n_out,
                              then=then_sd, orelse=else_sd)
        raise NotImplementedError(
            f"TF op {op!r} (node {node.name!r}) has no import mapping — "
            "register one in deeplearning4j_tpu/autodiff/tf_import.py")

    def _import_function(self, fname: str):
        """FunctionDef (from graph_def.library) → sub-SameDiff with
        ordered placeholders and designated outputs — the body of a
        while_loop/cond IR node.  Uses TF's own function_def_to_graph
        so `node:out:i` function-body tensor refs resolve correctly."""
        from tensorflow.python.framework.function_def_to_graph import (
            function_def_to_graph)
        fdef = next((f for f in self.library.function
                     if f.signature.name == fname), None)
        if fdef is None:
            raise ValueError(f"Function {fname!r} not in graph library")
        fg = function_def_to_graph(fdef)
        sub = _Importer(fg.as_graph_def(), trainable_consts=False,
                        library=self.library)
        sub_sd = sub.run(prune=False)
        sub_sd.outputs = []
        for t in fg.outputs:
            name = t.op.name if t.value_index == 0 else \
                f"{t.op.name}:{t.value_index}"
            sub_sd.outputs.append(name)
        return sub_sd

    def run(self, prune: bool = True) -> SameDiff:
        nodes = list(self.gd.node)
        # GraphDefs from freezing are topologically sorted, but don't rely
        # on it (Kahn over tensor deps).
        produced = set()
        pending = nodes
        ordered = []
        while pending:
            rest = []
            for n in pending:
                deps = [i.split("^")[-1].split(":")[0] for i in n.input]
                if all(d in produced for d in deps):
                    ordered.append(n)
                    produced.add(n.name)
                else:
                    rest.append(n)
            if len(rest) == len(pending):
                raise ValueError(
                    f"Cyclic or dangling graph: {[n.name for n in rest[:5]]}")
            pending = rest
        for node in ordered:
            self._handle(node)
        # Dead-code elimination: consts only consumed by skipped nodes
        # (Assert messages and the like — including non-numeric string
        # tensors npz can't store) are dropped.  Subgraph imports skip
        # this (prune=False): a function OUTPUT may legally be a raw
        # placeholder/const no op consumes.
        if prune:
            consumed = {i for n in self.sd.ops for i in n.inputs}
            produced = {o for n in self.sd.ops for o in n.outputs}
            for name in list(self.sd.values):
                if name not in consumed and name not in produced:
                    del self.sd.values[name]
                    del self.sd.vars[name]
        return self.sd


def _register_extra_ops():
    """Ops only the importer produces (einsum, fused_batch_norm)."""
    from deeplearning4j_tpu.autodiff.ops import OP_REGISTRY, register_op
    import jax.numpy as jnp
    from jax import lax
    if "einsum" not in OP_REGISTRY:
        register_op("einsum")(
            lambda *xs, equation: jnp.einsum(equation, *xs))
    if "fused_batch_norm" not in OP_REGISTRY:
        @register_op("fused_batch_norm")
        def _fbn(x, scale, offset, mean, var, eps=1e-3):
            inv = lax.rsqrt(var + eps) * scale
            return x * inv + (offset - mean * inv)


_register_extra_ops()


def import_graph_def(graph_def, trainable_consts: bool = True,
                     trainable_filter: Optional[Callable] = None
                     ) -> SameDiff:
    """GraphDef proto (frozen) → SameDiff IR.

    ``trainable_filter(name, np_value) -> bool`` overrides the default
    which-consts-become-VARIABLEs heuristic (see
    ``_default_trainable_filter``)."""
    return _Importer(graph_def, trainable_consts, trainable_filter).run()


def import_frozen_pb(path: str, trainable_consts: bool = True,
                     trainable_filter: Optional[Callable] = None
                     ) -> SameDiff:
    """Frozen ``.pb`` file → SameDiff IR (TFGraphMapper.importGraph)."""
    from tensorflow.core.framework import graph_pb2
    gd = graph_pb2.GraphDef()
    with open(path, "rb") as f:
        gd.ParseFromString(f.read())
    return import_graph_def(gd, trainable_consts, trainable_filter)


def import_saved_model(path: str, signature: str = "serving_default",
                       trainable_consts: bool = True,
                       trainable_filter: Optional[Callable] = None
                       ) -> SameDiff:
    """TF SavedModel DIRECTORY → SameDiff IR (the
    ``TFFrameworkImporter`` SavedModel entry): loads the signature's
    concrete function, folds variables to constants, imports the frozen
    GraphDef."""
    import tensorflow as tf
    from tensorflow.python.framework.convert_to_constants import (
        convert_variables_to_constants_v2)
    loaded = tf.saved_model.load(path)
    sig = loaded.signatures.get(signature)
    if sig is None:
        raise ValueError(
            f"SavedModel at {path!r} has no signature {signature!r}; "
            f"available: {sorted(loaded.signatures)}")
    frozen = convert_variables_to_constants_v2(sig)
    return import_graph_def(frozen.graph.as_graph_def(),
                            trainable_consts, trainable_filter)


def freeze_keras_model(model, input_signature) -> "Any":
    """Helper: tf.keras/``transformers`` TF model → frozen GraphDef with
    variables folded to Const (what ``import_graph_def`` consumes).
    Functional control flow is preserved (lower_control_flow=False) so
    graphs with loops import as while_loop/cond IR nodes instead of
    un-importable v1 Switch/Merge frames."""
    import tensorflow as tf
    from tensorflow.python.framework.convert_to_constants import (
        convert_variables_to_constants_v2)
    fn = tf.function(lambda *a: model(*a))
    concrete = fn.get_concrete_function(*input_signature)
    frozen = convert_variables_to_constants_v2(concrete,
                                               lower_control_flow=False)
    return frozen.graph.as_graph_def(), concrete
