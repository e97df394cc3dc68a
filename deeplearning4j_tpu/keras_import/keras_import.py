"""Keras HDF5 → MultiLayerNetwork / ComputationGraph.

Scope (the layer set covering this repo's zoo, per review item 6):
InputLayer, Dense, Conv2D, DepthwiseConv2D, MaxPooling2D,
AveragePooling2D, GlobalAveragePooling2D, BatchNormalization, Flatten,
Dropout, Activation, ZeroPadding2D, Embedding, LSTM, Add, Concatenate.

Weight-layout facts used (verified against keras 3.13):
* Dense kernel [in, out] — identical to our ``DenseLayer`` "W".
* Conv2D kernel HWIO, channels_last — identical to our NHWC/HWIO stack.
* LSTM kernel [in, 4u], recurrent [u, 4u], bias [4u], gate order
  i, f, g(cell), o — identical to our fused LSTM layout.
* BatchNormalization: gamma, beta (params) + moving_mean, moving_variance
  (state).
"""
from __future__ import annotations

import json
from typing import Any, Dict, List, Optional, Tuple

import h5py
import numpy as np

from deeplearning4j_tpu.models.computation_graph import ComputationGraph
from deeplearning4j_tpu.models.multi_layer_network import MultiLayerNetwork
from deeplearning4j_tpu.nn.conf.builder import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.graph_vertices import (
    ElementWiseVertex, MergeVertex, PreprocessorVertex)
from deeplearning4j_tpu.nn.conf.inputs import InputType, Preprocessor
from deeplearning4j_tpu.nn.conf.layers_conv import (
    BatchNormalization, ConvolutionLayer, DepthwiseConvolution2D,
    GlobalPoolingLayer, SubsamplingLayer, ZeroPaddingLayer)
from deeplearning4j_tpu.nn.conf.layers_core import (
    ActivationLayer, DenseLayer, DropoutLayer, EmbeddingLayer, OutputLayer)
from deeplearning4j_tpu.nn.conf.layers_recurrent import (
    LSTM, LastTimeStep, RnnOutputLayer)

_ACTIVATIONS = {
    "linear": "identity", "relu": "relu", "relu6": "relu6",
    "sigmoid": "sigmoid", "tanh": "tanh", "softmax": "softmax",
    "softplus": "softplus", "softsign": "softsign", "elu": "elu",
    "selu": "selu", "gelu": "gelu", "swish": "swish",
    "hard_sigmoid": "hardsigmoid", "leaky_relu": "leakyrelu",
    "exponential": "exp",
}


def _act(name: Optional[str]) -> str:
    if not name:
        return "identity"
    out = _ACTIVATIONS.get(str(name).lower())
    if out is None:
        raise ValueError(f"Unsupported Keras activation {name!r}")
    return out


def _pair(v):
    return tuple(v) if isinstance(v, (list, tuple)) else (v, v)


class KerasModelImport:
    """``KerasModelImport.importKerasSequentialModelAndWeights`` /
    ``importKerasModelAndWeights`` equivalents."""

    # ------------------------------------------------------------------
    @staticmethod
    def import_keras_model_and_weights(path: str):
        """Auto-detects Sequential vs Functional; returns
        MultiLayerNetwork or ComputationGraph with weights loaded."""
        with h5py.File(path, "r") as f:
            cfg = f.attrs.get("model_config")
            if cfg is None:
                raise ValueError(
                    f"{path!r} has no model_config attr — not a legacy "
                    "Keras full-model .h5 (Keras 3: save with "
                    "model.save('m.h5'))")
            d = json.loads(cfg)
            weights = KerasModelImport._read_weights(f["model_weights"])
        if d["class_name"] == "Sequential":
            return KerasModelImport._import_sequential(d["config"], weights)
        if d["class_name"] in ("Functional", "Model"):
            return KerasModelImport._import_functional(d["config"], weights)
        raise ValueError(f"Unsupported model class {d['class_name']!r}")

    # alias matching the DL4J static-method names
    import_keras_sequential_model_and_weights = \
        import_keras_model_and_weights

    # ------------------------------------------------------------------
    @staticmethod
    def _read_weights(grp) -> Dict[str, Dict[str, np.ndarray]]:
        """model_weights/<layer>/**/<leaf> → {layer: {leaf: array}}."""
        out: Dict[str, Dict[str, np.ndarray]] = {}
        for layer_name in grp:
            leaf: Dict[str, np.ndarray] = {}

            def visit(name, obj):
                if isinstance(obj, h5py.Dataset):
                    leaf[name.split("/")[-1].split(":")[0]] = np.asarray(obj)
            grp[layer_name].visititems(visit)
            if leaf:
                out[layer_name] = leaf
        return out

    # ------------------------------------------------------------------
    # Layer conversion
    # ------------------------------------------------------------------
    @staticmethod
    def _convert(cls_name: str, c: dict, is_last: bool):
        """One keras layer config → (our layer conf or None, params_map)
        where params_map maps our param name → keras leaf name."""
        name = c.get("name")
        if cls_name == "Dense":
            act = _act(c.get("activation"))
            if is_last:
                loss = "mcxent" if act == "softmax" else (
                    "xent" if act == "sigmoid" else "mse")
                ly = OutputLayer(n_out=c["units"], activation=act,
                                 loss=loss, has_bias=c.get("use_bias", True))
            else:
                ly = DenseLayer(n_out=c["units"], activation=act,
                                has_bias=c.get("use_bias", True))
            ly.name = name
            return ly, {"W": "kernel", "b": "bias"}
        if cls_name == "Conv2D":
            ly = ConvolutionLayer(
                n_out=c["filters"], kernel_size=_pair(c["kernel_size"]),
                stride=_pair(c.get("strides", 1)),
                dilation=_pair(c.get("dilation_rate", 1)),
                convolution_mode=("same" if c.get("padding") == "same"
                                  else "truncate"),
                activation=_act(c.get("activation")),
                has_bias=c.get("use_bias", True))
            ly.name = name
            return ly, {"W": "kernel", "b": "bias"}
        if cls_name == "DepthwiseConv2D":
            ly = DepthwiseConvolution2D(
                kernel_size=_pair(c["kernel_size"]),
                stride=_pair(c.get("strides", 1)),
                depth_multiplier=c.get("depth_multiplier", 1),
                convolution_mode=("same" if c.get("padding") == "same"
                                  else "truncate"),
                activation=_act(c.get("activation")),
                has_bias=c.get("use_bias", True))
            ly.name = name
            return ly, {"W": "depthwise_kernel", "b": "bias"}
        if cls_name in ("MaxPooling2D", "AveragePooling2D"):
            ly = SubsamplingLayer(
                kernel_size=_pair(c.get("pool_size", 2)),
                stride=_pair(c.get("strides") or c.get("pool_size", 2)),
                pooling_type="max" if cls_name.startswith("Max") else "avg",
                convolution_mode=("same" if c.get("padding") == "same"
                                  else "truncate"))
            ly.name = name
            return ly, {}
        if cls_name in ("GlobalAveragePooling2D", "GlobalMaxPooling2D"):
            ly = GlobalPoolingLayer(
                pooling_type="avg" if "Average" in cls_name else "max")
            ly.name = name
            return ly, {}
        if cls_name == "BatchNormalization":
            ly = BatchNormalization(eps=c.get("epsilon", 1e-3),
                                    decay=c.get("momentum", 0.99))
            ly.name = name
            return ly, {"gamma": "gamma", "beta": "beta",
                        "state:mean": "moving_mean",
                        "state:var": "moving_variance"}
        if cls_name == "Dropout":
            ly = DropoutLayer(rate=c.get("rate", 0.5))
            ly.name = name
            return ly, {}
        if cls_name == "Activation":
            ly = ActivationLayer(activation=_act(c.get("activation")))
            ly.name = name
            return ly, {}
        if cls_name == "ZeroPadding2D":
            pad = c.get("padding", 1)
            if isinstance(pad, (list, tuple)) and isinstance(
                    pad[0], (list, tuple)):
                pad = (pad[0][0], pad[0][1], pad[1][0], pad[1][1])
            ly = ZeroPaddingLayer(padding=pad)
            ly.name = name
            return ly, {}
        if cls_name == "Embedding":
            ly = EmbeddingLayer(n_in=c["input_dim"], n_out=c["output_dim"])
            ly.name = name
            return ly, {"W": "embeddings"}
        if cls_name == "LSTM":
            ly = LSTM(n_out=c["units"],
                      activation=_act(c.get("activation", "tanh")),
                      gate_activation=_act(
                          c.get("recurrent_activation", "sigmoid")))
            ly.name = name
            return ly, {"W": "kernel", "R": "recurrent_kernel", "b": "bias"}
        if cls_name == "Flatten":
            return None, {}  # our conv→ff preprocessor auto-inserts
        raise ValueError(
            f"Unsupported Keras layer {cls_name!r} ({name!r}) — extend "
            "deeplearning4j_tpu/keras_import/keras_import.py")

    @staticmethod
    def _input_type(batch_shape) -> InputType:
        dims = [d for d in batch_shape[1:]]
        if len(dims) == 3:
            return InputType.convolutional(dims[0], dims[1], dims[2])
        if len(dims) == 2:
            return InputType.recurrent(dims[1], dims[0])
        return InputType.feed_forward(dims[0])

    # ------------------------------------------------------------------
    @staticmethod
    def _import_sequential(cfg: dict, weights) -> MultiLayerNetwork:
        layers_cfg = cfg["layers"] if isinstance(cfg, dict) else cfg
        lb = NeuralNetConfiguration.builder().list()
        converted: List[Tuple[Any, Dict[str, str], str]] = []
        last_real = None
        for i, lc in enumerate(layers_cfg):
            if lc["class_name"] != "Flatten":
                last_real = i
        # A model ending Dense → Activation('softmax') must import as ONE
        # OutputLayer (activation folded in), not DenseLayer+ActivationLayer
        # — the latter leaves the network without a loss head and fails
        # later in fit() with a confusing error (advisor round 2).
        folded_act, skip_idx = None, None
        if layers_cfg and layers_cfg[-1]["class_name"] == "Activation":
            j = len(layers_cfg) - 2
            # Only Flatten may sit between (it is shape-only and never
            # emitted); a Dropout there changes training numerics, and a
            # Dense with its own non-linearity composes two activations
            # — both cases keep the un-folded import.
            while j >= 0 and layers_cfg[j]["class_name"] == "Flatten":
                j -= 1
            if j >= 0 and layers_cfg[j]["class_name"] == "Dense" and \
                    _act(layers_cfg[j]["config"].get("activation")) == \
                    "identity":
                skip_idx = len(layers_cfg) - 1
                last_real = j
                folded_act = layers_cfg[-1]["config"].get("activation")
        for i, lc in enumerate(layers_cfg):
            if i == skip_idx:
                continue
            cls, c = lc["class_name"], lc["config"]
            if i == last_real and folded_act is not None:
                c = dict(c, activation=folded_act)
            if cls == "InputLayer":
                shape = c.get("batch_shape") or c.get("batch_input_shape")
                lb.set_input_type(KerasModelImport._input_type(shape))
                continue
            if i == 0 and (c.get("batch_input_shape") is not None):
                lb.set_input_type(KerasModelImport._input_type(
                    c["batch_input_shape"]))
            ly, pmap = KerasModelImport._convert(cls, c, i == last_real)
            if ly is None:
                continue
            # keras LSTM with return_sequences=False: append LastTimeStep
            lb.layer(ly)
            converted.append((ly, pmap, c.get("name")))
            if cls == "LSTM" and not c.get("return_sequences", False):
                lb.layer(LastTimeStep())
                converted.append((LastTimeStep(), {}, None))
        model = MultiLayerNetwork(lb.build()).init()
        KerasModelImport._copy_weights_mln(model, converted, weights)
        return model

    @staticmethod
    def _copy_weights_mln(model, converted, weights):
        li = 0
        for ly, pmap, kname in converted:
            key = f"layer_{li}"
            li += 1
            if not pmap or kname not in weights:
                continue
            KerasModelImport._fill(model.params_tree[key],
                                   model.state_tree[key], pmap,
                                   weights[kname], kname)

    @staticmethod
    def _fill(params, state, pmap, w, kname):
        for ours, theirs in pmap.items():
            if theirs not in w:
                if ours == "b":
                    continue  # use_bias=False
                raise KeyError(
                    f"Layer {kname!r}: missing weight {theirs!r}; "
                    f"have {sorted(w)}")
            val = np.asarray(w[theirs])
            if ours.startswith("state:"):
                tgt = state
                ours = ours.split(":", 1)[1]
            else:
                tgt = params
            if tuple(tgt[ours].shape) != tuple(val.shape):
                raise ValueError(
                    f"Layer {kname!r} weight {ours}: shape "
                    f"{val.shape} != expected {tuple(tgt[ours].shape)}")
            tgt[ours] = val.astype(np.asarray(tgt[ours]).dtype)

    # ------------------------------------------------------------------
    @staticmethod
    def _import_functional(cfg: dict, weights) -> ComputationGraph:
        layers_cfg = cfg["layers"]

        def _refs(spec) -> List[str]:
            """'name' | ['name', n, t] | [['a',0,0], ['b',0,0]] — keras
            flattens single-output refs to one triple."""
            if isinstance(spec, str):
                return [spec]
            if (isinstance(spec, list) and spec
                    and isinstance(spec[0], str)):
                return [spec[0]]
            return [r for s in spec for r in _refs(s)]

        in_names = _refs(cfg.get("input_layers", []))
        out_names = _refs(cfg.get("output_layers", []))

        g = NeuralNetConfiguration.builder().graph()
        converted: Dict[str, Tuple[Any, Dict[str, str]]] = {}
        input_types = []
        for lc in layers_cfg:
            cls, c, name = lc["class_name"], lc["config"], lc["config"]["name"]
            inbound = lc.get("inbound_nodes", [])
            srcs = KerasModelImport._inbound_names(inbound)
            if cls == "InputLayer":
                g.add_inputs(name)
                shape = c.get("batch_shape") or c.get("batch_input_shape")
                input_types.append(KerasModelImport._input_type(shape))
                continue
            if cls == "Add":
                g.add_vertex(name, ElementWiseVertex("add"), *srcs)
                continue
            if cls in ("Concatenate", "Merge"):
                g.add_vertex(name, MergeVertex(), *srcs)
                continue
            is_out = name in out_names
            ly, pmap = KerasModelImport._convert(cls, c, is_out)
            if ly is None:  # Flatten -> explicit cnn_to_ff vertex
                g.add_vertex(name, PreprocessorVertex(
                    Preprocessor("cnn_to_ff")), *srcs)
                continue
            g.add_layer(name, ly, *srcs)
            converted[name] = (ly, pmap)
        g.set_input_types(*input_types)
        g.set_outputs(*out_names)
        model = ComputationGraph(g.build()).init()
        for name, (ly, pmap) in converted.items():
            if pmap and name in weights:
                KerasModelImport._fill(model.params_tree[name],
                                       model.state_tree[name], pmap,
                                       weights[name], name)
        return model

    @staticmethod
    def _inbound_names(inbound) -> List[str]:
        """Keras 3 inbound_nodes: [{'args': [{'class_name':
        '__keras_tensor__', 'config': {'keras_history': [name, ...]}}...]}]
        (legacy: [[[name, 0, 0, {}], ...]])."""
        names: List[str] = []

        def walk(x):
            if isinstance(x, dict):
                if x.get("class_name") == "__keras_tensor__":
                    names.append(x["config"]["keras_history"][0])
                else:
                    for v in x.values():
                        walk(v)
            elif isinstance(x, list):
                if (len(x) >= 3 and isinstance(x[0], str)
                        and isinstance(x[1], int)):
                    names.append(x[0])  # legacy [name, node, tensor, {}]
                else:
                    for v in x:
                        walk(v)
        walk(inbound)
        return names

