"""SameDiff-equivalent: serializable define-by-run graph IR.

Parity target: ``org.nd4j.autodiff.samediff.SameDiff`` (the ~12-kLoC JVM
class), ``SDVariable``, ``TrainingConfig``, and the FlatBuffers
``SameDiff.save/load`` format (SURVEY.md §2.2, §3.3).

TPU-first redesign, not a port:

* DL4J's ``InferenceSession``/``TrainingSession`` interpret the DAG
  op-by-op (dep-tracking queue, one JNI crossing per op — SURVEY §3.3 "HOT
  LOOP").  Here ``output``/``fit`` TRACE the recorded graph into a single
  jitted XLA program; the topological walk happens once at trace time.
* Reverse-mode: DL4J maintains a mirrored gradient graph (per-op
  ``doDiff``).  Here gradients are ``jax.grad`` of the traced function —
  there is no gradient graph to build, serialize, or get out of sync.
* Serialization is a zip of ``graph.json`` (structure) + ``values.npz``
  (VARIABLE/CONSTANT arrays) instead of FlatBuffers.
"""
from __future__ import annotations

import dataclasses
import io
import json
import zipfile
from typing import Any, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.autodiff.ops import get_op
from deeplearning4j_tpu.optimize.updaters import (
    BaseUpdater, updater_from_dict)

VAR_TYPES = ("VARIABLE", "CONSTANT", "PLACEHOLDER", "ARRAY")


def _clean_attr(v):
    """JSON-safe attrs (TF import hands us np arrays/bytes/dtypes;
    control-flow ops carry whole subgraphs)."""
    if isinstance(v, SameDiff):
        return {"__subgraph__": v.to_portable_dict()}
    if isinstance(v, bytes):
        return v.decode()
    if isinstance(v, (np.ndarray, np.generic)):
        return np.asarray(v).tolist()
    if isinstance(v, (list, tuple)):
        return [_clean_attr(x) for x in v]
    if isinstance(v, np.dtype):
        return v.name
    return v


def _revive_attr(v):
    """Inverse of _clean_attr for the subgraph case."""
    if isinstance(v, dict) and "__subgraph__" in v:
        return SameDiff.from_portable_dict(v["__subgraph__"])
    return v


@dataclasses.dataclass
class SDVariable:
    """A named symbol in the graph (``org.nd4j.autodiff.samediff
    .SDVariable``): VARIABLE (trainable), CONSTANT, PLACEHOLDER (fed), or
    ARRAY (op output)."""

    sd: "SameDiff"
    name: str
    var_type: str
    shape: Optional[Sequence[int]] = None
    dtype: str = "float32"

    # -- ergonomic operator sugar (SDVariable.add/mul/... in DL4J) --
    def _bin(self, op, other, reverse=False):
        other = self.sd._as_var(other)
        a, b = (other, self) if reverse else (self, other)
        return self.sd.op(op, a, b)

    def __add__(self, o):
        return self._bin("add", o)

    def __radd__(self, o):
        return self._bin("add", o, True)

    def __sub__(self, o):
        return self._bin("sub", o)

    def __rsub__(self, o):
        return self._bin("sub", o, True)

    def __mul__(self, o):
        return self._bin("mul", o)

    def __rmul__(self, o):
        return self._bin("mul", o, True)

    def __truediv__(self, o):
        return self._bin("div", o)

    def __matmul__(self, o):
        return self._bin("matmul", o)

    def __neg__(self):
        return self.sd.op("neg", self)

    def eval(self, feeds: Optional[Dict[str, Any]] = None):
        return self.sd.output(feeds or {}, [self.name])[self.name]


@dataclasses.dataclass
class OpNode:
    op_name: str
    inputs: List[str]
    outputs: List[str]
    attrs: Dict[str, Any]

    def to_dict(self):
        return {"op": self.op_name, "inputs": self.inputs,
                "outputs": self.outputs,
                "attrs": {k: _clean_attr(v) for k, v in self.attrs.items()}}


@dataclasses.dataclass
class TrainingConfig:
    """``org.nd4j.autodiff.samediff.TrainingConfig`` analogue: updater,
    l2, and the mapping from DataSet slots to placeholder names."""

    updater: Union[BaseUpdater, dict]
    l2: float = 0.0
    data_set_feature_mapping: Sequence[str] = ()
    data_set_label_mapping: Sequence[str] = ()
    # Mixed-precision policy for the TRAINING path only ("bfloat16" =
    # AMP: f32 master weights, float leaves cast to bf16 at graph entry,
    # loss accumulated f32; grads come back f32 through the cast).  The
    # reference has no AMP (fp32-only cuDNN helper path) — this is a
    # TPU-first capability, required to keep imported-graph fine-tunes
    # on the MXU's bf16 path.  output()/golden parity are unaffected.
    compute_dtype: Optional[str] = None

    def resolved_updater(self) -> BaseUpdater:
        u = self.updater
        return updater_from_dict(u) if isinstance(u, dict) else u


class SameDiff:
    """The graph container + builder + executor."""

    def __init__(self):
        self.vars: Dict[str, SDVariable] = {}
        self.values: Dict[str, np.ndarray] = {}  # VARIABLE + CONSTANT
        self.ops: List[OpNode] = []  # creation order == topological order
        self.loss_variables: List[str] = []
        self.training_config: Optional[TrainingConfig] = None
        # designated outputs (subgraphs need an explicit, ordered list)
        self.outputs: Optional[List[str]] = None
        self._updater_state = None
        self._step = 0
        self._fn_cache: Dict[Any, Any] = {}

    @staticmethod
    def create() -> "SameDiff":
        return SameDiff()

    # ------------------------------------------------------------------
    # Variable creation
    # ------------------------------------------------------------------
    def _unique(self, base: str) -> str:
        if base not in self.vars:
            return base
        i = 1
        while f"{base}_{i}" in self.vars:
            i += 1
        return f"{base}_{i}"

    def _register(self, name, var_type, shape=None, dtype="float32"):
        v = SDVariable(self, name, var_type,
                       tuple(shape) if shape is not None else None,
                       str(dtype))
        self.vars[name] = v
        return v

    def placeholder(self, name: str, shape=None, dtype="float32") -> SDVariable:
        return self._register(self._unique(name), "PLACEHOLDER", shape, dtype)

    def var(self, name: str, value=None, shape=None, dtype="float32",
            initializer: str = "zeros", key=None) -> SDVariable:
        """Trainable variable; give an array, or shape+initializer."""
        name = self._unique(name)
        if value is None:
            if initializer == "zeros":
                value = np.zeros(shape, dtype)
            elif initializer == "ones":
                value = np.ones(shape, dtype)
            elif initializer == "normal":
                k = key if key is not None else jax.random.key(0)
                value = np.asarray(jax.random.normal(k, shape, dtype))
            else:
                raise ValueError(f"Unknown initializer {initializer!r}")
        value = np.asarray(value)
        self.values[name] = value
        return self._register(name, "VARIABLE", value.shape, value.dtype.name)

    def constant(self, name: str, value) -> SDVariable:
        name = self._unique(name)
        value = np.asarray(value)
        self.values[name] = value
        return self._register(name, "CONSTANT", value.shape, value.dtype.name)

    def _as_var(self, x) -> SDVariable:
        if isinstance(x, SDVariable):
            return x
        return self.constant("const", np.asarray(x))

    # ------------------------------------------------------------------
    # Op recording
    # ------------------------------------------------------------------
    def op(self, op_name: str, *inputs, name: Optional[str] = None,
           n_out: Optional[int] = None, **attrs):
        """Record one op; returns its SDVariable (or tuple for multi-out).
        The registry is consulted eagerly so unknown ops fail at build
        time (the DeclarableOp lookup, minus the JNI)."""
        opdef = get_op(op_name)
        in_vars = [self._as_var(x) for x in inputs]
        if n_out is None and opdef.n_out == 0:
            raise ValueError(
                f"Op {op_name!r} has a variable output count — pass "
                "n_out= explicitly (e.g. sd.op('split', x, n_out=3, ...))")
        n = n_out if n_out is not None else opdef.n_out
        base = name or op_name
        outs = [self._unique(base if n == 1 else f"{base}:{i}")
                for i in range(n)]
        self.ops.append(OpNode(op_name, [v.name for v in in_vars], outs,
                               attrs))
        out_vars = [self._register(o, "ARRAY") for o in outs]
        self._fn_cache.clear()
        return out_vars[0] if n == 1 else tuple(out_vars)

    def __getattr__(self, item):
        # sd.matmul(a, b) sugar for any registered op.
        from deeplearning4j_tpu.autodiff.ops import OP_REGISTRY
        if item in OP_REGISTRY:
            return lambda *a, **kw: self.op(item, *a, **kw)
        raise AttributeError(item)

    def set_loss_variables(self, *names):
        self.loss_variables = [n.name if isinstance(n, SDVariable) else n
                               for n in names]

    # ------------------------------------------------------------------
    # Execution (trace-to-XLA — replaces InferenceSession's interpreter)
    # ------------------------------------------------------------------
    def _run_graph(self, param_vals: Dict[str, Any],
                   feed_vals: Dict[str, Any], needed: set,
                   compute_dtype: Optional[str] = None) -> Dict[str, Any]:
        if compute_dtype is None:
            cast = lambda v: v
        else:
            cd = jnp.dtype(compute_dtype)

            def cast(v):
                # only float leaves move; ids/masks/bools stay put
                dt = np.asarray(v).dtype if not hasattr(v, "dtype") \
                    else v.dtype
                if np.issubdtype(dt, np.floating):
                    return jnp.asarray(v, cd)
                return v
        env: Dict[str, Any] = {}
        for k, v in self.values.items():
            if self.vars[k].var_type == "CONSTANT":
                env[k] = cast(v) if compute_dtype else v
        env.update({k: cast(v) for k, v in param_vals.items()})
        env.update({k: cast(v) for k, v in feed_vals.items()})
        for node in self.ops:
            if not any(o in needed for o in node.outputs):
                continue
            args = [env[i] for i in node.inputs]
            if node.op_name == "while_loop":
                out = self._exec_while(node, args)
            elif node.op_name == "cond":
                out = self._exec_cond(node, args)
            else:
                op = get_op(node.op_name)
                out = op.fn(*args, **node.attrs)
            if len(node.outputs) == 1:
                env[node.outputs[0]] = out
            else:
                for o, v in zip(node.outputs, out):
                    env[o] = v
        return env

    # ------------------------------------------------------------------
    # Control flow (SURVEY §3.3: the TF Switch/Merge/Enter/Exit frame
    # machinery of AbstractSession becomes structured lax.while_loop /
    # lax.cond — compiler-friendly, no per-op frame interpreter)
    # ------------------------------------------------------------------
    def run_subgraph(self, inputs: Sequence[Any]) -> List[Any]:
        """Execute this graph as a PURE function: `inputs` bind to the
        placeholders in registration order; returns the designated
        ``self.outputs`` (explicit, ordered — required for subgraphs)."""
        ph = [v.name for v in self.vars.values()
              if v.var_type == "PLACEHOLDER"]
        if len(ph) != len(inputs):
            raise ValueError(
                f"subgraph expects {len(ph)} inputs ({ph}), got "
                f"{len(inputs)}")
        outs = self.outputs
        if not outs:
            raise ValueError("subgraph has no designated outputs")
        needed = self._needed_for(outs)
        env = self._run_graph(self._param_values(),
                              dict(zip(ph, inputs)), needed)
        return [env[o] for o in outs]

    @staticmethod
    def _resolve_ident(sub: "SameDiff", name: str, depth: int = 4) -> str:
        """Follow identity ops backward inside a subgraph."""
        prod = {o: n for n in sub.ops for o in n.outputs}
        for _ in range(depth):
            n = prod.get(name)
            if n is None or n.op_name != "identity":
                return name
            name = n.inputs[0]
        return name

    def _while_static_pattern(self, node):
        """Match the bounded-counter loop shape (round-3 review item 5):
        cond is ``less(state_k, N)`` with N a cond-graph constant or a
        pass-through loop var, and the body increments state_k by
        exactly 1.  Returns (k, ("const", N) | ("state", j)) or None.
        For this shape ``lax.scan`` with a static trip count is
        EXACTLY equivalent to the while (cond holds for
        i = init..N-1 and fails at N) — and scan, unlike XLA while,
        is reverse-differentiable, so imported graphs with bounded
        loops in the loss path can fine-tune."""
        cond_sd, body_sd = node.attrs["cond"], node.attrs["body"]
        ph = [v.name for v in cond_sd.vars.values()
              if v.var_type == "PLACEHOLDER"]
        outs = cond_sd.outputs or []
        if len(outs) != 1:
            return None
        prod = {o: n for n in cond_sd.ops for o in n.outputs}
        less = prod.get(self._resolve_ident(cond_sd, outs[0]))
        if less is None or less.op_name != "less":
            return None
        a = self._resolve_ident(cond_sd, less.inputs[0])
        b = self._resolve_ident(cond_sd, less.inputs[1])
        if a not in ph:
            return None
        k = ph.index(a)
        bv = cond_sd.vars.get(b)
        if bv is not None and bv.var_type == "CONSTANT":
            nval = np.asarray(cond_sd.values[b])
            if not np.issubdtype(nval.dtype, np.integer):
                return None      # float bound: int() would truncate
            bound = ("const", int(nval.reshape(())))
        elif b in ph:
            bound = ("state", ph.index(b))
        else:
            return None
        bph = [v.name for v in body_sd.vars.values()
               if v.var_type == "PLACEHOLDER"]
        bouts = body_sd.outputs or []
        if len(bouts) != len(bph) or k >= len(bouts):
            return None
        bprod = {o: n for n in body_sd.ops for o in n.outputs}
        inc = bprod.get(self._resolve_ident(body_sd, bouts[k]))
        if inc is None or inc.op_name != "add":
            return None
        i0 = self._resolve_ident(body_sd, inc.inputs[0])
        i1 = self._resolve_ident(body_sd, inc.inputs[1])
        if i0 == bph[k]:
            step = i1
        elif i1 == bph[k]:
            step = i0
        else:
            return None
        sv = body_sd.vars.get(step)
        if sv is None or sv.var_type != "CONSTANT":
            return None
        sval = np.asarray(body_sd.values[step])
        if not np.issubdtype(sval.dtype, np.integer) or \
                int(sval.reshape(())) != 1:
            return None
        if bound[0] == "state":
            j = bound[1]
            if self._resolve_ident(body_sd, bouts[j]) != bph[j]:
                return None          # bound must ride unchanged
        return k, bound

    def _while_trip_static(self, node, args):
        """Static trip count when the counter pattern matches AND the
        init/bound values are host-known at trace time, else None."""
        pat = self._while_static_pattern(node)
        if pat is None:
            return None
        k, bound = pat

        def host_int(v):
            if isinstance(v, jax.core.Tracer):
                return None
            try:
                a = np.asarray(v)
                if not np.issubdtype(a.dtype, np.integer):
                    return None   # float counter: int() would truncate
                return int(a.reshape(()))
            except Exception:
                return None
        init = host_int(args[k])
        if init is None:
            return None
        n = bound[1] if bound[0] == "const" else host_int(args[bound[1]])
        if n is None:
            return None
        return max(0, n - init)

    def _exec_while(self, node, args):
        """``while cond(*state): state = body(*state)``.  Bounded
        counter loops (see ``_while_static_pattern``) lower to
        ``lax.scan`` with a static trip count — reverse-differentiable,
        so they can sit in a fine-tune loss path.  Everything else
        lowers to lax.while_loop (inference only — XLA while is not
        reverse-differentiable).  State is ALL inputs (TF v2 While
        semantics: captured tensors ride as pass-through loop vars)."""
        cond_sd, body_sd = node.attrs["cond"], node.attrs["body"]
        init = tuple(jnp.asarray(a) for a in args)
        trip = self._while_trip_static(node, args)
        if trip is not None:
            def scan_body(state, _):
                r = body_sd.run_subgraph(list(state))
                return tuple(jnp.asarray(x).astype(i.dtype)
                             for x, i in zip(r, init)), None
            out, _ = jax.lax.scan(scan_body, init, None,
                                  length=int(trip))
            return out if len(node.outputs) > 1 else out[0]

        def cond_fn(state):
            r = cond_sd.run_subgraph(list(state))
            return jnp.reshape(jnp.asarray(r[0]), ()).astype(bool)

        def body_fn(state):
            r = body_sd.run_subgraph(list(state))
            return tuple(jnp.asarray(x).astype(i.dtype)
                         for x, i in zip(r, init))

        out = jax.lax.while_loop(cond_fn, body_fn, init)
        return out if len(node.outputs) > 1 else out[0]

    def _exec_cond(self, node, args):
        """``then(*operands) if pred else orelse(*operands)`` via
        lax.cond (differentiable)."""
        then_sd, else_sd = node.attrs["then"], node.attrs["orelse"]
        pred = jnp.reshape(jnp.asarray(args[0]).astype(bool), ())
        operands = tuple(jnp.asarray(a) for a in args[1:])

        def mk(branch_sd):
            def fn(ops_):
                r = branch_sd.run_subgraph(list(ops_))
                return tuple(jnp.asarray(x) for x in r)
            return fn

        out = jax.lax.cond(pred, mk(then_sd), mk(else_sd), operands)
        return out if len(node.outputs) > 1 else out[0]

    def _needed_for(self, outputs: Sequence[str]) -> set:
        """Backward slice: op outputs required to compute `outputs`."""
        produced_by = {o: node for node in self.ops for o in node.outputs}
        needed, stack = set(), list(outputs)
        while stack:
            n = stack.pop()
            if n in needed:
                continue
            needed.add(n)
            node = produced_by.get(n)
            if node is not None:
                needed.update(node.outputs)
                stack.extend(node.inputs)
        return needed

    def _function(self, outputs: Sequence[str], feed_names: Sequence[str]):
        key = (tuple(outputs), tuple(sorted(feed_names)))
        if key in self._fn_cache:
            return self._fn_cache[key]
        needed = self._needed_for(outputs)

        def fn(params, feeds):
            env = self._run_graph(params, feeds, needed)
            missing = [o for o in outputs if o not in env]
            if missing:
                raise KeyError(f"Outputs not computed: {missing}")
            return [env[o] for o in outputs]

        jfn = jax.jit(fn)
        self._fn_cache[key] = jfn
        return jfn

    def _param_values(self) -> Dict[str, np.ndarray]:
        return {k: v for k, v in self.values.items()
                if self.vars[k].var_type == "VARIABLE"}

    def output(self, feeds: Dict[str, Any],
               outputs: Optional[Sequence[str]] = None) -> Dict[str, Any]:
        """Execute and fetch (DL4J ``SameDiff.output(Map, String...)``)."""
        feeds = {(k.name if isinstance(k, SDVariable) else k): jnp.asarray(v)
                 for k, v in feeds.items()}
        if outputs is None:
            all_outs = {o for n in self.ops for o in n.outputs}
            consumed = {i for n in self.ops for i in n.inputs}
            outputs = sorted(all_outs - consumed) or sorted(all_outs)
        outputs = [o.name if isinstance(o, SDVariable) else o for o in outputs]
        fn = self._function(outputs, feeds.keys())
        vals = fn(self._param_values(), feeds)
        return dict(zip(outputs, vals))

    # ------------------------------------------------------------------
    # Gradients (jax.grad over the traced loss — no gradient graph)
    # ------------------------------------------------------------------
    def _loss_fn(self, feeds_keys, l2=0.0, compute_dtype=None):
        losses = self.loss_variables
        if not losses:
            raise ValueError("set_loss_variables(...) first")
        needed = self._needed_for(losses)

        def fn(params, feeds):
            env = self._run_graph(params, feeds, needed,
                                  compute_dtype=compute_dtype)
            total = 0.0
            for name in losses:
                total = total + jnp.mean(
                    jnp.asarray(env[name], jnp.float32))
            if l2:
                for v in params.values():
                    total = total + 0.5 * l2 * jnp.sum(jnp.square(v))
            return total
        return fn

    def calculate_gradients(self, feeds: Dict[str, Any],
                            wrt: Optional[Sequence[str]] = None
                            ) -> Dict[str, np.ndarray]:
        feeds = {(k.name if isinstance(k, SDVariable) else k): jnp.asarray(v)
                 for k, v in feeds.items()}
        params = self._param_values()
        key = ("grad", tuple(self.loss_variables),
               tuple(sorted(feeds.keys())))
        if key not in self._fn_cache:
            self._fn_cache[key] = jax.jit(
                jax.grad(self._loss_fn(feeds.keys())))
        grads = self._fn_cache[key](params, feeds)
        if wrt is not None:
            wrt = [w.name if isinstance(w, SDVariable) else w for w in wrt]
            grads = {k: grads[k] for k in wrt}
        return grads

    # ------------------------------------------------------------------
    # Training (TrainingSession analogue: ONE jitted step)
    # ------------------------------------------------------------------
    def set_training_config(self, cfg: TrainingConfig):
        self.training_config = cfg

    def _check_trainable_loops(self):
        """Fail FAST (fit-time, not as a jax error at grad time) when a
        while_loop in the loss path cannot scan-convert.  Recurses into
        cond/while subgraphs: a loop nested inside a branch must not
        escape the check."""
        needed = self._needed_for(self.loss_variables)

        def check_sub(sub_sd):
            for n in sub_sd.ops:
                for key in ("cond", "body", "then", "orelse"):
                    child = n.attrs.get(key)
                    if isinstance(child, SameDiff):
                        check_sub(child)
                if n.op_name != "while_loop":
                    continue
                pat = sub_sd._while_static_pattern(n)
                # Inside a parent scan/while body every placeholder is
                # a TRACER at trace time, so a structurally-matching
                # loop whose counter init or bound flows in as loop
                # state still can't resolve a static trip count — it
                # would fall back to non-differentiable lax.while_loop
                # and die later with a raw JAX error (ADVICE r4).
                # Require both to resolve to subgraph CONSTANTs, the
                # exact condition under which the trip count is
                # static.  A ("state", j) bound is fine when the
                # while's j-th input is itself a constant of this
                # subgraph (a captured constant riding as loop state).
                def _is_const(name):
                    v = sub_sd.vars.get(
                        sub_sd._resolve_ident(sub_sd, name))
                    return v is not None and v.var_type == "CONSTANT"

                ok = pat is not None and (
                    pat[1][0] == "const"
                    or (pat[1][0] == "state"
                        and pat[1][1] < len(n.inputs)
                        and _is_const(n.inputs[pat[1][1]])))
                if ok:
                    ok = _is_const(n.inputs[pat[0]])
                if not ok:
                    raise ValueError(
                        f"nested while_loop producing {n.outputs[0]!r} "
                        "inside a control-flow subgraph on the loss "
                        "path is not scan-convertible (its counter "
                        "init and bound must be constants of the "
                        "nested graph); see the while_loop training "
                        "requirements.")

        for node in self.ops:
            if not any(o in needed for o in node.outputs):
                continue
            for key in ("cond", "body", "then", "orelse"):
                child = node.attrs.get(key)
                if isinstance(child, SameDiff):
                    check_sub(child)
            if node.op_name != "while_loop":
                continue
            pat = self._while_static_pattern(node)
            ok = pat is not None
            if ok:
                k, bound = pat

                def _is_const(name):
                    v = self.vars.get(name)
                    return v is not None and v.var_type == "CONSTANT"
                ok = _is_const(node.inputs[k]) and (
                    bound[0] == "const" or _is_const(
                        node.inputs[bound[1]]))
            if not ok:
                raise ValueError(
                    f"while_loop producing {node.outputs[0]!r} is in "
                    "the loss path but is not scan-convertible: "
                    "training needs `cond = (i < N)` with a constant "
                    "bound, a body that increments i by 1, and a "
                    "constant initial counter (XLA while is not "
                    "reverse-differentiable).  Inference via output() "
                    "still works; restructure the loop or freeze this "
                    "subgraph to fine-tune the rest.")

    def _train_step_fn(self, feed_names):
        cfg = self.training_config
        updater = cfg.resolved_updater()
        self._check_trainable_loops()
        loss_fn = self._loss_fn(feed_names, l2=cfg.l2,
                                compute_dtype=cfg.compute_dtype)

        def step(params, opt_state, step_idx, feeds):
            loss, grads = jax.value_and_grad(loss_fn)(params, feeds)
            updates, opt_state = updater.update(grads, opt_state, params,
                                                step_idx)
            params = jax.tree_util.tree_map(lambda p, u: p - u, params,
                                            updates)
            opt_state = updater.finalize(opt_state, params)
            return params, opt_state, loss

        return jax.jit(step, donate_argnums=(0, 1)), updater

    def fit(self, data, n_epochs: int = 1):
        """Train from a DataSet/MultiDataSet iterator using the configured
        feature/label placeholder mappings (DL4J ``SameDiff.fit``)."""
        cfg = self.training_config
        if cfg is None:
            raise ValueError("set_training_config(...) first")
        feat_names = list(cfg.data_set_feature_mapping)
        lab_names = list(cfg.data_set_label_mapping)
        step_fn, updater = self._train_step_fn(feat_names + lab_names)
        params = {k: jnp.asarray(v) for k, v in self._param_values().items()}
        if self._updater_state is None:
            self._updater_state = updater.init_state(params)
        losses = []
        iterator = data if hasattr(data, "__iter__") else [data]
        for _ in range(n_epochs):
            for ds in iterator:
                feats = ds.features if isinstance(ds.features, (list, tuple)) \
                    else [ds.features]
                labs = ds.labels if isinstance(ds.labels, (list, tuple)) \
                    else [ds.labels]
                feeds = {n: jnp.asarray(a)
                         for n, a in zip(feat_names + lab_names,
                                         list(feats) + list(labs))}
                params, self._updater_state, loss = step_fn(
                    params, self._updater_state,
                    jnp.asarray(self._step, jnp.int32), feeds)
                self._step += 1
                losses.append(float(loss))
            if hasattr(data, "reset"):
                data.reset()
        for k, v in params.items():
            self.values[k] = np.asarray(v)
        return losses

    # ------------------------------------------------------------------
    # Serialization (zip: graph.json + values.npz — the .fb analogue)
    # ------------------------------------------------------------------
    def to_dict(self) -> dict:
        return {
            "format": "deeplearning4j_tpu/samediff-v1",
            "variables": [
                {"name": v.name, "type": v.var_type,
                 "shape": list(v.shape) if v.shape is not None else None,
                 "dtype": v.dtype}
                for v in self.vars.values()],
            "ops": [n.to_dict() for n in self.ops],
            "loss_variables": self.loss_variables,
            "outputs": self.outputs,
        }

    def to_portable_dict(self) -> dict:
        """Self-contained dict INCLUDING values (JSON-safe) — how
        control-flow subgraphs embed in their parent's attrs.  Values
        ride as base64 npz bytes, not number lists: an imported loop
        body can capture weight-sized constants, and tolist() would
        blow the checkpoint JSON up ~10x per float."""
        import base64
        d = self.to_dict()
        if self.values:
            buf = io.BytesIO()
            np.savez_compressed(buf, **self.values)
            d["values_npz_b64"] = base64.b64encode(
                buf.getvalue()).decode("ascii")
        return d

    @staticmethod
    def from_portable_dict(d: dict) -> "SameDiff":
        import base64
        sd = SameDiff()
        for v in d["variables"]:
            sd._register(v["name"], v["type"], v["shape"], v["dtype"])
        for n in d["ops"]:
            sd.ops.append(OpNode(
                n["op"], n["inputs"], n["outputs"],
                {k: _revive_attr(v) for k, v in n["attrs"].items()}))
        sd.loss_variables = d.get("loss_variables", [])
        sd.outputs = d.get("outputs")
        if "values_npz_b64" in d:
            vals = np.load(io.BytesIO(
                base64.b64decode(d["values_npz_b64"])), allow_pickle=False)
            for k in vals.files:
                sd.values[k] = vals[k]
        for k, meta in d.get("values_inline", {}).items():  # legacy form
            sd.values[k] = np.asarray(
                meta["data"], dtype=meta["dtype"]).reshape(meta["shape"])
        return sd

    def save(self, path: str):
        buf = io.BytesIO()
        np.savez(buf, **self.values)
        with zipfile.ZipFile(path, "w", zipfile.ZIP_DEFLATED) as z:
            z.writestr("graph.json", json.dumps(self.to_dict(), indent=1))
            z.writestr("values.npz", buf.getvalue())

    @staticmethod
    def load(path: str) -> "SameDiff":
        sd = SameDiff()
        with zipfile.ZipFile(path) as z:
            d = json.loads(z.read("graph.json"))
            vals = np.load(io.BytesIO(z.read("values.npz")))
            for v in d["variables"]:
                sd._register(v["name"], v["type"], v["shape"], v["dtype"])
            for n in d["ops"]:
                sd.ops.append(OpNode(
                    n["op"], n["inputs"], n["outputs"],
                    {k: _revive_attr(v) for k, v in n["attrs"].items()}))
            sd.loss_variables = d.get("loss_variables", [])
            sd.outputs = d.get("outputs")
            for k in vals.files:
                sd.values[k] = vals[k]
        return sd

    def summary(self) -> str:
        lines = [f"SameDiff: {len(self.vars)} vars, {len(self.ops)} ops"]
        for v in self.vars.values():
            if v.var_type != "ARRAY":
                lines.append(f"  {v.var_type:<11} {v.name} {v.shape}")
        counts: Dict[str, int] = {}
        for n in self.ops:
            counts[n.op_name] = counts.get(n.op_name, 0) + 1
        lines.append("  ops: " + ", ".join(
            f"{k}x{c}" for k, c in sorted(counts.items())))
        return "\n".join(lines)
