"""Op registry for the graph IR.

The analogue of libnd4j's ``DeclarableOp``/``OpRegistrator`` (~500 named
ops, reference ``libnd4j/include/ops/declarable/**``) and the JVM op
classes (``org.nd4j.linalg.api.ops.**``) — except every op here is a thin
jax/lax lowering, so "registering an op" is one function, not a C++ kernel
pair plus shape function plus JavaCPP binding.

Static/constant folding: ops whose inputs are all host values (numpy
arrays, ints) execute with numpy at TRACE time.  This is how TF graphs'
shape-metaprogramming subgraphs (Shape → StridedSlice → Pack → Reshape)
become static under jit: ``shape`` always returns a host np.int64 vector
(XLA shapes are static), and everything derived from it stays host-side,
so Reshape/Tile/etc. see concrete targets — compiler-friendly control
flow with no data-dependent shapes.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


@dataclasses.dataclass
class OpDef:
    name: str
    fn: Callable  # fn(*inputs, **attrs) -> output or tuple of outputs
    n_out: int = 1  # 0 = variable output count; caller must pass n_out


OP_REGISTRY: Dict[str, OpDef] = {}


def register_op(name: str, n_out: int = 1):
    def deco(fn):
        OP_REGISTRY[name] = OpDef(name=name, fn=fn, n_out=n_out)
        return fn
    return deco


def get_op(name: str) -> OpDef:
    op = OP_REGISTRY.get(name)
    if op is None:
        raise KeyError(
            f"Unknown op {name!r}; registered: {sorted(OP_REGISTRY)}")
    return op


def is_static_value(v) -> bool:
    """True when `v` is a host value (safe to constant-fold with numpy)."""
    return isinstance(v, (int, float, bool, np.ndarray, np.generic, list,
                          tuple))


def _xp(*args):
    """numpy when all inputs are host values (constant folding), else jnp."""
    return np if all(is_static_value(a) for a in args) else jnp


# ---------------------------------------------------------------------------
# Elementwise binary (broadcasting)
# ---------------------------------------------------------------------------
for _name, _f in [
    ("add", lambda m: m.add), ("sub", lambda m: m.subtract),
    ("mul", lambda m: m.multiply), ("div", lambda m: m.divide),
    ("floordiv", lambda m: m.floor_divide), ("mod", lambda m: m.mod),
    ("pow", lambda m: m.power), ("maximum", lambda m: m.maximum),
    ("minimum", lambda m: m.minimum),
    ("squared_difference", lambda m: (lambda a, b: m.square(a - b))),
]:
    def _make(f):
        def impl(a, b):
            m = _xp(a, b)
            return f(m)(a, b)
        return impl
    register_op(_name)(_make(_f))

for _name, _f in [
    ("equal", lambda m: m.equal), ("not_equal", lambda m: m.not_equal),
    ("greater", lambda m: m.greater), ("less", lambda m: m.less),
    ("greater_equal", lambda m: m.greater_equal),
    ("less_equal", lambda m: m.less_equal),
    ("logical_and", lambda m: m.logical_and),
    ("logical_or", lambda m: m.logical_or),
]:
    def _make_cmp(f):
        def impl(a, b):
            m = _xp(a, b)
            return f(m)(a, b)
        return impl
    register_op(_name)(_make_cmp(_f))


# ---------------------------------------------------------------------------
# Elementwise unary
# ---------------------------------------------------------------------------
for _name, _jf in [
    ("neg", jnp.negative), ("abs", jnp.abs), ("sign", jnp.sign),
    ("exp", jnp.exp), ("log", jnp.log), ("log1p", jnp.log1p),
    ("sqrt", jnp.sqrt), ("rsqrt", lambda x: lax.rsqrt(x)),
    ("square", jnp.square), ("reciprocal", jnp.reciprocal),
    ("floor", jnp.floor), ("ceil", jnp.ceil), ("round", jnp.round),
    ("sin", jnp.sin), ("cos", jnp.cos), ("tan", jnp.tan),
    ("tanh", jnp.tanh), ("sigmoid", jax.nn.sigmoid), ("erf", lax.erf),
    ("relu", jax.nn.relu), ("relu6", jax.nn.relu6), ("elu", jax.nn.elu),
    ("selu", jax.nn.selu), ("softplus", jax.nn.softplus),
    ("softsign", jax.nn.soft_sign), ("logical_not", jnp.logical_not),
    ("isnan", jnp.isnan), ("isinf", jnp.isinf),
]:
    register_op(_name)(lambda x, _f=_jf: _f(x))

register_op("identity")(lambda x: x)
register_op("stop_gradient")(lambda x: x if is_static_value(x)
                             else lax.stop_gradient(x))
register_op("erfc")(lambda x: lax.erfc(x))
register_op("leaky_relu")(lambda x, alpha=0.2: jax.nn.leaky_relu(x, alpha))
register_op("gelu")(lambda x, approximate=True: jax.nn.gelu(x, approximate=approximate))
register_op("clip_by_value")(lambda x, lo, hi: jnp.clip(x, lo, hi))
register_op("cast")(lambda x, dtype: (np.asarray(x).astype(dtype)
                                      if is_static_value(x)
                                      else x.astype(dtype)))


# ---------------------------------------------------------------------------
# Matmul family — the MXU path
# ---------------------------------------------------------------------------
@register_op("matmul")
def _matmul(a, b, transpose_a=False, transpose_b=False, expect_k=None):
    """2-D+ matmul (``Mmul``/TF MatMul/BatchMatMulV2 in one: jnp batches).

    ``expect_k`` is set by ``rewrites.fold_flatten_reshapes``, which
    removed a flattening reshape on ``a``: when the contraction axis is
    already innermost (every TF Tensordot over the last axis) the
    operand rides through rank-3 untouched and jnp batches the dot; in
    any other case re-applying the flatten here reproduces the dropped
    reshape exactly, so the fold is semantics-identical either way."""
    if expect_k is not None and a.shape[-1] != expect_k:
        a = jnp.reshape(a, (-1, expect_k))
    if transpose_a:
        a = jnp.swapaxes(a, -1, -2)
    if transpose_b:
        b = jnp.swapaxes(b, -1, -2)
    return jnp.matmul(a, b)


@register_op("tensordot")
def _tensordot(a, b, axes=2):
    return jnp.tensordot(a, b, axes=axes)


@register_op("bias_add")
def _bias_add(x, b):
    return x + b


# ---------------------------------------------------------------------------
# Reductions
# ---------------------------------------------------------------------------
def _norm_axis(axis):
    if axis is None:
        return None
    if isinstance(axis, (np.ndarray, list, tuple)):
        seq = np.asarray(axis).reshape(-1).tolist()
        return tuple(int(a) for a in seq)
    return int(axis)


for _name, _f in [("reduce_sum", "sum"), ("reduce_mean", "mean"),
                  ("reduce_max", "max"), ("reduce_min", "min"),
                  ("reduce_prod", "prod"), ("reduce_any", "any"),
                  ("reduce_all", "all")]:
    def _make_red(fname):
        def impl(x, axis=None, keep_dims=False):
            m = _xp(x)
            return getattr(m, fname)(x, axis=_norm_axis(axis),
                                     keepdims=bool(keep_dims))
        return impl
    register_op(_name)(_make_red(_f))

register_op("argmax")(lambda x, axis=-1: jnp.argmax(x, axis=_norm_axis(axis)))
register_op("argmin")(lambda x, axis=-1: jnp.argmin(x, axis=_norm_axis(axis)))
@register_op("cumsum")
def _cumsum(x, axis=0, exclusive=False, reverse=False):
    axis = int(axis)
    if reverse:
        x = jnp.flip(x, axis)
    out = jnp.cumsum(x, axis=axis)
    if exclusive:
        out = jnp.concatenate(
            [jnp.zeros_like(lax.slice_in_dim(out, 0, 1, axis=axis)),
             lax.slice_in_dim(out, 0, out.shape[axis] - 1, axis=axis)],
            axis=axis)
    if reverse:
        out = jnp.flip(out, axis)
    return out


# ---------------------------------------------------------------------------
# Shape metaprogramming (static: constant-folds at trace time)
# ---------------------------------------------------------------------------
@register_op("shape")
def _shape(x):
    """XLA shapes are static — return a HOST vector so downstream
    Pack/StridedSlice/Reshape stay constant under jit (the TF-import
    equivalent of SameDiff's shape functions)."""
    return np.asarray(np.shape(x) if is_static_value(x) else x.shape,
                      dtype=np.int64)


@register_op("size")
def _size(x):
    return np.int64(np.prod(np.shape(x) if is_static_value(x) else x.shape))


@register_op("rank")
def _rank(x):
    return np.int64(len(np.shape(x) if is_static_value(x) else x.shape))


@register_op("reshape")
def _reshape(x, shape):
    shape = tuple(int(s) for s in np.asarray(shape).reshape(-1))
    m = _xp(x)
    return m.reshape(x, shape)


@register_op("transpose")
def _transpose(x, perm=None):
    if perm is not None:
        perm = tuple(int(p) for p in np.asarray(perm).reshape(-1))
    m = _xp(x)
    return m.transpose(x, perm)


@register_op("expand_dims")
def _expand_dims(x, axis=0):
    return _xp(x).expand_dims(x, int(axis))


@register_op("squeeze")
def _squeeze(x, axis=None):
    ax = _norm_axis(axis)
    return _xp(x).squeeze(x, axis=ax)


@register_op("concat")
def _concat(*xs, axis=0):
    return _xp(*xs).concatenate(xs, axis=int(axis))


@register_op("pack")
def _pack(*xs, axis=0):
    return _xp(*xs).stack(xs, axis=int(axis))


@register_op("unstack", n_out=0)  # variable out count, resolved at build
def _unstack(x, axis=0, num=None):
    axis = int(axis)
    n = num or x.shape[axis]
    return tuple(jnp.squeeze(s, axis)
                 for s in jnp.split(x, int(n), axis=axis))


@register_op("split", n_out=0)
def _split(x, num_split, axis=0):
    """Equal split (int) or explicit section sizes (list — ONNX
    Split's ``split`` attr / opset-13 sizes input)."""
    if isinstance(num_split, (list, tuple, np.ndarray)):
        sizes = [int(v) for v in np.asarray(num_split).reshape(-1)]
        bounds = np.cumsum(sizes)[:-1].tolist()
        return tuple(jnp.split(x, bounds, axis=int(axis)))
    return tuple(jnp.split(x, int(num_split), axis=int(axis)))


@register_op("tile")
def _tile(x, multiples):
    multiples = tuple(int(m) for m in np.asarray(multiples).reshape(-1))
    return _xp(x).tile(x, multiples)


@register_op("slice")
def _slice(x, begin, size):
    begin = [int(b) for b in np.asarray(begin).reshape(-1)]
    size = [int(s) for s in np.asarray(size).reshape(-1)]
    idx = tuple(slice(b, None if s == -1 else b + s)
                for b, s in zip(begin, size))
    return x[idx]


@register_op("strided_slice")
def _strided_slice(x, begin, end, strides=None, begin_mask=0, end_mask=0,
                   ellipsis_mask=0, new_axis_mask=0, shrink_axis_mask=0):
    """Full TF StridedSlice semantics: begin/end/shrink masks plus
    new-axis (None) and ellipsis positions."""
    begin = [int(b) for b in np.asarray(begin).reshape(-1)]
    end = [int(e) for e in np.asarray(end).reshape(-1)]
    strides = ([int(s) for s in np.asarray(strides).reshape(-1)]
               if strides is not None else [1] * len(begin))
    idx = []
    for i in range(len(begin)):
        if (new_axis_mask >> i) & 1:
            idx.append(None)
            continue
        if (ellipsis_mask >> i) & 1:
            idx.append(Ellipsis)
            continue
        b = None if (begin_mask >> i) & 1 else begin[i]
        e = None if (end_mask >> i) & 1 else end[i]
        if (shrink_axis_mask >> i) & 1:
            idx.append(begin[i])
        else:
            idx.append(slice(b, e, strides[i]))
    return x[tuple(idx)]


@register_op("gather")
def _gather(params, indices, axis=0, batch_dims=0):
    axis, batch_dims = int(axis), int(batch_dims)
    if batch_dims == 0:
        m = _xp(params, indices)
        return m.take(params, np.asarray(indices) if m is np else indices,
                      axis=axis)
    # TF GatherV2 batch_dims semantics: the first `batch_dims` axes of
    # params and indices are matched pairwise; `axis` counts in the FULL
    # params rank.  vmap over each batch axis, gathering on the residual.
    fn = lambda p, i: jnp.take(p, i, axis=axis - batch_dims)
    for _ in range(batch_dims):
        fn = jax.vmap(fn)
    return fn(jnp.asarray(params), jnp.asarray(indices))


@register_op("gather_nd")
def _gather_nd(params, indices):
    idx = tuple(jnp.moveaxis(indices, -1, 0))
    return params[idx]


@register_op("scatter_nd")
def _scatter_nd(indices, updates, shape):
    shape = tuple(int(s) for s in np.asarray(shape).reshape(-1))
    z = jnp.zeros(shape, updates.dtype)
    idx = tuple(jnp.moveaxis(indices, -1, 0))
    return z.at[idx].add(updates)


@register_op("one_hot")
def _one_hot(indices, depth, on_value=1.0, off_value=0.0, axis=-1,
             dtype="float32"):
    oh = jax.nn.one_hot(indices, int(depth), axis=int(axis), dtype=dtype)
    if on_value != 1.0 or off_value != 0.0:
        oh = oh * (on_value - off_value) + off_value
    return oh


@register_op("fill")
def _fill(shape, value):
    shape = tuple(int(s) for s in np.asarray(shape).reshape(-1))
    if is_static_value(value):
        return np.full(shape, value)
    return jnp.full(shape, value)


@register_op("zeros_like")
def _zeros_like(x):
    return _xp(x).zeros_like(x)


@register_op("ones_like")
def _ones_like(x):
    return _xp(x).ones_like(x)


@register_op("range")
def _range(start, limit, delta=1):
    return np.arange(int(start), int(limit), int(delta))


@register_op("pad")
def _pad(x, paddings, constant_value=0.0):
    pads = [tuple(int(v) for v in row)
            for row in np.asarray(paddings).reshape(-1, 2)]
    return jnp.pad(x, pads, constant_values=constant_value)


@register_op("broadcast_to")
def _broadcast_to(x, shape):
    shape = tuple(int(s) for s in np.asarray(shape).reshape(-1))
    return _xp(x).broadcast_to(x, shape)


@register_op("where")
def _where(cond, a, b):
    return _xp(cond, a, b).where(cond, a, b)


@register_op("select")
def _select(cond, a, b):
    return _xp(cond, a, b).where(cond, a, b)


# ---------------------------------------------------------------------------
# NN ops
# ---------------------------------------------------------------------------
@register_op("softmax")
def _softmax(x, axis=-1):
    return jax.nn.softmax(x, axis=int(axis))


@register_op("log_softmax")
def _log_softmax(x, axis=-1):
    return jax.nn.log_softmax(x, axis=int(axis))


@register_op("softmax_cross_entropy_with_logits")
def _sce(labels, logits):
    return -jnp.sum(labels * jax.nn.log_softmax(logits, -1), axis=-1)


@register_op("sparse_softmax_cross_entropy_with_logits")
def _ssce(labels, logits):
    lp = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(
        lp, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]


@register_op("sigmoid_cross_entropy_with_logits")
def _bce(labels, logits):
    return jnp.maximum(logits, 0) - logits * labels + jnp.log1p(
        jnp.exp(-jnp.abs(logits)))


@register_op("layer_norm")
def _layer_norm(x, gamma, beta, axis=-1, eps=1e-12):
    mean = jnp.mean(x, axis=axis, keepdims=True)
    var = jnp.var(x, axis=axis, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * gamma + beta


@register_op("dropout")
def _dropout(x, rate=0.0):
    # Inference graphs import dropout as identity (the TF graph freezes
    # keep_prob=1); training uses the framework's own dropout plumbing.
    return x


@register_op("l2_normalize")
def _l2_normalize(x, axis=-1, eps=1e-12):
    return x * lax.rsqrt(jnp.maximum(
        jnp.sum(jnp.square(x), axis=axis, keepdims=True), eps))


@register_op("embedding_lookup")
def _embedding_lookup(table, ids):
    return jnp.take(table, ids, axis=0)


@register_op("conv2d")
def _conv2d(x, w, strides=(1, 1), padding="SAME", dilations=(1, 1)):
    if isinstance(padding, (bytes, str)):
        pad = padding.decode() if isinstance(padding, bytes) else padding
    else:
        pad = [tuple(p) for p in padding]
    return lax.conv_general_dilated(
        x, w, window_strides=tuple(int(s) for s in strides), padding=pad,
        rhs_dilation=tuple(int(d) for d in dilations),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


@register_op("max_pool")
def _max_pool(x, ksize=(2, 2), strides=(2, 2), padding="VALID"):
    k, s = tuple(int(v) for v in ksize), tuple(int(v) for v in strides)
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, *k, 1), (1, *s, 1),
                             padding)


# ---------------------------------------------------------------------------
# Control flow — registered for build-time lookup; EXECUTION is handled
# by SameDiff._run_graph (_exec_while/_exec_cond lowering to jax.lax),
# because these ops carry whole subgraphs in their attrs.
# ---------------------------------------------------------------------------
@register_op("while_loop", n_out=0)
def _while_loop_stub(*args, **attrs):
    raise RuntimeError(
        "while_loop executes via SameDiff._exec_while, not the registry")


@register_op("cond", n_out=0)
def _cond_stub(*args, **attrs):
    raise RuntimeError(
        "cond executes via SameDiff._exec_cond, not the registry")


@register_op("fused_attention")
def _fused_attention(q, k, v, bias=None, causal=False, scale=None,
                     compute_dtype=None, bias_layout=None):
    """softmax(QK^T*scale + bias)V in one node — the lowering target of
    the importer's attention-subgraph rewrite (``autodiff/rewrites.py``).
    Routes to the Pallas flash kernel when shape/mask permit, else to
    XLA einsums.  ``compute_dtype='bfloat16'`` runs the attention math
    at full MXU rate (the TPU training configuration); output returns
    in the input dtype either way."""
    from deeplearning4j_tpu.kernels.flash_attention import attention
    q, k, v = jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)
    out_dtype = q.dtype
    if compute_dtype is not None:
        cd = jnp.dtype(compute_dtype)
        q, k, v = q.astype(cd), k.astype(cd), v.astype(cd)
    squeeze_head = q.ndim == 3
    if squeeze_head:   # [b, t, d] -> single-head [b, 1, t, d]
        q, k, v = q[:, None], k[:, None], v[:, None]
    if bias is not None:
        bias = jnp.asarray(bias)
        if bias_layout == "qk" and bias.ndim == 2:
            # declared square [tq, tk] attention bias (the kept causal
            # mask): lift to [1, 1, tq, tk] — the kernel's bare-2-D
            # convention is a [b, tk] padding mask, ambiguous with this
            bias = bias[None, None]
    out = attention(q, k, v, bias=bias,
                    causal=bool(causal),
                    scale=None if scale is None else float(scale))
    if squeeze_head:
        out = out[:, 0]
    return out.astype(out_dtype)


@register_op("avg_pool")
def _avg_pool(x, ksize=(2, 2), strides=(2, 2), padding="VALID"):
    k, s = tuple(int(v) for v in ksize), tuple(int(v) for v in strides)
    summed = lax.reduce_window(x, 0.0, lax.add, (1, *k, 1), (1, *s, 1),
                               padding)
    ones = jnp.ones(x.shape[1:3] + (1,), x.dtype)[None]
    counts = lax.reduce_window(ones, 0.0, lax.add, (1, *k, 1), (1, *s, 1),
                               padding)
    return summed / counts


# ---------------------------------------------------------------------------
# Round-3 registry breadth (round-2 review weak item 8: each import target
# hits the op wall — grow toward the reference's ~500 declarable ops).
# Elementwise extensions
# ---------------------------------------------------------------------------
for _name, _jf in [
    ("asin", jnp.arcsin), ("acos", jnp.arccos), ("atan", jnp.arctan),
    ("sinh", jnp.sinh), ("cosh", jnp.cosh), ("asinh", jnp.arcsinh),
    ("acosh", jnp.arccosh), ("atanh", jnp.arctanh),
    ("expm1", jnp.expm1), ("rint", jnp.rint),
    ("isfinite", jnp.isfinite),
    ("lgamma", lambda x: lax.lgamma(x)),
    ("digamma", lambda x: lax.digamma(x)),
]:
    register_op(_name)(lambda x, _f=_jf: _f(x))

register_op("atan2")(lambda y, x: jnp.arctan2(y, x))
register_op("xlogy")(lambda x, y: jnp.where(
    x == 0.0, jnp.zeros_like(x), x * jnp.log(y)))
register_op("xdivy")(lambda x, y: jnp.where(
    x == 0.0, jnp.zeros_like(x), x / y))
register_op("logical_xor")(lambda a, b: jnp.logical_xor(a, b))
register_op("l2_loss")(lambda x: jnp.sum(jnp.square(x)) / 2.0)


@register_op("add_n")
def _add_n(*xs):
    out = xs[0]
    for x in xs[1:]:
        out = out + x
    return out


# ---------------------------------------------------------------------------
# Array manipulation
# ---------------------------------------------------------------------------
@register_op("reverse")
def _reverse(x, axis):
    ax = tuple(int(a) for a in np.asarray(axis).reshape(-1))
    return jnp.flip(x, ax)


@register_op("roll")
def _roll(x, shift, axis):
    sh = [int(s) for s in np.asarray(shift).reshape(-1)]
    ax = [int(a) for a in np.asarray(axis).reshape(-1)]
    return jnp.roll(x, sh, ax)


@register_op("top_k", n_out=2)
def _top_k(x, k=1, sorted=True):
    v, i = lax.top_k(x, int(k))
    return v, i.astype(jnp.int32)


@register_op("invert_permutation")
def _invert_permutation(p):
    p = jnp.asarray(p)
    return jnp.zeros_like(p).at[p].set(
        jnp.arange(p.shape[0], dtype=p.dtype))


@register_op("matrix_band_part")
def _matrix_band_part(x, lower, upper):
    lower, upper = int(np.asarray(lower)), int(np.asarray(upper))
    m, n = x.shape[-2], x.shape[-1]
    rows = lax.broadcasted_iota(jnp.int32, (m, n), 0)
    cols = lax.broadcasted_iota(jnp.int32, (m, n), 1)
    keep = jnp.ones((m, n), bool)
    if lower >= 0:
        keep &= (rows - cols) <= lower
    if upper >= 0:
        keep &= (cols - rows) <= upper
    return jnp.where(keep, x, jnp.zeros((), x.dtype))


@register_op("mirror_pad")
def _mirror_pad(x, paddings, mode="REFLECT"):
    pads = [tuple(int(v) for v in row)
            for row in np.asarray(paddings).reshape(-1, 2)]
    m = str(mode).upper()
    return jnp.pad(x, pads,
                   mode="reflect" if m == "REFLECT" else "symmetric")


@register_op("cumprod")
def _cumprod(x, axis=0, exclusive=False, reverse=False):
    axis = int(axis)
    if reverse:
        x = jnp.flip(x, axis)
    out = jnp.cumprod(x, axis=axis)
    if exclusive:
        out = jnp.concatenate(
            [jnp.ones_like(lax.slice_in_dim(out, 0, 1, axis=axis)),
             lax.slice_in_dim(out, 0, out.shape[axis] - 1, axis=axis)],
            axis=axis)
    if reverse:
        out = jnp.flip(out, axis)
    return out


@register_op("tensor_scatter_update")
def _tensor_scatter_update(x, indices, updates):
    idx = tuple(jnp.moveaxis(jnp.asarray(indices), -1, 0))
    return jnp.asarray(x).at[idx].set(updates)


@register_op("tensor_scatter_add")
def _tensor_scatter_add(x, indices, updates):
    idx = tuple(jnp.moveaxis(jnp.asarray(indices), -1, 0))
    return jnp.asarray(x).at[idx].add(updates)


@register_op("depth_to_space")
def _depth_to_space(x, block_size=2):
    b = int(block_size)
    n, h, w, c = x.shape
    x = x.reshape(n, h, w, b, b, c // (b * b))
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(n, h * b, w * b, c // (b * b))


@register_op("space_to_depth")
def _space_to_depth(x, block_size=2):
    b = int(block_size)
    n, h, w, c = x.shape
    x = x.reshape(n, h // b, b, w // b, b, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(n, h // b, w // b, c * b * b)


@register_op("space_to_batch_nd")
def _space_to_batch_nd(x, block_shape, paddings):
    bs = [int(v) for v in np.asarray(block_shape).reshape(-1)]
    pads = [(0, 0)] + [tuple(int(v) for v in row) for row in
                       np.asarray(paddings).reshape(-1, 2)]
    pads += [(0, 0)] * (x.ndim - len(pads))
    x = jnp.pad(x, pads)
    n = x.shape[0]
    spatial = x.shape[1:1 + len(bs)]
    rest = x.shape[1 + len(bs):]
    shape = [n]
    for s, b in zip(spatial, bs):
        shape += [s // b, b]
    x = x.reshape(shape + list(rest))
    # [n, s1/b1, b1, s2/b2, b2, ...] -> [b1, b2, ..., n, s1/b1, ...]
    perm = ([2 * i + 2 for i in range(len(bs))] + [0]
            + [2 * i + 1 for i in range(len(bs))]
            + list(range(1 + 2 * len(bs), x.ndim)))
    x = x.transpose(perm)
    out_n = n * int(np.prod(bs))
    return x.reshape([out_n] + [s // b for s, b in zip(spatial, bs)]
                     + list(rest))


@register_op("batch_to_space_nd")
def _batch_to_space_nd(x, block_shape, crops):
    bs = [int(v) for v in np.asarray(block_shape).reshape(-1)]
    cr = [tuple(int(v) for v in row) for row in
          np.asarray(crops).reshape(-1, 2)]
    n = x.shape[0]
    spatial = x.shape[1:1 + len(bs)]
    rest = x.shape[1 + len(bs):]
    base_n = n // int(np.prod(bs))
    x = x.reshape(bs + [base_n] + list(spatial) + list(rest))
    # [b1, b2, n, s1, s2, ...] -> [n, s1, b1, s2, b2, ...]
    perm = [len(bs)]
    for i in range(len(bs)):
        perm += [len(bs) + 1 + i, i]
    perm += list(range(1 + 2 * len(bs), x.ndim))
    x = x.transpose(perm)
    x = x.reshape([base_n] + [s * b for s, b in zip(spatial, bs)]
                  + list(rest))
    idx = [slice(None)]
    for (lo, hi), s, b in zip(cr, spatial, bs):
        idx.append(slice(lo, s * b - hi))
    return x[tuple(idx)]


def _legacy_axis_coords(out_n: int, in_n: int):
    """TF half_pixel_centers=False sampling: src = i * (in/out)."""
    return jnp.arange(out_n, dtype=jnp.float32) * (in_n / out_n)


@register_op("resize_bilinear")
def _resize_bilinear(x, size, half_pixel_centers=True):
    h, w = (int(s) for s in np.asarray(size).reshape(-1))
    if half_pixel_centers:
        return jax.image.resize(x, (x.shape[0], h, w, x.shape[3]),
                                method="bilinear")
    # legacy TF sampling (attr default!): corner-anchored coordinates
    def interp(arr, coords, axis):
        i0 = jnp.floor(coords).astype(jnp.int32)
        i1 = jnp.minimum(i0 + 1, arr.shape[axis] - 1)
        shape = [1] * arr.ndim
        shape[axis] = coords.shape[0]
        frac = (coords - i0).reshape(shape)
        a0 = jnp.take(arr, i0, axis=axis)
        a1 = jnp.take(arr, i1, axis=axis)
        return a0 + (a1 - a0) * frac

    y = interp(x, _legacy_axis_coords(h, x.shape[1]), 1)
    return interp(y, _legacy_axis_coords(w, x.shape[2]), 2)


@register_op("resize_nearest")
def _resize_nearest(x, size, half_pixel_centers=True):
    h, w = (int(s) for s in np.asarray(size).reshape(-1))
    if half_pixel_centers:
        return jax.image.resize(x, (x.shape[0], h, w, x.shape[3]),
                                method="nearest")
    iy = jnp.floor(_legacy_axis_coords(h, x.shape[1])).astype(jnp.int32)
    ix = jnp.floor(_legacy_axis_coords(w, x.shape[2])).astype(jnp.int32)
    return jnp.take(jnp.take(x, iy, axis=1), ix, axis=2)


# ---------------------------------------------------------------------------
# Segment reductions (embedding-gradient graphs)
# ---------------------------------------------------------------------------
@register_op("unsorted_segment_sum")
def _unsorted_segment_sum(data, segment_ids, num_segments):
    return jax.ops.segment_sum(
        jnp.asarray(data), jnp.asarray(segment_ids).astype(jnp.int32),
        int(np.asarray(num_segments)))


@register_op("unsorted_segment_mean")
def _unsorted_segment_mean(data, segment_ids, num_segments):
    n = int(np.asarray(num_segments))
    ids = jnp.asarray(segment_ids).astype(jnp.int32)
    s = jax.ops.segment_sum(jnp.asarray(data), ids, n)
    cnt = jax.ops.segment_sum(jnp.ones(ids.shape, s.dtype), ids, n)
    return s / jnp.maximum(cnt.reshape(cnt.shape + (1,) *
                                       (s.ndim - cnt.ndim)), 1.0)


@register_op("unsorted_segment_max")
def _unsorted_segment_max(data, segment_ids, num_segments):
    return jax.ops.segment_max(
        jnp.asarray(data), jnp.asarray(segment_ids).astype(jnp.int32),
        int(np.asarray(num_segments)))


# ---------------------------------------------------------------------------
# NN extensions
# ---------------------------------------------------------------------------
@register_op("conv2d_transpose")
def _conv2d_transpose(dy, w, strides=(1, 1), padding="SAME",
                      output_shape=None):
    """TF Conv2DBackpropInput semantics (the op behind
    tf.nn.conv2d_transpose): the gradient of conv2d wrt its input.

    ``output_shape`` (the op's input_sizes operand) disambiguates odd
    input sizes under SAME/stride>1 — lax.conv_transpose alone always
    reconstructs in*stride, which is wrong for e.g. in=5, s=2 (out=3,
    5 != 6).  With it, the exact adjoint is computed: dy dilated by the
    stride, padded with (k-1-pad) on each side, correlated with the
    spatially-flipped, io-swapped kernel."""
    s = tuple(int(v) for v in strides)
    if output_shape is None:
        return lax.conv_transpose(
            dy, w, strides=s,
            padding=padding if isinstance(padding, str) else
            [tuple(p) for p in padding],
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            transpose_kernel=True)
    tgt = [int(v) for v in np.asarray(output_shape).reshape(-1)]
    in_h, in_w = tgt[1], tgt[2]
    kh, kw = w.shape[0], w.shape[1]
    pad = []
    for size, k, st, dn in ((in_h, kh, s[0], dy.shape[1]),
                            (in_w, kw, s[1], dy.shape[2])):
        if str(padding) == "SAME":
            o = -(-size // st)
            total = max((o - 1) * st + k - size, 0)
            plo = total // 2
        else:                       # VALID forward: no padding
            plo = 0
        dilated = (dn - 1) * st + 1
        lo = k - 1 - plo
        hi = size + k - 1 - dilated - lo
        pad.append((lo, hi))
    w_t = jnp.swapaxes(w[::-1, ::-1], 2, 3)   # flip HW, swap I<->O
    return lax.conv_general_dilated(
        dy, w_t, window_strides=(1, 1), padding=pad,
        lhs_dilation=s, dimension_numbers=("NHWC", "HWIO", "NHWC"))


@register_op("depthwise_conv2d")
def _depthwise_conv2d(x, w, strides=(1, 1), padding="SAME",
                      dilations=(1, 1)):
    h, ww, c, m = w.shape           # TF filter [H, W, C_in, mult]
    return lax.conv_general_dilated(
        x, w.reshape(h, ww, 1, c * m),
        window_strides=tuple(int(s) for s in strides),
        padding=padding,
        rhs_dilation=tuple(int(d) for d in dilations),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=c)


@register_op("conv3d")
def _conv3d(x, w, strides=(1, 1, 1), padding="SAME",
            dilations=(1, 1, 1)):
    return lax.conv_general_dilated(
        x, w, window_strides=tuple(int(s) for s in strides),
        padding=padding,
        rhs_dilation=tuple(int(d) for d in dilations),
        dimension_numbers=("NDHWC", "DHWIO", "NDHWC"))


@register_op("max_pool3d")
def _max_pool3d(x, ksize=(2, 2, 2), strides=(2, 2, 2), padding="VALID"):
    k = tuple(int(v) for v in ksize)
    s = tuple(int(v) for v in strides)
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, *k, 1),
                             (1, *s, 1), padding)


@register_op("avg_pool3d")
def _avg_pool3d(x, ksize=(2, 2, 2), strides=(2, 2, 2), padding="VALID"):
    k = tuple(int(v) for v in ksize)
    s = tuple(int(v) for v in strides)
    summed = lax.reduce_window(x, 0.0, lax.add, (1, *k, 1), (1, *s, 1),
                               padding)
    ones = jnp.ones(x.shape[1:4] + (1,), x.dtype)[None]
    counts = lax.reduce_window(ones, 0.0, lax.add, (1, *k, 1),
                               (1, *s, 1), padding)
    return summed / counts


@register_op("lrn")
def _lrn(x, depth_radius=5, bias=1.0, alpha=1.0, beta=0.5):
    r = int(depth_radius)
    sq = jnp.square(x)
    pads = [(0, 0)] * 3 + [(r, r)]
    acc = lax.reduce_window(sq, 0.0, lax.add, (1, 1, 1, 2 * r + 1),
                            (1, 1, 1, 1), pads)
    return x / jnp.power(bias + alpha * acc, beta)


@register_op("softmax_cross_entropy_with_logits_v2", n_out=2)
def _sce_v2(logits, labels):
    """TF's raw op: outputs (per-example loss, backprop = p - labels)."""
    lp = jax.nn.log_softmax(logits, -1)
    loss = -jnp.sum(labels * lp, -1)
    return loss, jnp.exp(lp) - labels


@register_op("sparse_softmax_cross_entropy_with_logits_v2", n_out=2)
def _ssce_v2(logits, labels):
    lp = jax.nn.log_softmax(logits, -1)
    oh = jax.nn.one_hot(labels, logits.shape[-1], dtype=lp.dtype)
    loss = -jnp.sum(oh * lp, -1)
    return loss, jnp.exp(lp) - oh


# ---------------------------------------------------------------------------
# Linear algebra
# ---------------------------------------------------------------------------
@register_op("matrix_inverse")
def _matrix_inverse(x, adjoint=False):
    if adjoint:
        x = jnp.swapaxes(x, -1, -2)
    return jnp.linalg.inv(x)


@register_op("cholesky")
def _cholesky(x):
    return jnp.linalg.cholesky(x)


@register_op("matrix_determinant")
def _matrix_determinant(x):
    return jnp.linalg.det(x)


@register_op("matrix_triangular_solve")
def _matrix_triangular_solve(matrix, rhs, lower=True, adjoint=False):
    return jax.scipy.linalg.solve_triangular(
        matrix, rhs, lower=bool(lower),
        trans="T" if adjoint else "N")


@register_op("matrix_diag")
def _matrix_diag(d):
    return jnp.zeros(d.shape + (d.shape[-1],), d.dtype) + \
        jnp.eye(d.shape[-1], dtype=d.dtype) * d[..., None]


@register_op("matrix_diag_part")
def _matrix_diag_part(x):
    return jnp.diagonal(x, axis1=-2, axis2=-1)


@register_op("matrix_set_diag")
def _matrix_set_diag(x, d):
    eye = jnp.eye(x.shape[-2], x.shape[-1], dtype=x.dtype)
    return x * (1 - eye) + eye * d[..., None]


# ---------------------------------------------------------------------------
# ONNX-semantics ops (the NCHW-native lowering targets of
# autodiff/onnx_import.py — XLA takes NCHW dimension numbers directly)
# ---------------------------------------------------------------------------
@register_op("reshape_with_zero")
def _reshape_with_zero(x, shape):
    """ONNX Reshape: 0 copies the input dim, -1 infers."""
    tgt = [int(s) for s in np.asarray(shape).reshape(-1)]
    tgt = [x.shape[i] if s == 0 else s for i, s in enumerate(tgt)]
    return jnp.reshape(x, tgt)


@register_op("flatten_onnx")
def _flatten_onnx(x, axis=1):
    axis = int(axis)
    lead = int(np.prod(x.shape[:axis])) if axis else 1
    return jnp.reshape(x, (lead, -1))


@register_op("unsqueeze_onnx")
def _unsqueeze_onnx(x, axis):
    # ONNX Unsqueeze axes are relative to the OUTPUT rank; normalize
    # negatives against ndim+len(axes) before inserting in ascending
    # order (axes=[-1,-3] on (2,3) -> (2,1,3,1), not (1,2,3,1)).
    # Host-preserving (_xp): shape-metaprogramming chains (Shape ->
    # Gather -> Unsqueeze -> Concat, e.g. torch LSTM h0 Expands) must
    # stay constant-foldable.
    m = _xp(x)
    if m is np:
        x = np.asarray(x)
    axes = [int(v) for v in np.asarray(axis).reshape(-1)]
    out_rank = np.ndim(x) + len(axes)
    norm = sorted(a + out_rank if a < 0 else a for a in axes)
    for a in norm:
        x = m.expand_dims(x, a)
    return x


@register_op("softmax_onnx_pre13")
def _softmax_onnx_pre13(x, axis=1):
    # Opset<13 ONNX Softmax: coerce to 2-D at `axis`, softmax over the
    # flattened trailing block, restore shape.
    axis = int(axis) % max(1, x.ndim)
    lead = int(np.prod(x.shape[:axis])) if axis else 1
    flat = jnp.reshape(x, (lead, -1))
    return jnp.reshape(jax.nn.softmax(flat, axis=-1), x.shape)


@register_op("clip_scalar")
def _clip_scalar(x, lo=-np.inf, hi=np.inf):
    return jnp.clip(x, lo, hi)


def _onnx_spatial_pads(pads, n_spatial):
    if pads is None:
        return [(0, 0)] * n_spatial
    p = [int(v) for v in np.asarray(pads).reshape(-1)]
    return [(p[i], p[i + n_spatial]) for i in range(n_spatial)]


def _onnx_padding(auto_pad, pads, x, window, strides, dilations=None):
    """Resolve ONNX auto_pad/pads to explicit per-spatial-dim pairs.
    SAME_LOWER puts the odd pad at the BEGINNING (XLA's 'SAME' string
    is SAME_UPPER, so both SAME variants are computed explicitly)."""
    n_sp = x.ndim - 2
    ap = str(auto_pad)
    if ap in ("SAME_UPPER", "SAME_LOWER"):
        dil = dilations or (1,) * n_sp
        out = []
        for i in range(n_sp):
            size = x.shape[2 + i]
            k_eff = (int(window[i]) - 1) * int(dil[i]) + 1
            o = -(-size // int(strides[i]))        # ceil
            total = max((o - 1) * int(strides[i]) + k_eff - size, 0)
            lo = (total + 1) // 2 if ap == "SAME_LOWER" else total // 2
            out.append((lo, total - lo))
        return out
    if ap == "VALID":
        return [(0, 0)] * n_sp
    return _onnx_spatial_pads(pads, n_sp)


@register_op("onnx_conv")
def _onnx_conv(x, w, b=None, strides=(1, 1), pads=None,
               auto_pad="NOTSET", dilations=(1, 1), group=1):
    n_sp = x.ndim - 2
    padding = _onnx_padding(auto_pad, pads, x, w.shape[2:], strides,
                            dilations)
    dn = ("NCHW", "OIHW", "NCHW") if n_sp == 2 else \
        ("NCDHW", "OIDHW", "NCDHW")
    y = lax.conv_general_dilated(
        x, w, window_strides=tuple(int(s) for s in strides),
        padding=padding,
        rhs_dilation=tuple(int(d) for d in dilations),
        dimension_numbers=dn, feature_group_count=int(group))
    if b is not None:
        y = y + b.reshape((1, -1) + (1,) * n_sp)
    return y


@register_op("onnx_max_pool")
def _onnx_max_pool(x, kernel_shape=(2, 2), strides=(2, 2), pads=None,
                   auto_pad="NOTSET"):
    k = tuple(int(v) for v in kernel_shape)
    s = tuple(int(v) for v in strides)
    padding = [(0, 0), (0, 0)] + _onnx_padding(auto_pad, pads, x, k, s)
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, *k),
                             (1, 1, *s), padding)


@register_op("onnx_avg_pool")
def _onnx_avg_pool(x, kernel_shape=(2, 2), strides=(2, 2), pads=None,
                   auto_pad="NOTSET", count_include_pad=0):
    k = tuple(int(v) for v in kernel_shape)
    s = tuple(int(v) for v in strides)
    padding = [(0, 0), (0, 0)] + _onnx_padding(auto_pad, pads, x, k, s)
    summed = lax.reduce_window(x, 0.0, lax.add, (1, 1, *k), (1, 1, *s),
                               padding)
    if count_include_pad:
        counts = float(np.prod(k))
    else:
        ones = jnp.ones((1, 1) + x.shape[2:], x.dtype)
        counts = lax.reduce_window(ones, 0.0, lax.add, (1, 1, *k),
                                   (1, 1, *s), padding)
    return summed / counts


@register_op("onnx_global_avg_pool")
def _onnx_global_avg_pool(x):
    return jnp.mean(x, axis=tuple(range(2, x.ndim)), keepdims=True)


@register_op("onnx_batch_norm")
def _onnx_batch_norm(x, scale, b, mean, var, eps=1e-5):
    shape = (1, -1) + (1,) * (x.ndim - 2)
    inv = lax.rsqrt(var + eps) * scale
    return x * inv.reshape(shape) + (b - mean * inv).reshape(shape)


@register_op("onnx_layer_norm")
def _onnx_layer_norm(x, scale, b=None, axis=-1, eps=1e-5):
    axis = int(axis)
    axes = tuple(range(axis % x.ndim, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    y = (x - mean) * lax.rsqrt(var + eps) * scale
    if b is not None:
        y = y + b
    return y


@register_op("onnx_pad")
def _onnx_pad(x, pads, mode="constant", value=0.0):
    p = [int(v) for v in np.asarray(pads).reshape(-1)]
    n = x.ndim
    pairs = [(p[i], p[i + n]) for i in range(n)]
    mode = str(mode)
    if mode == "constant":
        return jnp.pad(x, pairs, constant_values=value)
    return jnp.pad(x, pairs,
                   mode="reflect" if mode == "reflect" else "edge")


@register_op("onnx_slice")
def _onnx_slice(x, starts, ends, axes, steps):
    idx = [slice(None)] * x.ndim
    for st, en, ax, sp in zip(starts, ends, axes, steps):
        dim = x.shape[ax]
        en = min(int(en), dim) if en >= 0 else en
        idx[int(ax)] = slice(int(st), int(en), int(sp))
    return x[tuple(idx)]


# ---------------------------------------------------------------------------
# TF RNN-cell block ops (round-3 review missing 5: LSTMBlockCell /
# dynamic_rnn-era frozen graphs).  Gate layout: LSTMBlockCell/BlockLSTM
# are ICFO; BlockLSTMV2 is IFCO.  Ref: tf.raw_ops.{LSTMBlockCell,
# BlockLSTM,BlockLSTMV2,GRUBlockCell} [UNVERIFIED upstream:
# libnd4j lstmLayer / lstmBlock declarables].
# ---------------------------------------------------------------------------
def _lstm_gate_split(z, gate_order):
    a, b_, c, d = jnp.split(z, 4, axis=-1)
    if gate_order == "icfo":
        return a, b_, c, d          # i, ci, f, o
    return a, c, b_, d              # ifco -> (i, ci, f, o)


def _lstm_cell_math(x, cs_prev, h_prev, w, wci, wcf, wco, b,
                    forget_bias, cell_clip, use_peephole: "Static",
                    gate_order):
    xh = jnp.concatenate([x, h_prev], axis=1)
    i, ci, f, o = _lstm_gate_split(xh @ w + b, gate_order)
    if use_peephole:
        i = i + wci * cs_prev
        f = f + wcf * cs_prev
    i = jax.nn.sigmoid(i)
    f = jax.nn.sigmoid(f + forget_bias)
    ci = jnp.tanh(ci)
    cs = ci * i + cs_prev * f
    if cell_clip is not None and float(cell_clip) > 0:
        cs = jnp.clip(cs, -float(cell_clip), float(cell_clip))
    if use_peephole:
        o = o + wco * cs
    o = jax.nn.sigmoid(o)
    co = jnp.tanh(cs)
    h = co * o
    return i, cs, f, o, ci, co, h


@register_op("lstm_block_cell", n_out=7)
def _lstm_block_cell(x, cs_prev, h_prev, w, wci, wcf, wco, b,
                     forget_bias=1.0, cell_clip=3.0,
                     use_peephole=False, gate_order="icfo"):
    return _lstm_cell_math(x, cs_prev, h_prev, w, wci, wcf, wco, b,
                           forget_bias, cell_clip, use_peephole,
                           gate_order)


@register_op("block_lstm", n_out=7)
def _block_lstm(seq_len_max, x, cs_prev, h_prev, w, wci, wcf, wco, b,
                forget_bias=1.0, cell_clip=3.0, use_peephole=False,
                gate_order="icfo"):
    """Whole-sequence LSTM over x [t, b, in] via ONE lax.scan (the
    dynamic_rnn replacement: no per-timestep frame interpreter).
    Steps at or past seq_len_max freeze the carry and emit zeros."""
    slm = jnp.asarray(seq_len_max, jnp.int32).reshape(())

    def step(carry, xt):
        cs_p, h_p, t = carry
        i, cs, f, o, ci, co, h = _lstm_cell_math(
            xt, cs_p, h_p, w, wci, wcf, wco, b, forget_bias,
            cell_clip, use_peephole, gate_order)
        valid = t < slm
        cs_n = jnp.where(valid, cs, cs_p)
        h_n = jnp.where(valid, h, h_p)
        zero = lambda a: jnp.where(valid, a, jnp.zeros_like(a))
        return (cs_n, h_n, t + 1), tuple(
            zero(v) for v in (i, cs, f, o, ci, co, h))

    _, ys = lax.scan(step, (cs_prev, h_prev, jnp.asarray(0, jnp.int32)),
                     x)
    return ys


@register_op("gru_block_cell", n_out=4)
def _gru_block_cell(x, h_prev, w_ru, w_c, b_ru, b_c):
    xh = jnp.concatenate([x, h_prev], axis=1)
    r, u = jnp.split(jax.nn.sigmoid(xh @ w_ru + b_ru), 2, axis=-1)
    xrh = jnp.concatenate([x, r * h_prev], axis=1)
    c = jnp.tanh(xrh @ w_c + b_c)
    h = u * h_prev + (1.0 - u) * c
    return r, u, c, h


# ---------------------------------------------------------------------------
# ONNX recurrent ops (torch.onnx.export emits these for nn.LSTM/GRU).
# ONNX gate orders: LSTM [i o f c], GRU [z r h].  Optional inputs are
# slot-encoded via the ``present`` attr (ONNX's empty-string inputs
# collapse positions otherwise).
# ---------------------------------------------------------------------------
def _slotted(args, present):
    slots = {}
    for p, a in zip(present, args):
        slots[int(p)] = a
    return slots


@register_op("onnx_lstm", n_out=3)
def _onnx_lstm(*args, present=(0, 1, 2), hidden_size=None,
               direction="forward"):
    s = _slotted(args, present)
    x, w, r = s[0], s[1], s[2]
    if 4 in s and s[4] is not None:
        raise NotImplementedError("ONNX LSTM sequence_lens")
    if 7 in s:
        raise NotImplementedError("ONNX LSTM peepholes")
    t, bsz, _ = x.shape
    nd = w.shape[0]
    h = int(hidden_size or w.shape[1] // 4)
    b_all = s.get(3)
    if b_all is None:
        b_all = jnp.zeros((nd, 8 * h), x.dtype)
    h0 = s.get(5)
    c0 = s.get(6)
    if h0 is None:
        h0 = jnp.zeros((nd, bsz, h), x.dtype)
    if c0 is None:
        c0 = jnp.zeros((nd, bsz, h), x.dtype)

    def run_dir(d, reverse):
        wi, ri = w[d], r[d]
        bias = b_all[d, :4 * h] + b_all[d, 4 * h:]
        xs = jnp.flip(x, 0) if reverse else x

        def step(carry, xt):
            hp, cp = carry
            g = xt @ wi.T + hp @ ri.T + bias
            i_, o_, f_, c_ = jnp.split(g, 4, -1)      # ONNX iofc
            i_ = jax.nn.sigmoid(i_)
            o_ = jax.nn.sigmoid(o_)
            f_ = jax.nn.sigmoid(f_)
            c = f_ * cp + i_ * jnp.tanh(c_)
            hh = o_ * jnp.tanh(c)
            return (hh, c), hh

        (hT, cT), ys = lax.scan(step, (h0[d], c0[d]), xs)
        if reverse:
            ys = jnp.flip(ys, 0)
        return ys, hT, cT

    dirs = {"forward": [(0, False)], "reverse": [(0, True)],
            "bidirectional": [(0, False), (1, True)]}[str(direction)]
    outs = [run_dir(d, rev) for d, rev in dirs]
    y = jnp.stack([o[0] for o in outs], axis=1)       # [t, nd, b, h]
    y_h = jnp.stack([o[1] for o in outs], axis=0)
    y_c = jnp.stack([o[2] for o in outs], axis=0)
    return y, y_h, y_c


@register_op("onnx_gru", n_out=2)
def _onnx_gru(*args, present=(0, 1, 2), hidden_size=None,
              direction="forward", linear_before_reset=0):
    s = _slotted(args, present)
    x, w, r = s[0], s[1], s[2]
    if 4 in s and s[4] is not None:
        raise NotImplementedError("ONNX GRU sequence_lens")
    t, bsz, _ = x.shape
    nd = w.shape[0]
    h = int(hidden_size or w.shape[1] // 3)
    b_all = s.get(3)
    if b_all is None:
        b_all = jnp.zeros((nd, 6 * h), x.dtype)
    h0 = s.get(5)
    if h0 is None:
        h0 = jnp.zeros((nd, bsz, h), x.dtype)

    def run_dir(d, reverse):
        wi, ri = w[d], r[d]
        wb, rb = b_all[d, :3 * h], b_all[d, 3 * h:]
        xs = jnp.flip(x, 0) if reverse else x

        lbr = bool(int(linear_before_reset))

        def step(hp, xt):
            gx = xt @ wi.T + wb
            zx, rx, hx = jnp.split(gx, 3, -1)         # ONNX zrh
            if lbr:
                gh = hp @ ri.T + rb
                zh, rh, hh_ = jnp.split(gh, 3, -1)
            else:   # h-gate recurrence applies AFTER reset: don't
                    # burn a third of the recurrent matmul on it here
                zh, rh = jnp.split(hp @ ri[:2 * h].T + rb[:2 * h],
                                   2, -1)
            z = jax.nn.sigmoid(zx + zh)
            rr = jax.nn.sigmoid(rx + rh)
            if lbr:
                ht = jnp.tanh(hx + rr * hh_)
            else:
                ht = jnp.tanh(hx + (rr * hp) @ ri[2 * h:].T
                              + rb[2 * h:])
            hn = (1.0 - z) * ht + z * hp
            return hn, hn

        hT, ys = lax.scan(step, h0[d], xs)
        if reverse:
            ys = jnp.flip(ys, 0)
        return ys, hT

    dirs = {"forward": [(0, False)], "reverse": [(0, True)],
            "bidirectional": [(0, False), (1, True)]}[str(direction)]
    outs = [run_dir(d, rev) for d, rev in dirs]
    y = jnp.stack([o[0] for o in outs], axis=1)
    y_h = jnp.stack([o[1] for o in outs], axis=0)
    return y, y_h


@register_op("broadcast_to_dynamic")
def _broadcast_to_dynamic(x, shape):
    """ONNX Expand whose target rides the graph (Shape->...->Concat):
    the shape chain constant-folds to a HOST vector at trace time (see
    module docstring); anything else is a data-dependent shape XLA
    cannot compile — fail loudly."""
    if not is_static_value(shape):
        raise ValueError(
            "Expand target shape did not constant-fold at trace time "
            "(data-dependent shapes are not compilable)")
    tgt = [int(s) for s in np.asarray(shape).reshape(-1)]
    # ONNX Expand: BIDIRECTIONAL numpy broadcast — right-align and pad
    # BOTH sides to the max rank (a target shorter than x's rank is
    # legal and must not truncate x)
    xs = list(np.shape(x))
    rank = max(len(xs), len(tgt))
    xs = [1] * (rank - len(xs)) + xs
    tgt = [1] * (rank - len(tgt)) + tgt
    out = [max(a, b) for a, b in zip(xs, tgt)]
    return _xp(x).broadcast_to(x, tuple(out))


@register_op("reshape_dynamic")
def _reshape_dynamic(x, shape):
    """ONNX Reshape with a graph-computed target (host at trace time);
    supports 0 = copy input dim and a single -1."""
    if not is_static_value(shape):
        raise ValueError(
            "Reshape target did not constant-fold at trace time")
    tgt = [int(s) for s in np.asarray(shape).reshape(-1)]
    tgt = [np.shape(x)[i] if s == 0 else s for i, s in enumerate(tgt)]
    return _xp(x).reshape(x, tuple(tgt))
