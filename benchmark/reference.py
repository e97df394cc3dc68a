"""The plain reference: a post-LN transformer in straightforward
float32 ``jax.numpy`` at matmul precision "highest" -- embeddings
(word + learned position + LayerNorm), blocks (fused qkv, full
multi-head attention, tanh-GELU feed-forward, biases), a mean-pooled
classifier head or a per-position LM head, the loss, its gradients and
Adam.  No kernel, no cache, no batching.  It imports nothing of the
package and takes nothing the package has made: weights and inputs come
from the seed alone.

``quant="fp8"`` is the CONTROL (never the reference): a float8 step --
every matmul operand rounded to float8_e4m3 forward, its gradient to
float8_e5m2 backward, a scale per tensor -- the precision below the
configurations' bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

LN_EPS = 1e-12
_BLOCK_LEAVES = {"Wqkv": ("d", "3d"), "bqkv": ("3d",), "Wo": ("d", "d"),
                 "bo": ("d",), "ln1_g": ("d",), "ln1_b": ("d",),
                 "W1": ("d", "ff"), "b1": ("ff",), "W2": ("ff", "d"),
                 "b2": ("d",), "ln2_g": ("d",), "ln2_b": ("d",)}


def seed_key(seed: int):
    """Any whole number up to a little over 2**31 (and beyond)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


@functools.partial(jax.jit, static_argnames=("vocab", "max_len", "d",
                                             "layers", "ff", "n_out"))
def _weights(key, *, vocab, max_len, d, layers, ff, n_out):
    dims = {"d": d, "3d": 3 * d, "ff": ff}
    n = iter(range(10_000))

    def draw(shape, gain=False):
        w = 0.02 * jax.random.normal(jax.random.fold_in(key, next(n)),
                                     shape, jnp.float32)
        return 1.0 + w if gain else w

    emb = {"W": draw((vocab, d)), "P": draw((max_len, d)),
           "g": draw((d,), True), "b": draw((d,))}
    blocks = {name: draw((layers,) + tuple(dims[a] for a in axes),
                         gain=name.endswith("_g"))
              for name, axes in _BLOCK_LEAVES.items()}
    head = {"W": draw((d, n_out)), "b": draw((n_out,))}
    return {"emb": emb, "blocks": blocks, "head": head}


def make_weights(shape: dict, seed: int):
    """All weights in ONE jitted call on the device: N(0, 0.02) for
    matrices, tables and biases, 1 + N(0, 0.02) for LayerNorm gains
    (BERT's initialiser range; biases drawn too, so that every
    parameter takes part in what is compared).  Block leaves carry a
    leading [layers] axis."""
    return weights_from_key(shape, seed_key(seed))


def weights_from_key(shape: dict, key):
    """``make_weights`` for callers that are already inside a jit."""
    return _weights(key, vocab=shape["vocab"], max_len=shape["max_len"],
                    d=shape["d"], layers=shape["layers"], ff=shape["ff"],
                    n_out=shape["n_out"])


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _round_to(x, dtype, top):
    scale = jnp.max(jnp.abs(x)) / top + 1e-30
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _q8(x):
    """A float8 training step's rounding of one matmul operand: e4m3
    forward, and its gradient rounded to e5m2 on the way back, each
    under a scale per tensor."""
    return _round_to(x, jnp.float8_e4m3fn, 448.0)


_q8.defvjp(lambda x: (_q8(x), None),
           lambda _, g: (_round_to(g, jnp.float8_e5m2, 57344.0),))


def _ops(quant):
    q = _q8 if quant == "fp8" else (lambda x: x)
    if quant not in (None, "fp8"):
        raise ValueError(f"unknown control precision {quant!r}")
    return q


def layer_norm(x, g, b):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * g + b


def embed(emb, ids):
    x = emb["W"][ids] + emb["P"][: ids.shape[1]][None]
    return layer_norm(x, emb["g"], emb["b"])


def block(p, x, heads: int, causal: bool, q):
    b, t, d = x.shape
    dh = d // heads
    qkv = q(x) @ q(p["Wqkv"]) + p["bqkv"]
    qh, kh, vh = (z.reshape(b, t, heads, dh)
                  for z in jnp.split(qkv, 3, axis=-1))
    s = jnp.einsum("bqhd,bkhd->bhqk", q(qh), q(kh)) / np.sqrt(dh)
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], s,
                      -1e30)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", q(jax.nn.softmax(s, axis=-1)),
                     q(vh)).reshape(b, t, d)
    h = layer_norm(x + q(ctx) @ q(p["Wo"]) + p["bo"],
                   p["ln1_g"], p["ln1_b"])
    f = jax.nn.gelu(q(h) @ q(p["W1"]) + p["b1"], approximate=True)
    return layer_norm(h + q(f) @ q(p["W2"]) + p["b2"],
                      p["ln2_g"], p["ln2_b"])


def hidden(w, ids, heads: int, causal: bool, quant=None):
    """Embeddings and every block, one block's intermediates alive at a
    time (the layer loop is a rematerialised scan)."""
    q = _ops(quant)
    body = jax.checkpoint(
        lambda x, p: (block(p, x, heads, causal, q), None))
    x, _ = jax.lax.scan(body, embed(w["emb"], ids), w["blocks"])
    return x


def classifier_logits(w, ids, heads: int, quant=None):
    q = _ops(quant)
    pooled = jnp.mean(hidden(w, ids, heads, False, quant), axis=1)
    return q(pooled) @ q(w["head"]["W"]) + w["head"]["b"]


def lm_logits(w, ids, heads: int, quant=None):
    q = _ops(quant)
    x = hidden(w, ids, heads, True, quant)
    return q(x) @ q(w["head"]["W"]) + w["head"]["b"]


# ---------------------------------------------------------------------------
# training: loss, gradients in blocks of rows, Adam
# ---------------------------------------------------------------------------
def _xent_sum(w, ids, onehot, heads, quant):
    logp = jax.nn.log_softmax(classifier_logits(w, ids, heads, quant))
    return -jnp.sum(onehot * logp)


@functools.partial(jax.jit, static_argnames=("heads", "quant"))
def _grad_rows(w, ids, onehot, heads, quant):
    return jax.value_and_grad(_xent_sum)(w, ids, onehot, heads, quant)


@functools.partial(jax.jit, donate_argnums=(0,))
def _tree_add(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


def loss_and_grads(w, ids, onehot, heads: int, rows: int, quant=None):
    """Mean cross-entropy over the batch and its gradients, accumulated
    over blocks of ``rows`` rows so that the float32 pass fits."""
    n = ids.shape[0]
    total, grads = 0.0, None
    for i in range(0, n, rows):
        l, g = _grad_rows(w, ids[i:i + rows], onehot[i:i + rows], heads,
                          quant)
        total = total + l
        grads = g if grads is None else _tree_add(grads, g)
    scale = 1.0 / n
    return total * scale, jax.tree_util.tree_map(lambda g: g * scale, grads)


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _adam(w, m, v, g, t, lr, b1, b2, eps):
    tm = jax.tree_util.tree_map
    m = tm(lambda m, g: b1 * m + (1 - b1) * g, m, g)
    v = tm(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
    alpha = lr * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
    w = tm(lambda w, m, v: w - alpha * m / (jnp.sqrt(v) + eps), w, m, v)
    return w, m, v


@jax.jit
def leaf_norms(tree):
    """{"emb.W": norm, ..., "blocks.Wq": [layers] norms, ...}: one norm
    per parameter, block leaves layer by layer.  The fused qkv
    projection counts as the three parameters it holds (Wq, Wk, Wv and
    bq, bk, bv): the key's bias has no gradient under softmax, and a
    fused leaf would hide that from the rule that leaves it out."""
    out = {}
    for group, leaves in tree.items():
        for name, a in leaves.items():
            axes = tuple(range(1, a.ndim)) if group == "blocks" else None
            parts = ({name: a} if not name.endswith("qkv") else
                     {name[0] + part: x for part, x in
                      zip("qkv", jnp.split(a, 3, axis=-1))})
            for part, x in parts.items():
                out[f"{group}.{part}"] = jnp.sqrt(jnp.sum(jnp.square(x), axis=axes))
    return out


@jax.jit
def _change_norms(w, w0):
    return leaf_norms(jax.tree_util.tree_map(jnp.subtract, w, w0))


def follow_training(shape: dict, adam: dict, seed: int, batches, rows: int,
                    quant=None, batch_rows=None, make=make_weights):
    """The first ``len(batches)`` Adam steps from the seed's weights.
    Returns the losses, the first step's gradient norms and the norms
    of the parameters' change after the last step, per leaf.
    ``batch_rows`` keeps only those rows of every batch (the planted
    half-batch fault); ``make(shape, seed)`` is where the weights come
    from (a family with this block and other weights gives its own)."""
    with jax.default_matmul_precision("highest"):
        w = make(shape, seed)
        zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, w)
        m, v = zeros(), zeros()
        losses, grad_norms = [], None
        for t, (ids, onehot) in enumerate(batches, start=1):
            if batch_rows is not None:
                ids, onehot = ids[:batch_rows], onehot[:batch_rows]
            loss, g = loss_and_grads(w, jnp.asarray(ids), jnp.asarray(onehot),
                                     shape["heads"], rows, quant)
            losses.append(float(loss))
            if grad_norms is None:
                grad_norms = jax.device_get(leaf_norms(g))
            # linear warm-up: the step's rate is lr * min(1, t / warmup)
            lr = adam["lr"] * min(1.0, t / adam.get("warmup", 1))
            w, m, v = _adam(w, m, v, g, jnp.float32(t), lr,
                            adam["beta1"], adam["beta2"], adam["eps"])
        change = jax.device_get(_change_norms(w, make(shape, seed)))
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}


# ---------------------------------------------------------------------------
# serving: the gap of each served token under the reference's logits
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("heads", "quant"))
def _lm(w, ids, heads, quant):
    return lm_logits(w, ids, heads, quant)


def served_token_gaps(w, heads: int, seq, t0: int, quant=None):
    """``seq`` is a prompt of ``t0`` tokens followed by served tokens.
    For each served token: how far its float32 logit lies below the
    reference's best at that position (0 where they agree).  With
    ``quant`` the token judged is the one the lower precision puts
    first, not the served one."""
    seq = np.asarray(seq, np.int32)
    pad = -len(seq) % 64          # few compiled lengths, causal: the
    ids = np.pad(seq, (0, pad))   # padding cannot reach what is read
    with jax.default_matmul_precision("highest"):
        logits = _lm(w, ids[None], heads, None)[0, t0 - 1:len(seq) - 1]
        judged = jnp.asarray(seq[t0:])
        if quant is not None:
            judged = jnp.argmax(
                _lm(w, ids[None], heads, quant)[0, t0 - 1:len(seq) - 1], -1)
        best = jnp.max(logits, axis=-1)
        got = jnp.take_along_axis(logits, judged[:, None], axis=-1)[:, 0]
    return np.asarray(best - got)
