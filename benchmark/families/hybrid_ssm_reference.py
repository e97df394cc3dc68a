"""The plain reference of the hybrid state-space / attention decoder:
the layer equations in straightforward float32 ``jax.numpy`` at matmul
precision "highest", ONE sequence at a time, a sequential scan over
time for the recurrence, full causal attention -- no cache, no kernel,
no batching.  It imports nothing of the package and takes nothing the
package has made: weights and inputs come from the seed alone.

``eps`` = 1e-6 everywhere.  ``RMSNorm(x; g) = g * x / sqrt(mean(x^2) +
eps)``.  Layer ``i``: ``x = x + Mixer_i(RMSNorm(x; norm1))``, then ``x =
x + W_down(silu(W_gate n) * (W_up n))`` with ``n = RMSNorm(x; norm2)``;
the mixer is attention where ``i % attn_period == attn_offset`` and
Mamba-1 elsewhere.  Embedding: a table, no positional term.  Output:
``RMSNorm(x; g)`` times the same table transposed.

Leaves are laid out as the Mamba paper writes them (``conv_w`` [d_inner,
d_conv], ``A_log`` [d_inner, d_state]); ``hybrid_ssm.to_program`` turns
them into the program's.

Departures from the published model (``AI21-Jamba2-3B``): weights are
drawn, not trained; nothing else -- every width, the layer order, the
three inner norms, the tied head and the absence of biases and of any
positional term are the published ones.

``quant`` is the CONTROL (never the reference): ``"state_bf16"`` carries
the recurrent state in bfloat16 between steps, ``"fp8+state_bf16"``
also rounds every matmul operand to float8_e4m3 under a scale per
tensor -- one precision below the configuration on both counts.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-6
QUANTS = (None, "state_bf16", "fp8+state_bf16")


# ---------------------------------------------------------------------------
# weights: leaves that are drawn when asked for, a layer at a time
# ---------------------------------------------------------------------------
def _draw(recipe: str, key, shape, std: float = 0.02):
    """One layer's values of a leaf.  Matrices and the table N(0,
    0.02), norm gains and D 1 + N(0, 0.02); the recurrence as the Mamba
    paper initialises it -- ``A_log = log(1 .. d_state)`` per channel (+
    N(0, 0.02)), ``dt_bias`` such that ``softplus(dt_bias)`` is
    log-uniform in [1e-3, 0.1].  With N(0, 0.02) there instead every
    channel forgets in two tokens and no comparison could tell a
    correct state from a stale one.  ``std`` is 0.02 unless the shape
    states an ``init_std``: at a test's tiny widths a layer's output
    under N(0, 0.02) vanishes beside the embedding, and every context
    gives the same token."""
    noise = std * jax.random.normal(key, shape, jnp.float32)
    if recipe == "normal":
        return noise
    if recipe == "gain":
        return 1.0 + noise
    if recipe == "a_log":                       # [d_inner, d_state]
        return jnp.log(jnp.arange(1, shape[-1] + 1, dtype=jnp.float32)) + noise
    if recipe == "dt_bias":
        step = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                       * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
        return step + jnp.log(-jnp.expm1(-step))   # softplus's inverse
    raise ValueError(f"unknown recipe {recipe!r}")


class Leaf:
    """A parameter that is a key, a shape and a recipe.  ``layers`` > 0:
    the leaf is stacked [layers, *shape] and every layer is drawn from
    its own fold of the key, so one layer can be made alone.
    ``astype`` records the rounding; nothing is computed until
    ``layer()`` or ``whole()``.  To ``jax.tree_util`` it is a leaf."""

    def __init__(self, key, shape, recipe, layers=0, casts=(), std=0.02):
        self.key, self.inner, self.recipe = key, tuple(shape), recipe
        self.layers, self.casts, self.std = layers, tuple(casts), std

    shape = property(lambda s: ((s.layers,) if s.layers else ()) + s.inner)
    dtype = property(lambda s: s.casts[-1] if s.casts else jnp.dtype("float32"))

    def astype(self, dtype):
        return Leaf(self.key, self.inner, self.recipe, self.layers,
                    self.casts + (jnp.dtype(dtype),), self.std)

    def _cast(self, a):
        for dt in self.casts:
            a = a.astype(dt)
        return a

    def layer(self, l):
        return self._cast(_draw(self.recipe, jax.random.fold_in(self.key, l),
                                self.inner, self.std))

    def whole(self):
        """All of it (a stacked leaf layer by layer: ``stack_layers``)."""
        if not self.layers:
            return self._cast(_draw(self.recipe, self.key, self.inner,
                                    self.std))
        return stack_layers({"a": self}, 0, self.layers)["a"]


def stack_layers(group: dict, lo: int, n: int, then=lambda name, a: a) -> dict:
    """Layers ``lo .. lo + n`` of every stacked leaf of ``group``, made
    ONE LAYER AT A TIME: each layer's leaves are drawn, rounded and
    passed through ``then(name, values)`` before the next layer's are,
    so no whole float32 copy of a leaf exists."""
    return jax.lax.map(
        lambda l: {k: then(k, leaf.layer(l)) for k, leaf in group.items()},
        lo + jnp.arange(n))


def leaf_specs(shape: dict) -> dict:
    """{group: {leaf: (shape of one layer, recipe)}}"""
    d, di, ns, r = shape["d"], shape["d_inner"], shape["d_state"], shape["dt_rank"]
    ff, hq, hkv, dh = shape["ff"], shape["heads"], shape["kv_heads"], shape["head_dim"]
    ffn = {"norm2": ((d,), "gain"), "W_gate": ((d, ff), "normal"),
           "W_up": ((d, ff), "normal"), "W_down": ((ff, d), "normal")}
    return {
        "mamba": {"norm1": ((d,), "gain"), "W_in": ((d, 2 * di), "normal"),
                  "conv_w": ((di, shape["d_conv"]), "normal"),
                  "conv_b": ((di,), "normal"),
                  "W_x": ((di, r + 2 * ns), "normal"),
                  "dt_norm": ((r,), "gain"), "b_norm": ((ns,), "gain"),
                  "c_norm": ((ns,), "gain"), "W_dt": ((r, di), "normal"),
                  "dt_bias": ((di,), "dt_bias"), "A_log": ((di, ns), "a_log"),
                  "D": ((di,), "gain"), "W_out": ((di, d), "normal"), **ffn},
        "attn": {"norm1": ((d,), "gain"), "Wq": ((d, hq * dh), "normal"),
                 "Wk": ((d, hkv * dh), "normal"), "Wv": ((d, hkv * dh), "normal"),
                 "Wo": ((hq * dh, d), "normal"), **ffn}}


def weights_from_key(shape: dict, key):
    """The tree of lazy leaves (jit-safe: ``key`` may be traced)."""
    n = iter(range(10_000))
    sub = lambda: jax.random.fold_in(key, next(n))
    counts = {"mamba": shape["ssm_layers"], "attn": shape["attn_layers"]}
    std = shape.get("init_std", 0.02)
    tree = {"emb": {"W": Leaf(sub(), (shape["vocab"], shape["d"]), "normal",
                              std=std)}}
    for group, leaves in leaf_specs(shape).items():
        tree[group] = {name: Leaf(sub(), inner, recipe, counts[group], std=std)
                       for name, (inner, recipe) in leaves.items()}
    tree["head"] = {"g": Leaf(sub(), (shape["d"],), "gain", std=std)}
    return tree


def layer_kinds(shape: dict) -> list:
    """[("attn" | "mamba", index within its kind)] for every layer."""
    out, seen = [], {"attn": 0, "mamba": 0}
    for i in range(shape["layers"]):
        kind = ("attn" if i % shape["attn_period"] == shape["attn_offset"]
                else "mamba")
        out.append((kind, seen[kind]))
        seen[kind] += 1
    return out


@functools.lru_cache(maxsize=None)
def _layer_maker(spec: tuple):
    """Jitted ``(keys, l) -> {leaf: that layer's values}`` for a group
    whose leaves are ``spec`` = ((name, shape, recipe, casts), ...)."""
    def make(keys, l):
        return {name: Leaf(keys[name], inner, recipe, 1, casts, std).layer(l)
                for name, inner, recipe, casts, std in spec}
    return jax.jit(make)


def layer_weights(group: dict, l: int) -> dict:
    spec = tuple((name, leaf.inner, leaf.recipe, leaf.casts, leaf.std)
                 for name, leaf in sorted(group.items()))
    return _layer_maker(spec)({k: leaf.key for k, leaf in group.items()}, l)


# ---------------------------------------------------------------------------
# forward, one sequence x [t, d]
# ---------------------------------------------------------------------------
def _q8(x):
    scale = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _ops(quant):
    """(the rounding of a matmul operand, of the carried state)"""
    if quant not in QUANTS:
        raise ValueError(f"unknown control precision {quant!r}")
    same = lambda x: x
    return (_q8 if quant == "fp8+state_bf16" else same,
            (lambda h: h.astype(jnp.bfloat16).astype(jnp.float32))
            if quant else same)


def rms_norm(x, g):
    return g * x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS)


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def mamba_mixer(p, n, q, qh):
    t = n.shape[0]
    k = p["conv_w"].shape[1]
    r, ns = p["dt_norm"].shape[0], p["b_norm"].shape[0]
    u, z = jnp.split(q(n) @ q(p["W_in"]), 2, axis=-1)
    up = jnp.pad(u, ((k - 1, 0), (0, 0)))          # zeros before the sequence
    uc = silu(p["conv_b"] + sum(p["conv_w"][:, j] * up[j:j + t]
                                for j in range(k)))
    xdbc = q(uc) @ q(p["W_x"])
    dt_r = rms_norm(xdbc[:, :r], p["dt_norm"])
    bm = rms_norm(xdbc[:, r:r + ns], p["b_norm"])
    cm = rms_norm(xdbc[:, r + ns:], p["c_norm"])
    delta = jax.nn.softplus(q(dt_r) @ q(p["W_dt"]) + p["dt_bias"])
    a = -jnp.exp(p["A_log"])                        # [d_inner, d_state]

    def tick(h, xs):
        d_t, u_t, b_t, c_t = xs
        h = qh(jnp.exp(d_t[:, None] * a) * h + (d_t * u_t)[:, None] * b_t[None])
        return h, h @ c_t + p["D"] * u_t

    _, y = jax.lax.scan(tick, jnp.zeros_like(a), (delta, uc, bm, cm))
    return q(y * silu(z)) @ q(p["W_out"])


def attention_mixer(p, n, q, heads: int, kv_heads: int):
    t = n.shape[0]
    dh = p["Wq"].shape[1] // heads
    qs = (q(n) @ q(p["Wq"])).reshape(t, heads, dh)
    ks = (q(n) @ q(p["Wk"])).reshape(t, kv_heads, dh)
    vs = (q(n) @ q(p["Wv"])).reshape(t, kv_heads, dh)
    # every query head of a group on its one K/V head
    ks, vs = (jnp.repeat(x, heads // kv_heads, axis=1) for x in (ks, vs))
    s = jnp.einsum("qhd,khd->hqk", q(qs), q(ks)) / np.sqrt(dh)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -1e30)
    ctx = jnp.einsum("hqk,khd->qhd", q(jax.nn.softmax(s, axis=-1)), q(vs))
    return q(ctx.reshape(t, heads * dh)) @ q(p["Wo"])


@functools.partial(jax.jit, static_argnames=("kind", "heads", "kv_heads", "quant"))
def layer_forward(p, x, kind, heads, kv_heads, quant):
    q, qh = _ops(quant)
    n = rms_norm(x, p["norm1"])
    x = x + (attention_mixer(p, n, q, heads, kv_heads) if kind == "attn"
             else mamba_mixer(p, n, q, qh))
    n = rms_norm(x, p["norm2"])
    return x + q(silu(q(n) @ q(p["W_gate"])) * (q(n) @ q(p["W_up"]))) @ q(p["W_down"])


@functools.partial(jax.jit, static_argnames=("quant",))
def _logits(g, table, x, quant):
    q, _ = _ops(quant)
    return q(rms_norm(x, g)) @ q(table).T


def lm_logits(w, shape: dict, ids, quants=(None,)) -> dict:
    """{quant: logits [t, vocab]} of ONE sequence ``ids`` [t], every
    layer's weights made once and used by each pass, one layer alive at
    a time."""
    with jax.default_matmul_precision("highest"):
        table = w["emb"]["W"].whole()
        xs = {qt: table[jnp.asarray(ids)] for qt in quants}
        for kind, l in layer_kinds(shape):
            p = layer_weights(w[kind], l)
            for qt in quants:
                xs[qt] = layer_forward(p, xs[qt], kind, shape["heads"],
                                       shape["kv_heads"], qt)
        g = w["head"]["g"].whole()
        return {qt: _logits(g, table, x, qt) for qt, x in xs.items()}


def served_token_gaps(w, shape: dict, seq, t0: int, quant=None):
    """``seq`` is a prompt of ``t0`` tokens followed by served tokens.
    For each served token: how far its float32 logit lies below the
    reference's best at that position (0 where they agree).  With
    ``quant`` the token judged is the one the lower precision puts
    first, not the served one."""
    seq = np.asarray(seq, np.int32)
    pad = -len(seq) % 256         # few compiled lengths (a layer's pass at
    ids = np.pad(seq, (0, pad))   # "highest" compiles for seconds); causal,
                                  # so the padding cannot reach what is read
    got = lm_logits(w, shape, ids, (None, quant) if quant else (None,))
    rows = slice(t0 - 1, len(seq) - 1)
    logits = got[None][rows]
    judged = jnp.asarray(seq[t0:])
    if quant is not None:
        judged = jnp.argmax(got[quant][rows], -1)
    best = jnp.max(logits, axis=-1)
    return np.asarray(best - jnp.take_along_axis(logits, judged[:, None],
                                                 axis=-1)[:, 0])
