"""The plain reference of the sparse-expert decoder with window and
full attention mixed: the layer equations in straightforward float32
``jax.numpy`` at matmul precision "highest", ONE sequence at a time,
full causal attention a K/V head at a time -- no cache, no kernel, no
batching.  It imports nothing of the package and takes nothing the
package has made: weights and inputs come from the seed alone (the lazy
leaves are ``hybrid_ssm_reference``'s).

Layer ``l``, ``kind_l`` = full where ``pattern[l] == 0`` else window; H
query heads, ``Hkv`` K/V heads of the layer's kind; no biases; ``eps``
as the configuration states it:

1. ``h = RMSNorm(x; norm1)``; ``q = h Wq -> [H, dk]``, ``k = h Wk ->
   [Hkv, dk]``, ``v = value_scale * (h Wv) -> [Hkv, dv]``.
2. Rotary, rotate-half pairing, on the first ``rotary`` lanes of q and k
   at the token's position, ``theta`` of the layer's kind.
3. ``s_ij = q_i . k_j / sqrt(dk)`` for ``j <= i`` and, in a window
   layer, ``i - window < j``.  Query head n reads K/V head ``n // (H /
   Hkv)``.
4. A window layer has a learned logit ``b_n`` a query head (the sink):
   ``a_i = sum_j exp(s_ij - m) v_j / (exp(b_n - m) + sum_j exp(s_ij -
   m))``, m the max over the ``s_ij`` and ``b_n``.  A full layer: plain
   softmax.
5. ``x = x + concat_n(a) Wo``.
6. ``h2 = RMSNorm(x; norm2)``.  A dense layer: ``x = x + (silu(h2 Wg) *
   (h2 Wu)) Wd``.  A routed layer: ``r = sigmoid(h2 Wr)`` over ALL the
   experts; the ``top_k`` with the largest ``r_e + c_e`` are selected;
   ``w_e = r_e / sum over the selected``; ``x = x + sum over e selected
   AND held of w_e (silu(h2 Wg_e) * (h2 Wu_e)) Wd_e`` -- held = the
   share's ``(first, count)``: what the experts held elsewhere would
   add is left out, here as in the program, and the partial sum goes on.
7. ``logits = RMSNorm(x; g) W_head^T`` over the rows of the vocabulary
   held.

Departures from the published model (``MiMo-V2-Flash``): weights are
drawn, not trained; the chip's share of the experts and the vocabulary
(``reduced``, in the configuration's file); the multi-token-prediction
layers its description mentions are not in its config and not built.

``quant="fp8"`` is the CONTROL (never the reference): every matmul
operand -- the router's too -- rounded to float8_e4m3 under a scale per
tensor, one precision below the configuration's bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.families.hybrid_ssm_reference import (Leaf, _q8,
                                                     layer_weights, silu)

QUANTS = (None, "fp8")
GROUPS = ("full_dense", "full_routed", "win_dense", "win_routed")


def group_of(window: bool, routed: bool) -> str:
    return ("win" if window else "full") + ("_routed" if routed else "_dense")


def layer_kinds(shape: dict) -> list:
    """[(group, index within its group)] for every layer."""
    out, seen = [], {g: 0 for g in GROUPS}
    for w, r in zip(shape["pattern"], shape["routed"]):
        g = group_of(bool(w), bool(r))
        out.append((g, seen[g]))
        seen[g] += 1
    return out


def leaf_specs(shape: dict) -> dict:
    """{group: {leaf: (shape of one layer, recipe)}} of the groups the
    pattern has, named and laid out as the program's runs are."""
    d, H, dk, dv = shape["d"], shape["heads"], shape["qk_dim"], shape["v_dim"]
    E, held = shape["experts"], shape["held"][1]
    out = {}
    for g in {g for g, _ in layer_kinds(shape)}:
        win, routed = g.startswith("win"), g.endswith("routed")
        hkv = shape["win_kv_heads" if win else "kv_heads"]
        spec = {"norm1": ((d,), "gain"), "Wq": ((d, H * dk), "normal"),
                "Wk": ((d, hkv * dk), "normal"),
                "Wv": ((d, hkv * dv), "normal"),
                "Wo": ((H * dv, d), "normal"), "norm2": ((d,), "gain")}
        if shape["win_sink" if win else "full_sink"]:
            spec["sink"] = ((H,), "normal")
        if routed:
            ff = shape["expert_ff"]
            spec.update(W_router=((d, E), "normal"), e_bias=((E,), "normal"),
                        W_gate=((held, d, ff), "normal"),
                        W_up=((held, d, ff), "normal"),
                        W_down=((held, ff, d), "normal"))
        else:
            ff = shape["ff"]
            spec.update(W_gate=((d, ff), "normal"), W_up=((d, ff), "normal"),
                        W_down=((ff, d), "normal"))
        out[g] = spec
    return out


def weights_from_key(shape: dict, key):
    """The tree of lazy leaves (jit-safe: ``key`` may be traced):
    matrices, tables, the selection bias ``c_e`` and the sink ``b_n``
    N(0, 0.02), gains 1 + N(0, 0.02)."""
    n = iter(range(10_000))
    sub = lambda: jax.random.fold_in(key, next(n))
    std = shape.get("init_std", 0.02)
    counts = {g: 0 for g in GROUPS}
    for g, _ in layer_kinds(shape):
        counts[g] += 1
    tree = {"emb": {"W": Leaf(sub(), (shape["vocab"], shape["d"]), "normal",
                              std=std)}}
    for g in GROUPS:                    # a fixed order of the keys' folds
        if counts[g]:
            tree[g] = {name: Leaf(sub(), inner, recipe, counts[g], std=std)
                       for name, (inner, recipe)
                       in sorted(leaf_specs(shape)[g].items())}
    tree["head"] = {"g": Leaf(sub(), (shape["d"],), "gain", std=std),
                    "W": Leaf(sub(), (shape["vocab"], shape["d"]), "normal",
                              std=std)}
    return tree


# ---------------------------------------------------------------------------
# forward, one sequence x [t, d]
# ---------------------------------------------------------------------------
def _ops(quant):
    if quant not in QUANTS:
        raise ValueError(f"unknown control precision {quant!r}")
    return _q8 if quant == "fp8" else (lambda x: x)


def rms_norm(x, g, eps):
    return g * x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def rotary(x, rotary_dim: int, theta: float):
    """x [t, heads, dim], row i at position i: lanes j and j + rotary / 2
    (j < rotary / 2) turn by ``i * theta ** (-2 j / rotary)``."""
    half = rotary_dim // 2
    ang = (jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None]
           * theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32)
                       / rotary_dim))
    x1, x2 = x[..., :half], x[..., half:rotary_dim]
    return jnp.concatenate(
        [x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
         x2 * jnp.cos(ang) + x1 * jnp.sin(ang), x[..., rotary_dim:]], axis=-1)


def attention_mixer(p, n, q, a: dict):
    """``a``: heads, kv_heads, qk_dim, v_dim, rotary, theta, window
    (None: full), value_scale."""
    t, H, hkv = n.shape[0], a["heads"], a["kv_heads"]
    dk, dv, g = a["qk_dim"], a["v_dim"], a["heads"] // a["kv_heads"]
    qs = (q(n) @ q(p["Wq"])).reshape(t, H, dk)
    ks = (q(n) @ q(p["Wk"])).reshape(t, hkv, dk)
    vs = a["value_scale"] * (q(n) @ q(p["Wv"])).reshape(t, hkv, dv)
    qs, ks = (rotary(x, a["rotary"], a["theta"]) for x in (qs, ks))
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    seen = j <= i
    if a["window"] is not None:
        seen = seen & (j > i - a["window"])
    sink = (p["sink"].reshape(hkv, g) if "sink" in p
            else jnp.full((hkv, g), -jnp.inf))

    def one_kv_head(xs):                   # the g query heads on it
        qh, kh, vh, b = xs                 # [t, g, dk] [t, dk] [t, dv] [g]
        s = jnp.einsum("qgd,kd->gqk", q(qh), q(kh)) / np.sqrt(dk)
        s = jnp.where(seen[None], s, -jnp.inf)
        m = jnp.maximum(jnp.max(s, axis=-1), b[:, None])       # [g, t]
        e = jnp.exp(s - m[..., None])
        w = e / (jnp.exp(b[:, None] - m) + jnp.sum(e, axis=-1))[..., None]
        return jnp.einsum("gqk,kd->qgd", q(w), q(vh))          # [t, g, dv]

    ctx = jax.lax.map(one_kv_head, (
        qs.reshape(t, hkv, g, dk).transpose(1, 0, 2, 3),
        ks.transpose(1, 0, 2), vs.transpose(1, 0, 2), sink))
    ctx = ctx.transpose(1, 0, 2, 3).reshape(t, H * dv)
    return q(ctx) @ q(p["Wo"])


def routed_ffn(p, n, q, top_k: int, first: int):
    """This share's part of the routed feed-forward: the experts
    ``first .. first + held`` of the ``top_k`` each token selected."""
    r = jax.nn.sigmoid(q(n) @ q(p["W_router"]))                # [t, E]
    _, sel = jax.lax.top_k(r + p["e_bias"], top_k)
    picked = jnp.take_along_axis(r, sel, axis=-1)
    w = picked / jnp.sum(picked, axis=-1, keepdims=True)       # [t, k]

    def one_expert(xs):
        wg, wu, wd, e = xs
        mine = jnp.sum(jnp.where(sel == first + e, w, 0.0), axis=-1)
        out = q(silu(q(n) @ q(wg)) * (q(n) @ q(wu))) @ q(wd)
        return mine[:, None] * out

    held = p["W_gate"].shape[0]
    return jnp.sum(jax.lax.map(one_expert, (
        p["W_gate"], p["W_up"], p["W_down"], jnp.arange(held))), axis=0)


@functools.partial(jax.jit, static_argnames=("attn", "eps", "top_k", "first",
                                             "quant"))
def layer_forward(p, x, attn, eps, top_k, first, quant):
    """``attn``: ``attention_mixer``'s ``a`` as a sorted tuple of items."""
    q = _ops(quant)
    x = x + attention_mixer(p, rms_norm(x, p["norm1"], eps), q, dict(attn))
    n = rms_norm(x, p["norm2"], eps)
    if "W_router" in p:
        return x + routed_ffn(p, n, q, top_k, first)
    return x + q(silu(q(n) @ q(p["W_gate"])) * (q(n) @ q(p["W_up"]))) \
        @ q(p["W_down"])


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _logits(g, table, x, eps, quant):
    q = _ops(quant)
    return q(rms_norm(x, g, eps)) @ q(table).T


def attention_of(shape: dict, group: str) -> tuple:
    win = group.startswith("win")
    return tuple(sorted({
        "heads": shape["heads"],
        "kv_heads": shape["win_kv_heads" if win else "kv_heads"],
        "qk_dim": shape["qk_dim"], "v_dim": shape["v_dim"],
        "rotary": shape["rotary"],
        "theta": shape["win_theta" if win else "theta"],
        "window": shape["window"] if win else None,
        "value_scale": shape["value_scale"]}.items()))


def lm_logits(w, shape: dict, ids, quants=(None,)) -> dict:
    """{quant: logits [t, vocab]} of ONE sequence ``ids`` [t], every
    layer's weights made once and used by each pass, one layer alive at
    a time."""
    with jax.default_matmul_precision("highest"):
        xs = {qt: w["emb"]["W"].whole()[jnp.asarray(ids)] for qt in quants}
        for group, l in layer_kinds(shape):
            p = layer_weights(w[group], l)
            for qt in quants:
                xs[qt] = layer_forward(
                    p, xs[qt], attention_of(shape, group), shape["eps"],
                    shape["top_k"], shape["held"][0], qt)
        g, table = w["head"]["g"].whole(), w["head"]["W"].whole()
        return {qt: _logits(g, table, x, shape["eps"], qt)
                for qt, x in xs.items()}


def served_token_gaps(w, shape: dict, seq, t0: int, quant=None):
    """``seq`` is a prompt of ``t0`` tokens followed by served tokens.
    For each served token: how far its float32 logit lies below the
    reference's best at that position (0 where they agree).  With
    ``quant`` the token judged is the one the lower precision puts
    first, not the served one."""
    seq = np.asarray(seq, np.int32)
    pad = -len(seq) % 256         # few compiled lengths; causal, so the
    ids = np.pad(seq, (0, pad))   # padding cannot reach what is read
    got = lm_logits(w, shape, ids, (None, quant) if quant else (None,))
    rows = slice(t0 - 1, len(seq) - 1)
    logits = got[None][rows]
    judged = jnp.asarray(seq[t0:])
    if quant is not None:
        judged = jnp.argmax(got[quant][rows], -1)
    best = jnp.max(logits, axis=-1)
    return np.asarray(best - jnp.take_along_axis(logits, judged[:, None],
                                                 axis=-1)[:, 0])
