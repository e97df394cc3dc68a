"""The plain reference of the gated sparse-expert decoder with window
and full attention mixed: the layer equations in straightforward float32
``jax.numpy`` at matmul precision "highest", ONE sequence at a time,
full causal attention a K/V head at a time, the routed feed-forward an
expert at a time -- no cache, no kernel, no batching.  It imports
nothing of the package and takes nothing the package has made: weights
and inputs come from the seed alone.

``x`` a token's residual row, ``l`` the layer, ``kind_l`` = full where
``pattern[l] == 0`` else sliding; ``H_l`` query heads of the layer's
kind on ``Hkv`` K/V heads, all ``dh`` wide; no biases:

1. ``n = RMSNorm(x; norm1)``; ``q = n Wq -> [H_l, dh]``, ``k = n Wk``,
   ``v = n Wv -> [Hkv, dh]``.
2. Rotary, rotate-half pairing, on the first ``rotary_l`` lanes of q and
   k at the token's position.  A plain base: ``inv_freq_i =
   theta^(-2i / rotary)``.  A YaRN base (``yarn_inv_freq``, a literal
   transcription of ``transformers``' ``_compute_yarn_parameters``):
   with ``D`` = rotary, ``extra_i = theta^(-2i/D)``, ``inter_i = extra_i
   / factor``, ``dim(r) = D ln(L0 / (2 pi r)) / (2 ln theta)``, ``low =
   floor(dim(beta_fast))``, ``high = ceil(dim(beta_slow))`` (clipped to
   ``[0, D - 1]``), ``ramp_i = clip((i - low) / (high - low), 0, 1)``,
   ``inv_freq_i = inter_i ramp_i + extra_i (1 - ramp_i)``; cos and sin
   are multiplied by ``attention_factor``.
3. ``s_ij = q_i . k_j / sqrt(dh)`` for ``j <= i`` and, in a sliding
   layer, ``i - window < j``; plain softmax; query head h reads K/V head
   ``h // (H_l / Hkv)``.
4. The gate: ``a = sigmoid(n Wg) -> [H_l]``; head h's output times
   ``a_h``; ``x = x + concat_h(.) Wo``.
5. ``m = RMSNorm(x; norm2)``.  A dense layer: ``x = x + (silu(m Wgate) *
   (m Wup)) Wdown``.  A routed layer: ``s = sigmoid(m Wr)`` over ALL the
   experts; the ``top_k`` with the largest ``s_e + b_e`` are picked;
   ``w_e = scale * s_e / sum of the picked s``; ``x = x + shared(m) +
   sum over e picked AND held of w_e expert_e(m)``, every expert and the
   shared one a SwiGLU.  ``held`` = (first, count) is the share of the
   experts computed (all of them in the configuration the benchmark
   runs; a share in the test that ties shares to the whole).
6. ``logits = RMSNorm(x; g) W_head^T``.

Departures from the published model (``Laguna-XS.2``): weights are drawn,
not trained; layers 0-4 of 40 (the configuration's ``reduced``); what
its ``config.json`` leaves unsaid is listed under ``assumed`` in the
configuration's file.

The routed experts' matrices are drawn AN EXPERT AT A TIME inside the
pass (``ExpertLeaf``: every (layer, expert) has its own fold of the
key): a routed layer whole in float32 is 3.4 GB, which does not fit
beside a serving process that ``study_lean.py`` keeps alive.

``quant="fp8"`` is the CONTROL (never the reference): every matmul
operand -- the router's and the gate's too -- rounded to float8_e4m3
under a scale per tensor, one precision below the configuration's
bfloat16.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.families.hybrid_ssm_reference import (Leaf, _draw,
                                                     layer_weights, silu)
from benchmark.families.sparse_window_reference import (GROUPS, _logits,
                                                        _ops, layer_kinds,
                                                        rms_norm)

EXPERT_LEAVES = ("W_gate", "W_up", "W_down")


class ExpertLeaf(Leaf):
    """A routed run's stacked expert matrices [layers, experts, *inner]:
    every (layer, expert) is drawn from its own fold of the key, so one
    expert can be made alone."""

    def astype(self, dtype):
        return ExpertLeaf(self.key, self.inner, self.recipe, self.layers,
                          self.casts + (jnp.dtype(dtype),), self.std)

    def expert(self, l, e):
        return draw_expert(jax.random.fold_in(self.key, l), e,
                           self.inner[1:], self.recipe, self.casts, self.std)

    def layer(self, l):
        return jax.lax.map(lambda e: self.expert(l, e),
                           jnp.arange(self.inner[0]))


def draw_expert(layer_key, e, inner, recipe, casts, std):
    a = _draw(recipe, jax.random.fold_in(layer_key, e), inner, std)
    for dt in casts:
        a = a.astype(dt)
    return a


def leaf_specs(shape: dict) -> dict:
    """{group: {leaf: (shape of one layer, recipe)}} of the groups the
    pattern has, named and laid out as the program's runs are."""
    d, dh = shape["d"], shape["qk_dim"]
    E, held = shape["experts"], shape["held"][1]
    out = {}
    for g in {g for g, _ in layer_kinds(shape)}:
        win, routed = g.startswith("win"), g.endswith("routed")
        H = shape["win_heads" if win else "heads"]
        hkv = shape["win_kv_heads" if win else "kv_heads"]
        spec = {"norm1": ((d,), "gain"), "Wq": ((d, H * dh), "normal"),
                "Wk": ((d, hkv * dh), "normal"),
                "Wv": ((d, hkv * dh), "normal"),
                "Wo": ((H * dh, d), "normal"), "norm2": ((d,), "gain")}
        if shape["gate"]:
            spec["Wg"] = ((d, H), "normal")
        if routed:
            ff, sff = shape["expert_ff"], shape["shared_ff"]
            spec.update(W_router=((d, E), "normal"), e_bias=((E,), "normal"),
                        W_gate=((held, d, ff), "normal"),
                        W_up=((held, d, ff), "normal"),
                        W_down=((held, ff, d), "normal"))
            if sff:
                spec.update(Ws_gate=((d, sff), "normal"),
                            Ws_up=((d, sff), "normal"),
                            Ws_down=((sff, d), "normal"))
        else:
            ff = shape["ff"]
            spec.update(W_gate=((d, ff), "normal"), W_up=((d, ff), "normal"),
                        W_down=((ff, d), "normal"))
        out[g] = spec
    return out


def weights_from_key(shape: dict, key):
    """The tree of lazy leaves (jit-safe: ``key`` may be traced):
    matrices, tables and the selection bias ``b`` N(0, 0.02), gains 1 +
    N(0, 0.02)."""
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
            routed = g.endswith("routed")
            tree[g] = {
                name: (ExpertLeaf if routed and name in EXPERT_LEAVES
                       else Leaf)(sub(), inner, recipe, counts[g], std=std)
                for name, (inner, recipe)
                in sorted(leaf_specs(shape)[g].items())}
    tree["head"] = {"g": Leaf(sub(), (shape["d"],), "gain", std=std),
                    "W": Leaf(sub(), (shape["vocab"], shape["d"]), "normal",
                              std=std)}
    return tree


# ---------------------------------------------------------------------------
# forward, one sequence x [t, d]
# ---------------------------------------------------------------------------
def yarn_inv_freq(D: int, base: float, factor: float, L0: float,
                  beta_fast: float, beta_slow: float):
    """``transformers``' ``_compute_yarn_parameters``, written out: the
    inverse frequencies [D / 2] in float32."""
    i = jnp.arange(D // 2, dtype=jnp.float32)
    extra = 1.0 / base ** (2.0 * i / D)
    inter = 1.0 / (factor * base ** (2.0 * i / D))
    dim = lambda r: D * math.log(L0 / (r * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(dim(beta_fast)), 0)
    high = min(math.ceil(dim(beta_slow)), D - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return inter * ramp + extra * (1.0 - ramp)


def rotary(x, rotary_dim: int, theta: float, scaling):
    """x [t, heads, dim], row i at position i: lanes j and j + rotary / 2
    (j < rotary / 2) turn by ``i * inv_freq_j``; under ``scaling`` (a
    sorted tuple of a YaRN dict's items) cos and sin carry its
    ``attention_factor``."""
    half = rotary_dim // 2
    if scaling is None:
        inv, factor = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32)
                                / rotary_dim), 1.0
    else:
        s = dict(scaling)
        inv = yarn_inv_freq(rotary_dim, theta, s["factor"],
                            s["original_max_position_embeddings"],
                            s["beta_fast"], s["beta_slow"])
        factor = s["attention_factor"]
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * inv
    cos, sin = factor * jnp.cos(ang), factor * jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:rotary_dim]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rotary_dim:]], axis=-1)


def attention_mixer(p, n, q, a: dict):
    """``a``: heads, kv_heads, dh, rotary, theta, scaling, window (None:
    full)."""
    t, H, hkv, dh = n.shape[0], a["heads"], a["kv_heads"], a["dh"]
    g = H // hkv
    qs = (q(n) @ q(p["Wq"])).reshape(t, H, dh)
    ks = (q(n) @ q(p["Wk"])).reshape(t, hkv, dh)
    vs = (q(n) @ q(p["Wv"])).reshape(t, hkv, dh)
    qs, ks = (rotary(x, a["rotary"], a["theta"], a["scaling"])
              for x in (qs, ks))
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    seen = j <= i
    if a["window"] is not None:
        seen = seen & (j > i - a["window"])

    def one_kv_head(xs):                   # the g query heads on it
        qh, kh, vh = xs                    # [t, g, dh] [t, dh] [t, dh]
        s = jnp.einsum("qgd,kd->gqk", q(qh), q(kh)) / np.sqrt(dh)
        s = jnp.where(seen[None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("gqk,kd->qgd", q(w), q(vh))          # [t, g, dh]

    ctx = jax.lax.map(one_kv_head, (
        qs.reshape(t, hkv, g, dh).transpose(1, 0, 2, 3),
        ks.transpose(1, 0, 2), vs.transpose(1, 0, 2)))
    ctx = ctx.transpose(1, 0, 2, 3).reshape(t, H, dh)
    if "Wg" in p:
        ctx = ctx * jax.nn.sigmoid(q(n) @ q(p["Wg"]))[:, :, None]
    return q(ctx.reshape(t, H * dh)) @ q(p["Wo"])


def swiglu(n, q, wg, wu, wd):
    return q(silu(q(n) @ q(wg)) * (q(n) @ q(wu))) @ q(wd)


def routed_ffn(p, n, q, top_k: int, held: tuple, scale: float, expert_of):
    """The routed feed-forward's part of the experts ``held`` = (first,
    count), an expert at a time (``expert_of(e)``: the three matrices of
    the e-th of them), and the shared expert where the layer has one."""
    s = jax.nn.sigmoid(q(n) @ q(p["W_router"]))                # [t, E]
    _, sel = jax.lax.top_k(s + p["e_bias"], top_k)
    picked = jnp.take_along_axis(s, sel, axis=-1)
    w = scale * picked / jnp.sum(picked, axis=-1, keepdims=True)  # [t, k]

    def one_expert(acc, e):
        mine = jnp.sum(jnp.where(sel == held[0] + e, w, 0.0), axis=-1)
        return acc + mine[:, None] * swiglu(n, q, *expert_of(e)), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(n),
                          jnp.arange(held[1]))
    if "Ws_gate" in p:
        out = out + swiglu(n, q, p["Ws_gate"], p["Ws_up"], p["Ws_down"])
    return out


@functools.lru_cache(maxsize=None)
def _layer_fn(attn, eps, top_k, held, scale, quant, experts):
    """The jitted forward of one layer.  ``attn``: ``attention_mixer``'s
    ``a`` as a sorted tuple of items; ``experts``: None (a dense layer),
    or how each of ``EXPERT_LEAVES`` is drawn -- ((a matrix's shape,
    recipe, casts, std), ...) -- from the layer's keys."""
    q = _ops(quant)

    def forward(p, keys, x):
        x = x + attention_mixer(p, rms_norm(x, p["norm1"], eps), q,
                                dict(attn))
        n = rms_norm(x, p["norm2"], eps)
        if experts is None:
            return x + swiglu(n, q, p["W_gate"], p["W_up"], p["W_down"])
        expert_of = lambda e: [draw_expert(keys[k], e, *how)
                               for k, how in zip(EXPERT_LEAVES, experts)]
        return x + routed_ffn(p, n, q, top_k, held, scale, expert_of)
    return jax.jit(forward)


def attention_of(shape: dict, group: str) -> tuple:
    win = group.startswith("win")
    scaling = shape["win_rope_scaling" if win else "rope_scaling"]
    return tuple(sorted({
        "heads": shape["win_heads" if win else "heads"],
        "kv_heads": shape["win_kv_heads" if win else "kv_heads"],
        "dh": shape["qk_dim"],
        "rotary": shape["win_rotary" if win else "rotary"],
        "theta": shape["win_theta" if win else "theta"],
        "scaling": None if not scaling else tuple(sorted(
            (k, v) for k, v in scaling.items() if k != "rope_type")),
        "window": shape["window"] if win else None}.items()))


def layer_forward(group: dict, l: int, x, shape: dict, name: str,
                  quant=None):
    """Layer ``l`` of the lazy ``group`` (a run kind's leaves) over x
    [t, d]: the small leaves made whole, the experts' in the pass."""
    lazy = {k: v for k, v in group.items() if isinstance(v, ExpertLeaf)}
    p = layer_weights({k: v for k, v in group.items() if k not in lazy}, l)
    experts = tuple((lazy[k].inner[1:], lazy[k].recipe, lazy[k].casts,
                     lazy[k].std) for k in EXPERT_LEAVES) if lazy else None
    keys = {k: jax.random.fold_in(v.key, l) for k, v in lazy.items()}
    return _layer_fn(attention_of(shape, name), shape["eps"], shape["top_k"],
                     tuple(shape["held"]), shape["routed_scale"], quant,
                     experts)(p, keys, x)


def lm_logits(w, shape: dict, ids, quants=(None,), rows=None) -> dict:
    """{quant: logits [t, vocab]} of ONE sequence ``ids`` [t] -- or of
    its positions ``rows`` (a slice) alone: 3,584 rows of a 100,352-row
    vocabulary are 1.4 GB a pass -- one layer alive at a time and of a
    routed layer one expert."""
    with jax.default_matmul_precision("highest"):
        xs = {qt: w["emb"]["W"].whole()[jnp.asarray(ids)] for qt in quants}
        for group, l in layer_kinds(shape):
            for qt in quants:
                xs[qt] = layer_forward(w[group], l, xs[qt], shape, group, qt)
        g, table = w["head"]["g"].whole(), w["head"]["W"].whole()
        return {qt: _logits(g, table, x if rows is None else x[rows],
                            shape["eps"], qt) for qt, x in xs.items()}


def served_token_gaps(w, shape: dict, seq, t0: int, quant=None):
    """``seq`` is a prompt of ``t0`` tokens followed by served tokens.
    For each served token: how far its float32 logit lies below the
    reference's best at that position (0 where they agree).  With
    ``quant`` the token judged is the one the lower precision puts
    first, not the served one."""
    seq = np.asarray(seq, np.int32)
    pad = -len(seq) % 256         # few compiled lengths; causal, so the
    ids = np.pad(seq, (0, pad))   # padding cannot reach what is read
    got = lm_logits(w, shape, ids, (None, quant) if quant else (None,),
                    slice(t0 - 1, len(seq) - 1))
    logits = got[None]
    judged = jnp.asarray(seq[t0:])
    if quant is not None:
        judged = jnp.argmax(got[quant], -1)
    best = jnp.max(logits, axis=-1)
    return np.asarray(best - jnp.take_along_axis(logits, judged[:, None],
                                                 axis=-1)[:, 0])
