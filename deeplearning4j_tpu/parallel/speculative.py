"""Speculative multi-token decode — draft construction + acceptance.

The decode tick is memory-bound: every single-token dispatch streams
the full parameter set from HBM for ONE token of math per slot.
Speculative sampling (Leviathan et al. / Chen et al., PAPERS.md)
converts K cheap DRAFT steps plus ONE batched target-model
verification into up to K+1 committed tokens per expensive target
pass — the verification processes K+1 token positions at matmul rate
(one params read amortized over the chunk) instead of K+1
params-bandwidth-bound single-token ticks.

The greedy round (``GenerationServer`` with ``speculative=``):

1. **anchor** — the target's held logits already determine the next
   token with certainty (``argmax``); no draft needed for it.
2. **draft** — starting from the anchor, the draft model runs K
   single-token steps through ITS OWN paged KV (the slot's ``dtable``
   blocks — ordinary pool blocks holding the first ``draft.n_layers``
   layers of the pool leaves), proposing tokens p_1..p_K by argmax.
3. **verify** — ONE batched target forward over the W = K+1 tokens
   [anchor, p_1..p_K] at positions pos..pos+K, writing target KV
   through the slot's block table and producing target logits
   G_0..G_K (``TransformerGenerator._verify_rows_paged``).
4. **accept** — :func:`accept_greedy`: p_i commits iff it equals the
   target's own argmax g_{i-1} AND every earlier proposal matched;
   the committed count is cut at the first EOS and clamped to the
   slot's remaining budget.  Held logits become G_{c-1}, so the NEXT
   round's anchor is the target's correction (on a mismatch) or its
   bonus token (on a full accept) — every committed token is the
   argmax of target logits over the committed prefix, which is what
   makes speculative greedy decode BYTE-IDENTICAL to non-speculative
   decode at every acceptance pattern.  Rejected-suffix KV writes are
   rolled back by simply not advancing ``pos`` past the commit point:
   the slot's blocks are claimed up front at admission (the PR 7
   contract), so rollback reuses them in place — the next round's
   verify overwrites the rejected rows and the ``col <= pos`` mask
   hides them meanwhile.

Draft quality affects only the acceptance RATE, never correctness:
the verify recomputes every committed token with the target model, so
a stale or even garbage draft degrades to ~1 token per round (the
anchor), not to wrong bytes.

The default draft is a SELF-DRAFT: the target truncated to its first
``draft_layers`` blocks, sharing the target's embedding and head
params (:func:`make_self_draft` — zero extra weights, and layer i of
a causal stack depends only on layers < i, so the truncation is a
well-formed cheaper decoder).  ``draft_net=`` swaps in an
independently trained proposer (:func:`make_draft`) whose geometry
must fit the pool (same vocab / heads / head dim, depth <= target).
Either way the draft's KV blocks come from the SAME pool the target's
do — draft blocks compete in the same admission/LRU economy, an
admission with speculation on claims roughly 2x the blocks, and a
retiring slot drains both tables through the one allocator.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.models.generation import (TransformerGenerator,
                                                  _cast_floating)


class DraftModel:
    """The draft side of a speculative server: ``gen`` supplies the
    layer math (``_step_paged`` / ``_prefill_rows`` over however many
    layers its stacked params hold), ``n_layers`` is the draft depth —
    the slice of the pool leaves its KV occupies — and :meth:`params`
    derives the draft's (emb, stacked runs, head) from the server's
    refreshed target params (a self-draft slices them; an external
    draft snapshots its own net)."""

    def __init__(self, gen: TransformerGenerator, n_layers: int,
                 params_fn):
        self.gen = gen
        self.n_layers = int(n_layers)
        self._params_fn = params_fn

    def params(self, target_params):
        """(emb_p, blk_stack, head_p) for the draft, derived from the
        target's CURRENT serving params — called from
        ``GenerationServer.refresh_params`` so a weight refresh
        refreshes the draft too."""
        return self._params_fn(target_params)

    def check_tp(self, tp: int) -> None:
        """Validate the draft's geometry against a mesh-sharded
        replica's tp degree (ISSUE 17): the draft's K/V rows land in
        the SAME head-sharded pool leaves the target's do, so its head
        count must split the same way — a self-draft inherits the
        target's heads and passes trivially, but an external draft
        with an incompatible head count must fail at construction, not
        as a GSPMD error mid-admission."""
        h = self.gen.kv_heads
        if tp > 1 and h % tp:
            raise ValueError(
                f"draft n_heads={h} must divide by tp={tp} (draft KV "
                "shares the head-sharded pool)")


def make_self_draft(gen: TransformerGenerator,
                    draft_layers: Optional[int] = None) -> DraftModel:
    """Truncated-target self-draft: the first ``draft_layers`` blocks
    of the target (default: half the stack, min 1) with the target's
    own embedding and head.  Costs ``draft_layers / n_layers`` of a
    target step per proposal and needs no extra weights; its params
    are SLICES of the server's cast target params, so a
    ``refresh_params`` refreshes both for free."""
    n = gen.kv_layers
    d = max(1, n // 2) if draft_layers is None else int(draft_layers)
    if not 1 <= d <= n:
        raise ValueError(
            f"draft_layers={d} out of range [1, {n}] (the self-draft "
            "truncates the target's own stack)")

    def params_fn(target_params):
        # the target's buffers VERBATIM — the consuming programs take
        # the [:n_layers] slice INSIDE jit (free, fused by XLA), so a
        # self-draft really is zero extra device memory; slicing here
        # would materialize a duplicate of the first d layers' params
        # for the server's lifetime
        return target_params

    return DraftModel(gen, d, params_fn)


def make_draft(gen: TransformerGenerator, draft_net) -> DraftModel:
    """External draft model (an independently trained small decoder).
    Geometry must fit the target's pool: same vocab (proposals index
    target logits), same head count and head dim (draft K/V rows land
    in the same pool leaves), and depth <= the target's (the draft
    occupies the first ``n_layers`` pool layers)."""
    dgen = TransformerGenerator(
        draft_net, compute_dtype=np.dtype(gen.compute_dtype).name)
    d = dgen.kv_layers
    if d > gen.kv_layers:
        raise ValueError(
            f"draft depth {d} exceeds the target's {gen.kv_layers} "
            "(draft KV lives in the first layers of the target's pool)")
    if dgen.kv_heads != gen.kv_heads:
        raise ValueError(
            f"draft n_heads {dgen.kv_heads} != target "
            f"{gen.kv_heads} (pool K/V layout is per-head)")
    if dgen.emb.n_out != gen.emb.n_out:
        raise ValueError(
            f"draft d_model {dgen.emb.n_out} != target {gen.emb.n_out} "
            "(pool K/V rows are [h, dh])")
    v_t, v_d = gen.vocab_size, dgen.vocab_size
    if v_d != v_t:
        raise ValueError(f"draft vocab {v_d} != target vocab {v_t} "
                         "(proposals must index target logits)")

    def params_fn(_target_params):
        emb_p, blk_ps, head_p = dgen._params()
        return _cast_floating(
            (emb_p, dgen._stack_blocks(blk_ps), head_p),
            dgen.compute_dtype)

    return DraftModel(dgen, d, params_fn)


class SpecConfig:
    """Parsed ``GenerationServer(speculative={...})`` config: ``k``
    draft proposals per round (the verification width is k+1),
    ``rounds`` — the max rounds fused into one dispatch (the scan-
    length analogue of ``tick_batch``; adaptive, pow2-quantized), the
    :class:`DraftModel`, and the adaptive-K knobs: ``adaptive=True``
    lets the :class:`AcceptanceController` pick each dispatch's draft
    depth within ``[1, k_max]`` (``k_max`` defaults to ``k``; ``k``
    stays the fixed depth when adaptive is off)."""

    def __init__(self, k: int, rounds: int, draft: DraftModel,
                 adaptive: bool = False, k_max: Optional[int] = None):
        self.k = int(k)
        self.rounds = int(rounds)
        self.draft = draft
        self.adaptive = bool(adaptive)
        self.k_max = self.k if k_max is None else int(k_max)
        if self.k < 1:
            raise ValueError("speculative k must be >= 1")
        if self.rounds < 1:
            raise ValueError("speculative rounds must be >= 1")
        if self.k_max < self.k:
            raise ValueError(
                f"speculative k_max={self.k_max} must be >= k={self.k} "
                "(k is the fixed/startup depth; the controller adapts "
                "within [1, k_max])")

    @classmethod
    def build(cls, gen: TransformerGenerator,
              spec: dict) -> "SpecConfig":
        spec = dict(spec)
        unknown = set(spec) - {"k", "rounds", "draft_layers",
                               "draft_net", "adaptive", "k_max"}
        if unknown:
            raise ValueError(
                f"unknown speculative key(s) {sorted(unknown)} "
                "(expected k / rounds / draft_layers / draft_net / "
                "adaptive / k_max)")
        draft_net = spec.get("draft_net")
        if draft_net is not None:
            if spec.get("draft_layers") is not None:
                raise ValueError("draft_layers applies to the "
                                 "self-draft; draft_net brings its "
                                 "own depth")
            draft = make_draft(gen, draft_net)
        else:
            draft = make_self_draft(gen, spec.get("draft_layers"))
        return cls(spec.get("k", 4), spec.get("rounds", 2), draft,
                   adaptive=spec.get("adaptive", False),
                   k_max=spec.get("k_max"))


def accept_greedy(v, g, active, remaining, eos, kcap=None):
    """The greedy acceptance rule on one verified chunk.

    ``v`` [B, W] — the verified tokens (anchor + K proposals);
    ``g`` [B, W] — the target's own argmax after each of them
    (``g[:, j] = argmax(G_j)``); ``active`` [B] bool; ``remaining``
    [B] int32 budgets; ``eos`` [B] int32 (-1 disables); ``kcap``
    [B] int32 (optional) — a per-slot draft-depth cap from the
    acceptance controller: proposals at index >= kcap[b] were never
    drafted for slot b (the dispatch runs at the pool-max K), so they
    can never commit.

    Returns ``(commit, remaining_after)``: ``commit[b]`` tokens
    ``v[b, :commit[b]]`` are byte-identical to what non-speculative
    greedy decode would have emitted — the anchor always commits,
    proposal p_i commits iff it matches g_{i-1} and every earlier
    proposal matched (one mismatch invalidates every later position's
    context), the count is clamped to the remaining budget, and a
    committed EOS cuts the run the way the non-speculative tick's
    ``hit_eos`` does (``remaining_after`` drops to 0)."""
    W = v.shape[1]
    match = (v[:, 1:] == g[:, :-1]).astype(jnp.int32)       # [B, K]
    if kcap is not None:
        match = jnp.where(
            jnp.arange(W - 1)[None, :] < kcap[:, None], match, 0)
    a = jnp.sum(jnp.cumprod(match, axis=1), axis=1)         # leading 1s
    c = jnp.minimum(1 + a, remaining)
    idx = jnp.arange(W)[None, :]
    hit = ((v == eos[:, None]) & (eos[:, None] >= 0)
           & (idx < c[:, None]))
    any_hit = jnp.any(hit, axis=1)
    first = jnp.argmax(hit, axis=1)
    c = jnp.where(any_hit, first + 1, c)
    rem_after = jnp.where(any_hit, 0, remaining - c)
    c = jnp.where(active, c, 0)
    rem_after = jnp.where(active, rem_after, remaining)
    return c.astype(jnp.int32), rem_after.astype(jnp.int32)


def accept_sampled(v, logp, logq, u, active, remaining, eos,
                   kcap=None):
    """Rejection-sampling acceptance (Leviathan et al. / Chen et al.)
    on one verified chunk — the sampled-slot analogue of
    :func:`accept_greedy`, preserving the EXACT target sampling
    distribution.

    ``v`` [B, W] — verified tokens (anchor + K proposals); ``logp`` /
    ``logq`` [B, K] — log-probability of proposal p_{i+1} under the
    TARGET's and the DRAFT's filtered sampling distribution at its
    position; ``u`` [B, K] — per-proposal uniforms from the slot's own
    PRNG; ``active`` / ``remaining`` / ``eos`` / ``kcap`` as in
    :func:`accept_greedy`.

    Proposal i is accepted with probability
    ``min(1, p_target(x_i) / p_draft(x_i))`` — i.e. iff
    ``u_i < exp(min(0, logp_i - logq_i))`` — and only while every
    earlier proposal was accepted.  The anchor always commits (it was
    drawn from the target's own held distribution).  Returns
    ``(commit, remaining_after, n_eval, rejected)``: ``n_eval[b]`` is
    how many proposals were actually evaluated for slot b (the
    per-slot proposed count — capped by kcap and by the remaining
    budget), and ``rejected[b]`` marks slots whose run ended at a
    genuine rejection (not budget / EOS exhaustion): those slots'
    NEXT token must come from the normalized residual
    ``max(0, p_target - p_draft)`` (:func:`residual_logits`), which
    the caller holds as the slot's next-anchor distribution."""
    B, W = v.shape
    K = W - 1
    n_eval = jnp.clip(jnp.minimum(K, remaining - 1), 0, K)
    if kcap is not None:
        n_eval = jnp.minimum(n_eval, jnp.clip(kcap, 0, K))
    idx = jnp.arange(K)[None, :]
    ok = (u < jnp.exp(jnp.minimum(logp - logq, 0.0)))
    ok = ok & (idx < n_eval[:, None])
    a = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1)
    rejected = a < n_eval
    c = jnp.minimum(1 + a, remaining)
    widx = jnp.arange(W)[None, :]
    hit = ((v == eos[:, None]) & (eos[:, None] >= 0)
           & (widx < c[:, None]))
    any_hit = jnp.any(hit, axis=1)
    first = jnp.argmax(hit, axis=1)
    c = jnp.where(any_hit, first + 1, c)
    rem_after = jnp.where(any_hit, 0, remaining - c)
    rejected = rejected & ~any_hit & (rem_after > 0) & active
    c = jnp.where(active, c, 0)
    rem_after = jnp.where(active, rem_after, remaining)
    return (c.astype(jnp.int32), rem_after.astype(jnp.int32),
            jnp.where(active, n_eval, 0).astype(jnp.int32), rejected)


def accept_mixed(greedy_row, v, g, logp, logq, u, active, remaining,
                 eos, kcap=None):
    """Per-row dispatch between the two acceptance rules for a MIXED
    pool (greedy + sampled slots in one tick).  ``greedy_row`` [B]
    bool selects :func:`accept_greedy` rows — their commit counts are
    computed by the identical greedy rule, so greedy slots stay
    byte-identical to non-speculative decode regardless of what the
    sampled slots in the same dispatch do.  Returns ``(commit,
    remaining_after, n_eval, rejected)`` with ``rejected`` always
    False on greedy rows (a greedy mismatch is corrected by the next
    anchor's argmax, not a residual draw)."""
    cg, rg = accept_greedy(v, g, active, remaining, eos, kcap=kcap)
    cs, rs, n_eval, rej = accept_sampled(
        v, logp, logq, u, active, remaining, eos, kcap=kcap)
    c = jnp.where(greedy_row, cg, cs)
    rem_after = jnp.where(greedy_row, rg, rs)
    return c, rem_after, n_eval, rej & ~greedy_row


def residual_logits(logp_t, logq_d):
    """Log of the normalized rejection residual
    ``max(0, p_target - p_draft)`` — the distribution a rejected
    position's replacement token must be drawn from for the committed
    stream to stay exactly target-distributed.  ``logp_t`` / ``logq_d``
    [..., V] log-probabilities of the two FILTERED sampling
    distributions at the rejected position.  Returned as UNNORMALIZED
    log-weights (-inf where the residual is zero) — a categorical draw
    normalizes implicitly.  Degenerate case p_target <= p_draft
    everywhere (numerically possible only when the dists coincide,
    where rejection has probability ~0) falls back to the target
    distribution."""
    diff = jnp.exp(logp_t) - jnp.exp(logq_d)
    pos = diff > 0.0
    res = jnp.where(pos, jnp.log(jnp.where(pos, diff, 1.0)), -jnp.inf)
    return jnp.where(jnp.any(pos, axis=-1, keepdims=True), res, logp_t)


class AcceptanceController:
    """Self-tuning draft depth from observed acceptance.

    Keeps a per-key EWMA of the per-proposal acceptance probability
    ``alpha`` (key = whatever the server hashes a slot to — tenant +
    leading prefix block in practice) plus a global aggregate, and
    picks the draft depth k in ``[1, k_max]`` maximizing the expected
    speedup of a spec round,

        E(tokens | k) / cost(k)  with  E = (1 - a^(k+1)) / (1 - a),
        cost = k * draft_cost + 1

    — the classic speculative-decode throughput model (draft_cost =
    draft step cost as a fraction of a target step, e.g.
    ``draft_layers / n_layers`` for a self-draft; the +1 is the
    batched verify, which runs at ~one target step regardless of k).

    Cold keys fall back to the global EWMA; a cold GLOBAL seeds itself
    from the ``generation_server_spec_{proposed,accepted}_total``
    counter history when a :class:`~..telemetry.tsdb.TimeSeriesStore`
    is attached (the PR 16 recorder beacons them), and to ``k_max``
    (optimistic — misprediction costs one round of drafting, while a
    timid start forfeits real speedup) when there is no history at
    all.  Purely host-side: observations arrive from the dispatch's
    host-sync path, decisions feed the NEXT dispatch — nothing here
    touches the compiled programs."""

    SERIES_PROPOSED = "generation_server_spec_proposed_total"
    SERIES_ACCEPTED = "generation_server_spec_accepted_total"

    def __init__(self, k_max: int, draft_cost: float,
                 ewma: float = 0.2, min_obs: int = 32,
                 store=None, window_s: float = 120.0):
        if not 1 <= int(k_max):
            raise ValueError("k_max must be >= 1")
        self.k_max = int(k_max)
        self.draft_cost = max(1e-3, float(draft_cost))
        self.ewma = float(ewma)
        self.min_obs = int(min_obs)
        self.window_s = float(window_s)
        self._store = store
        self._keys = {}          # key -> [alpha, n_proposed]
        self._global = None      # alpha
        self._global_n = 0
        import threading
        self._lock = threading.Lock()

    def attach_store(self, store) -> None:
        with self._lock:
            self._store = store

    def reset(self) -> None:
        """Drop all acceptance state, returning every key to the
        optimistic cold start (bench/ops hook — e.g. pinning
        ``k_for`` to the degrade cap so each depth's compiled
        program can be warmed deterministically)."""
        with self._lock:
            self._keys.clear()
            self._global = None
            self._global_n = 0

    def observe(self, key, proposed: int, accepted: int) -> None:
        """Fold one slot-round observation in.  ``proposed`` counts
        only genuinely evaluated proposals (n_eval), so budget/EOS
        truncation never reads as rejection."""
        proposed = int(proposed)
        if proposed <= 0:
            return
        r = min(1.0, max(0.0, int(accepted) / proposed))
        with self._lock:
            ent = self._keys.get(key)
            if ent is None:
                self._keys[key] = [r, proposed]
            else:
                ent[0] += self.ewma * (r - ent[0])
                ent[1] += proposed
            if self._global is None:
                self._global = r
            else:
                self._global += self.ewma * (r - self._global)
            self._global_n += proposed

    def rate(self, key) -> Optional[float]:
        """Best current acceptance estimate for ``key`` (per-key when
        warm, else global, else TSDB-seeded, else None)."""
        with self._lock:
            ent = self._keys.get(key)
            if ent is not None and ent[1] >= self.min_obs:
                return ent[0]
            if self._global_n >= self.min_obs:
                return self._global
            store = self._store
        seeded = self._store_rate(store)
        if seeded is not None:
            return seeded
        with self._lock:
            if ent is not None:
                return ent[0]
            return self._global

    def k_for(self, key, cap: Optional[int] = None) -> int:
        """Draft depth for the next round touching ``key``, within
        ``[1, min(k_max, cap)]`` (``cap`` is the degrade ladder's
        ``shrink_draft_k`` rung talking)."""
        hi = self.k_max if cap is None else max(1, min(self.k_max,
                                                       int(cap)))
        a = self.rate(key)
        if a is None:
            return hi
        return self._best_k(a, hi)

    def _best_k(self, alpha: float, hi: int) -> int:
        a = min(0.98, max(0.0, float(alpha)))
        best_k, best_s = 1, -1.0
        for k in range(1, hi + 1):
            e = (1.0 - a ** (k + 1)) / (1.0 - a)
            s = e / (k * self.draft_cost + 1.0)
            if s > best_s + 1e-12:
                best_k, best_s = k, s
        return best_k

    def _store_rate(self, store) -> Optional[float]:
        if store is None:
            return None
        try:
            import time as _time
            now = _time.time()
            rp = store.rate(self.SERIES_PROPOSED,
                            now - self.window_s, now)
            ra = store.rate(self.SERIES_ACCEPTED,
                            now - self.window_s, now)
        except Exception:
            return None
        if not rp or ra is None:
            return None
        return min(1.0, max(0.0, ra / rp))

    def snapshot(self) -> dict:
        """Controller introspection for ``stats()`` / debugging."""
        with self._lock:
            return {
                "keys": len(self._keys),
                "global_rate": self._global,
                "global_proposed": self._global_n,
            }
