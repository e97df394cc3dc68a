#!/usr/bin/env python
"""Benchmark entry point — prints ONE JSON line:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "secondary": [...]}

Flagship benchmarks:
  1. ResNet-50 ImageNet-shape training throughput (images/sec) vs the
     BASELINE.json north-star bar (0.9x nd4j-cuda on a V100).
  2. BERT-base training (b=32, t=512, bf16, Pallas flash attention in
     the hot path) — tokens/sec + MFU, reported as a secondary metric
     (BASELINE config 4 is a BERT fine-tune; the reference has no
     published transformer number, so vs_baseline is MFU/0.40 — the
     "40% MFU is the right bar" line from ROOFLINE.md).

Timing protocol: every training benchmark here rotates its input
buffers, runs >= 50 steps per window and ends the window with a scalar
readback (float(loss)), so a window covers device completion and not
just the enqueue.

A bench function that raises fails the run: nothing here substitutes
another model or folds an error into the result.  The MFU denominator
is ``runtime.backend.peak_flops()`` — the attached device's entry in
the one peaks table — so a run on a device without an entry fails
before it measures anything.
"""
import json
import sys
import time

import numpy as np

from deeplearning4j_tpu.runtime.backend import (enable_compile_cache,
                                                peak_flops)

# Baseline derivation (no in-tree reference numbers exist — BASELINE.md
# records `published: {}` and the reference mount is empty):
# BASELINE.json's north star is ">=0.9x nd4j-cuda images/sec/chip" on a
# V100.  DL4J's cuDNN helper path trains fp32 only (no AMP/loss-scaling
# support in the reference), and MLPerf-v0.5-era fp32 ResNet-50 V100
# implementations cluster at 340-380 img/s (e.g. the published
# tensorflow_benchmarks fp32 numbers; DL4J's own JavaCPP pipeline sits at
# or below that envelope).  We pin the optimistic end, 360 img/s; the bar
# is 0.9x that.  For scale: V100 *mixed-precision* SOTA was ~1450 img/s —
# our bf16 number beats that too (see ROOFLINE.md).
V100_RESNET50_IMG_PER_SEC = 360.0
BASELINE_TARGET = 0.9 * V100_RESNET50_IMG_PER_SEC

# MFU accounting: ResNet-50 forward ≈ 4.1 GFLOP/img at 224x224 (2 FLOP per
# MAC); training fwd+bwd ≈ 3x forward ≈ 12.3 GFLOP/img, against the
# attached chip's bf16 peak (runtime.backend.PEAK_BF16_FLOPS).  ResNet-50
# training is HBM-bandwidth-bound, not MXU-bound (see ROOFLINE.md for
# the measured per-op breakdown).
TRAIN_GFLOP_PER_IMG = 12.3

N_STEPS = 60
N_INPUT_BUFFERS = 4
N_TRIALS = 3  # variance bands on every headline number (review rec 8)


def _trials(window):
    """Run a timed measurement window N_TRIALS times against the SAME
    compiled state (compile/warm-up happened before the first call) and
    return (mean, sigma, per-trial values).  sigma is the population
    std-dev of the trial means — the variance band that decides whether
    two PRs' headline numbers actually differ (a single-trial headline
    can't see run-to-run spread of one executable)."""
    vals = [float(window()) for _ in range(N_TRIALS)]
    mean = sum(vals) / len(vals)
    sigma = (sum((v - mean) ** 2 for v in vals) / len(vals)) ** 0.5
    return mean, sigma, [round(v, 2) for v in vals]


def bench_resnet50():
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.zoo.resnet import ResNet50

    batch = 256  # measured sweet spot on v5e (64/128/256/512 swept)
    model = ResNet50(n_classes=1000, input_shape=(224, 224, 3)).init_graph()
    rng = np.random.default_rng(0)
    xs = [jnp.asarray(rng.normal(size=(batch, 224, 224, 3)), jnp.bfloat16)
          for _ in range(N_INPUT_BUFFERS)]
    y = jnp.asarray(np.eye(1000, dtype=np.float32)[
        rng.integers(0, 1000, batch)])
    step = model.compiled_train_step()
    state = step.init()
    state, loss = step(state, xs[0], y)
    float(loss)  # compile + drain

    def window():
        nonlocal state
        t0 = time.perf_counter()
        for i in range(N_STEPS):
            state, loss = step(state, xs[i % N_INPUT_BUFFERS], y)
        float(loss)  # hard sync
        return batch * N_STEPS / (time.perf_counter() - t0)

    ips, sigma, vals = _trials(window)
    mfu = ips * TRAIN_GFLOP_PER_IMG * 1e9 / peak_flops()
    return {"metric": "resnet50_train_throughput", "value": round(ips, 2),
            "sigma": round(sigma, 2), "n_trials": N_TRIALS,
            "trial_values": vals,
            "unit": "images/sec", "vs_baseline": round(ips / BASELINE_TARGET, 4),
            "mfu": round(mfu, 4), "batch": batch}


def bench_bert():
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.zoo.bert import Bert

    if jax.default_backend() not in ("tpu",):
        # 61 BERT-base steps with the flash kernel in Pallas interpret
        # mode would take hours on CPU — the secondary bench is
        # TPU-only by design.
        raise RuntimeError("bert bench requires a TPU backend")

    batch, t = 32, 512  # measured sweet spot (t=512 engages flash)
    m = Bert(seq_len=t)
    net = m.init_graph()
    net._build_solver()
    rng = np.random.default_rng(0)
    xs = [jnp.asarray(rng.integers(0, m.vocab_size, (batch, t)), jnp.int32)
          for _ in range(N_INPUT_BUFFERS)]
    y = jnp.asarray(np.eye(2, dtype=np.float32)[rng.integers(0, 2, batch)])

    def step(x):
        b = {"features": x, "labels": y}
        (net.params_tree, net.opt_state, net.state_tree, loss
         ) = net._solver.step(net.params_tree, net.opt_state,
                              net.state_tree, net.iteration_count, b,
                              net._rng.next_key())
        net.iteration_count += 1
        return loss

    float(step(xs[0]))  # compile + drain

    def window():
        t0 = time.perf_counter()
        for i in range(N_STEPS):
            loss = step(xs[i % N_INPUT_BUFFERS])
        float(loss)  # hard sync
        return batch * t * N_STEPS / (time.perf_counter() - t0)

    tok_s, sigma, vals = _trials(window)
    mfu = tok_s * m.flops_per_token_train() / peak_flops()
    return {"metric": "bert_base_train_throughput",
            "value": round(tok_s, 1), "sigma": round(sigma, 1),
            "n_trials": N_TRIALS, "trial_values": vals,
            "unit": "tokens/sec",
            "vs_baseline": round(mfu / 0.40, 4),  # 40% MFU bar
            "mfu": round(mfu, 4), "batch": batch, "seq_len": t,
            "flash_attention": True}


def bench_bert_imported(n_epochs: int = 60):
    """BASELINE config 4 ON SILICON: import the frozen BERT-base pb
    (the same ~438 MB artifact the parity tests use), fuse attention,
    attach the SST-2-style 2-class head, and fine-tune at b=40/t=512 in
    bf16 AMP — with the Pallas flash kernel VERIFIABLY in the train
    trace (route-taken probe, not _flash_applicable's opinion).

    r5 (round-4 review item 3): trains on REAL data — the hand-written
    tiny-sentiment corpus (238 train / 80 held-out sentences through
    WordPiece -> BertIterator) — and reports a held-out accuracy
    trajectory, not a random-token memorization curve.  Throughput is
    still timed over the first N_STEPS optimizer steps at the config-4
    geometry.  MFU note: flops_per_token_train() is the zoo-Bert
    analytic count used as a proxy for the imported graph (within ~2%
    — same backbone, different head), and tokens/sec counts PADDED
    tokens (the [b, t] geometry the chip actually processes; the
    corpus sentences occupy <= 16 of the 512 positions)."""
    import jax
    import jax.numpy as jnp
    if jax.default_backend() not in ("tpu",):
        raise RuntimeError("imported-bert bench requires a TPU backend")
    from deeplearning4j_tpu.autodiff import TrainingConfig
    from deeplearning4j_tpu.autodiff.rewrites import optimize_for_tpu
    from deeplearning4j_tpu.autodiff.tf_import import import_frozen_pb
    from deeplearning4j_tpu import kernels as fa
    from deeplearning4j_tpu.data.bert_iterator import BertIterator
    from deeplearning4j_tpu.data.tiny_sentiment import (make_tokenizer,
                                                        train_test_split)
    from deeplearning4j_tpu.optimize.updaters import Adam
    from deeplearning4j_tpu.utils.bert_fixture import (
        attach_classifier_head, ensure_bert_base_fixture)
    from deeplearning4j_tpu.zoo.bert import Bert

    # b=40 is the measured sweet spot (b=32: 37.7% MFU, b=40: 41.5%,
    # b=48: 40.9%, b=64 spills HBM and collapses to 7%)
    batch, t = 40, 512
    pb, _ = ensure_bert_base_fixture(t=t)
    sd = import_frozen_pb(pb)
    counts = optimize_for_tpu(sd)   # qkv/layernorm/gelu/attention
    n_fused = counts["attention"]
    attach_classifier_head(sd)
    sd.set_training_config(TrainingConfig(
        # the canonical BERT fine-tune lr — and in bf16 it is a CLIFF,
        # not a convention: measured on this exact pipeline, 2e-5
        # reaches 0.74 held-out; 5e-5 and above collapse the random
        # backbone into uniform predictions (loss pinned at ln 2,
        # acc 0.50) within the first epochs and never recover
        updater=Adam(learning_rate=2e-5),
        data_set_feature_mapping=["i", "m", "t"],
        data_set_label_mapping=["labels"],
        compute_dtype="bfloat16"))
    feed_names = ["i", "m", "t", "labels"]
    step_fn, updater = sd._train_step_fn(feed_names)
    params = {k: jnp.asarray(v) for k, v in sd._param_values().items()}
    opt_state = updater.init_state(params)

    tok = make_tokenizer()
    train, test = train_test_split()
    np.random.default_rng(7).shuffle(train)   # mix labels per batch
    train = train + train[:2]     # 240 = 6 x b=40: batch-shape-stable jit
    def batches(examples):
        out = []
        for mds in BertIterator(tok, examples, batch, t):
            ids, mask, tt = mds.features
            out.append({
                "i": jnp.asarray(ids), "m": jnp.asarray(mask),
                "t": jnp.asarray(tt),
                "labels": jnp.asarray(mds.labels[0])})
        return out
    train_bufs = batches(train)       # 6
    test_bufs = batches(test)         # 2

    logits_fn = sd._function(["logits"], ["i", "m", "t"])
    def held_out_acc(ps):
        hits = total = 0
        for buf in test_bufs:
            lg = logits_fn(ps, {k: buf[k] for k in ("i", "m", "t")})[0]
            hits += int(jnp.sum(jnp.argmax(lg, -1)
                                == buf["labels"]))
            total += int(buf["labels"].shape[0])
        return hits / total

    acc_before = held_out_acc(params)
    fa.reset_route_log()
    params, opt_state, loss = step_fn(
        params, opt_state, jnp.asarray(0, jnp.int32), train_bufs[0])
    loss_first = float(loss)  # compile + drain
    flash_routes = sum(1 for r in fa.route_log() if r[0] == "flash")

    # throughput window: N_TRIALS x N_STEPS real optimizer steps (the
    # fine-tune continues through them — trial steps are train steps)
    steps_done = 1
    last_loss = [loss]

    def window():
        nonlocal params, opt_state, steps_done
        t0 = time.perf_counter()
        for _ in range(N_STEPS):
            params, opt_state, w_loss = step_fn(
                params, opt_state, jnp.asarray(steps_done, jnp.int32),
                train_bufs[steps_done % len(train_bufs)])
            steps_done += 1
        last_loss[0] = w_loss
        float(w_loss)  # hard sync
        return batch * t * N_STEPS / (time.perf_counter() - t0)

    tok_s, sigma, vals = _trials(window)
    loss_ts = float(last_loss[0])

    # continue to n_epochs, recording the held-out trajectory
    step = steps_done
    acc_traj = []
    epochs_done = steps_done // len(train_bufs)
    acc_traj.append({"epoch": epochs_done,
                     "acc": round(held_out_acc(params), 4)})
    for ep in range(epochs_done, n_epochs):
        for buf in train_bufs:
            params, opt_state, loss = step_fn(
                params, opt_state, jnp.asarray(step, jnp.int32), buf)
            step += 1
        if (ep + 1) % 5 == 0 or ep == n_epochs - 1:
            acc_traj.append({"epoch": ep + 1,
                             "acc": round(held_out_acc(params), 4)})
    loss_last = float(loss)
    mfu = tok_s * Bert(seq_len=t).flops_per_token_train() / peak_flops()
    return {"metric": "bert_imported_finetune_throughput",
            "value": round(tok_s, 1), "sigma": round(sigma, 1),
            "n_trials": N_TRIALS, "trial_values": vals,
            "unit": "tokens/sec",
            "vs_baseline": round(mfu / 0.40, 4),  # 40% MFU bar
            "mfu": round(mfu, 4), "batch": batch, "seq_len": t,
            "mfu_note": "zoo-Bert analytic FLOPs as proxy for the "
                        "imported graph (~2%); tokens/sec counts the "
                        "padded [b,t] geometry",
            "fused_sites": n_fused, "rewrites": counts,
            "flash_routes_traced": flash_routes,
            "data": "tiny_sentiment 238 train / 80 held-out "
                    "(hand-written, real English)",
            "acc_before": round(acc_before, 4),
            "acc_trajectory": acc_traj,
            "acc_held_out": acc_traj[-1]["acc"],
            "loss_first": round(loss_first, 4),
            "loss_after_throughput_window": round(loss_ts, 4),
            "loss_last": round(loss_last, 4)}


def bench_gpt():
    """Causal decoder flagship (round-3 review item 2): GPT-2-small-shaped
    zoo.Gpt at t=2048, bf16, the Pallas flash kernel's CAUSAL path in
    the hot loop (route-probe-verified), sparse-label LM loss."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu import kernels as fa
    from deeplearning4j_tpu.zoo.gpt import Gpt

    if jax.default_backend() not in ("tpu",):
        raise RuntimeError("gpt bench requires a TPU backend")

    batch, t = 8, 2048
    m = Gpt(seq_len=t, max_len=t)
    net = m.init_graph()
    net._build_solver()
    rng = np.random.default_rng(0)
    xs = [jnp.asarray(rng.integers(0, m.vocab_size, (batch, t)), jnp.int32)
          for _ in range(N_INPUT_BUFFERS)]
    ys = [jnp.asarray(np.roll(np.asarray(x), -1, axis=1)) for x in xs]

    def step(i):
        b = {"features": xs[i], "labels": ys[i]}
        (net.params_tree, net.opt_state, net.state_tree, loss
         ) = net._solver.step(net.params_tree, net.opt_state,
                              net.state_tree, net.iteration_count, b,
                              net._rng.next_key())
        net.iteration_count += 1
        return loss

    fa.reset_route_log()
    float(step(0))  # compile + drain
    causal_flash = sum(1 for r in fa.route_log() if r[0] == "flash")

    def window():
        t0 = time.perf_counter()
        for i in range(N_STEPS):
            loss = step(i % N_INPUT_BUFFERS)
        float(loss)  # hard sync
        return batch * t * N_STEPS / (time.perf_counter() - t0)

    tok_s, sigma, vals = _trials(window)
    mfu = tok_s * m.flops_per_token_train() / peak_flops()
    return {"metric": "gpt_causal_train_throughput",
            "value": round(tok_s, 1), "sigma": round(sigma, 1),
            "n_trials": N_TRIALS, "trial_values": vals,
            "unit": "tokens/sec",
            "vs_baseline": round(mfu / 0.40, 4),  # 40% MFU bar
            "mfu": round(mfu, 4), "batch": batch, "seq_len": t,
            "causal_flash_routes": causal_flash}


def _streams_at_fixed_hbm(pool_rows, max_len, block_size, sys_len,
                          totals):
    """Admissibility math at a FIXED KV HBM budget (``pool_rows``
    cached token rows): how many concurrent streams fit under (a) the
    stripe layout — every stream pins a whole [max_len] stripe — and
    (b) the paged layout — each stream pins ceil(total/bs) blocks with
    the shared system prompt's full blocks resident ONCE.  ``totals``
    is the mixed per-stream request length cycle (prompt + budget)."""
    stripes = pool_rows // max_len
    bs = block_size
    n_pool = pool_rows // bs
    # every FULL system-prompt block is shareable (the t0-1 hashing cap
    # applies to a whole prompt's last token, not to a shared prefix
    # that user tails always follow)
    sys_blocks = sys_len // bs               # shared, counted once
    used, blocks_streams = sys_blocks, 0
    while True:
        total = totals[blocks_streams % len(totals)]
        need = -(-total // bs) - sys_blocks  # the stream's private tail
        if used + need > n_pool:
            break
        used += need
        blocks_streams += 1
    return stripes, blocks_streams


def bench_serving_decode(streams_ladder=(1, 4, 16), n_slots=16,
                         sys_len=384, user_len=32, n_new=64,
                         block_size=16, tick_batch=8, smoke=False):
    """Paged-KV shared-prefix serve window -> SERVING_DECODE_r07.json:
    1/4/16 concurrent streams sharing ONE long system prompt (unique
    user tails), TTFT p50/p99 and aggregate tokens/s per rung, the
    cold-prefill vs prefix-hit TTFT ratio (hit prefills only the
    suffix — the shared-prefix win), and concurrent-streams-at-fixed-
    HBM for stripes vs blocks at mixed request lengths (the paging
    win: a short request pins blocks, not a [max_len] stripe, and the
    system prompt is resident once).  Acceptance bar: prefix-hit TTFT
    strictly below cold TTFT, and >= 2x concurrent streams at fixed
    HBM.  ``smoke=True`` shrinks to a tiny CPU-runnable config (the
    artifact CI records); the default geometry is the TPU run."""
    import threading

    import jax
    from deeplearning4j_tpu.parallel import GenerationServer
    from deeplearning4j_tpu.zoo.gpt import Gpt

    if smoke:
        streams_ladder = (1, 2, 4)
        n_slots, sys_len, user_len, n_new, block_size = 4, 192, 8, 8, 8
        m = Gpt(vocab_size=50, max_len=256, d_model=32, n_layers=2,
                n_heads=4, d_ff=64, seq_len=8, compute_dtype=None,
                seed=3)
        compute_dtype = None
    else:
        if jax.default_backend() not in ("tpu",):
            raise RuntimeError(
                "serving_decode bench requires a TPU backend "
                "(smoke=True for the CPU config)")
        m = Gpt(seq_len=sys_len + user_len,
                max_len=sys_len + user_len + n_new)
        compute_dtype = "bfloat16"
    net = m.init_graph()
    max_len = sys_len + user_len + n_new
    rng = np.random.default_rng(0)
    vocab = m.vocab_size

    def prompt(prefix):
        """The prefix + a fresh random user tail (each call draws a
        NEW tail off the shared rng)."""
        tail = rng.integers(0, vocab, user_len).astype(np.int32)
        return np.concatenate([prefix, tail])

    with GenerationServer(net, n_slots=n_slots, max_len=max_len,
                          compute_dtype=compute_dtype,
                          tick_batch=tick_batch,
                          block_size=block_size) as srv:
        # compile both admission paths + the scan chain on a THROWAWAY
        # prefix so the measured colds stay genuinely cold
        warm = rng.integers(0, vocab, sys_len).astype(np.int32)
        srv.submit(prompt(warm), n_new=n_new)            # miss path
        srv.submit(prompt(warm), n_new=n_new)            # hit path
        srv.submit(prompt(warm), n_new=max(n_new - 1, 1))

        # cold vs prefix-hit TTFT, median of 3 fresh prefixes each
        colds, hits = [], []
        for t in range(3):
            sysp = rng.integers(0, vocab, sys_len).astype(np.int32)
            h = srv.submit_async(prompt(sysp), n_new=n_new)
            h.result()
            colds.append(h.ttft)
            h = srv.submit_async(prompt(sysp), n_new=n_new)
            h.result()
            hits.append(h.ttft)
        ttft_cold = float(np.median(colds))
        ttft_hit = float(np.median(hits))

        # the ladder: streams concurrent callers, one shared prefix
        sysp = rng.integers(0, vocab, sys_len).astype(np.int32)
        srv.submit(prompt(sysp), n_new=2)                # seed cache
        ladder = []
        for streams in streams_ladder:
            reqs = [prompt(sysp) for _ in range(2 * streams)]
            handles = [None] * len(reqs)
            errs = []

            def caller(lo):
                try:
                    for i in range(lo, len(reqs), streams):
                        handles[i] = srv.submit_async(reqs[i],
                                                      n_new=n_new)
                        handles[i].result()
                except Exception as e:   # threads swallow otherwise
                    errs.append(e)

            t_w = time.perf_counter()
            threads = [threading.Thread(target=caller, args=(s,))
                       for s in range(streams)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            if errs:
                raise errs[0]
            dt = time.perf_counter() - t_w
            ttfts = sorted(h.ttft for h in handles)
            ladder.append({
                "streams": streams,
                "requests": len(reqs),
                "new_tokens_per_sec": round(len(reqs) * n_new / dt, 1),
                "ttft_p50_s": round(float(np.percentile(ttfts, 50)), 4),
                "ttft_p99_s": round(float(np.percentile(ttfts, 99)), 4),
            })

    # fixed-HBM admissibility: the stripe pool's rows, mixed lengths —
    # half full-budget requests, half short chat turns over the same
    # system prompt
    pool_rows = n_slots * max_len
    totals = [max_len, sys_len + user_len + max(n_new // 4, 1)]
    stripes, blocks = _streams_at_fixed_hbm(pool_rows, max_len,
                                            block_size, sys_len, totals)
    return {"metric": "serving_decode_paged_prefix",
            "value": blocks, "unit": "concurrent_streams_at_fixed_hbm",
            "model": ("tiny CPU-smoke Gpt" if smoke
                      else "zoo.Gpt GPT-2-small-shaped"),
            "smoke": smoke, "n_slots": n_slots,
            "block_size": block_size, "kv_pool_rows": pool_rows,
            "sys_len": sys_len, "user_len": user_len, "n_new": n_new,
            "ttft_cold_s": round(ttft_cold, 4),
            "ttft_prefix_hit_s": round(ttft_hit, 4),
            "prefix_hit_ttft_ratio": round(ttft_hit / ttft_cold, 4),
            "streams_stripes": stripes,
            "streams_blocks": blocks,
            "vs_baseline": round(blocks / max(stripes, 1), 3),
            "mixed_request_totals": totals,
            "ladder": ladder,
            "note": "value is max admissible concurrent streams at "
                    "the stripe pool's HBM footprint under the paged "
                    "layout (mixed lengths, shared system prompt "
                    "resident once); vs_baseline is the x-over the "
                    "stripe layout's count; acceptance needs "
                    "prefix_hit_ttft_ratio < 1 and vs_baseline >= 2"}


def bench_speculative(ks=(2, 4), n_slots=4, prompt_len=12, n_new=48,
                      n_requests=8, tick_batch=8, smoke=False):
    """Speculative decode ladder -> SERVING_SPEC_r11.json: accepted-
    tokens/s per chip at K in {2, 4} draft tokens vs the non-
    speculative ``tick_batch``-fused baseline on the SAME geometry,
    recording the draft acceptance rate per rung.

    Two draft configs per K: the TRUNCATED self-draft (a quarter of
    the stack — the production shape, where the K-cheap-steps win
    lives; the smoke target's upper blocks are residual-scaled so the
    truncation is predictive, standing in for a trained model, and
    the acceptance is MEASURED) and the FULL-DEPTH self-draft (draft
    == target, acceptance exactly 1.0 by construction — the
    mechanism's upper bound and its cost floor).  Outputs are
    byte-compared against the baseline server inside the window: the
    bench fails rather than report a speedup that broke parity.
    ``smoke=True`` shrinks to the small CPU config (the artifact CI
    records); the default geometry is the TPU run."""
    import jax
    from deeplearning4j_tpu.parallel import GenerationServer
    from deeplearning4j_tpu.zoo.gpt import Gpt

    if smoke:
        n_slots, prompt_len, n_new, n_requests = 2, 8, 24, 4
        m = Gpt(vocab_size=50, max_len=64, d_model=128, n_layers=4,
                n_heads=4, d_ff=256, seq_len=8, compute_dtype=None,
                seed=3)
        compute_dtype = None
    else:
        if jax.default_backend() not in ("tpu",):
            raise RuntimeError(
                "speculative bench requires a TPU backend "
                "(smoke=True for the CPU config)")
        m = Gpt(seq_len=prompt_len, max_len=prompt_len + n_new)
        compute_dtype = "bfloat16"
    net = m.init_graph()
    n_layers = m.n_layers if hasattr(m, "n_layers") else 4
    trunc_depth = max(1, n_layers // 4)
    # the bench target's blocks ABOVE the truncation depth are scaled
    # toward the residual identity so the truncated self-draft is
    # PREDICTIVE — the trained-model regime this synthetic bench
    # stands in for (smoke AND TPU geometry alike: both construct an
    # untrained net, and an untrained random stack gives every
    # truncation coin-flip argmax agreement — a property of random
    # nets, not of the mechanism).  The acceptance rate below is
    # still MEASURED, never assumed.
    pt = net.params_tree
    for li in range(trunc_depth + 1, n_layers + 1):
        for w in ("Wo", "bo", "W2", "b2"):
            pt[f"layer_{li}"][w] = pt[f"layer_{li}"][w] * 0.05
    max_len = prompt_len + n_new
    rng = np.random.default_rng(0)
    vocab = m.vocab_size
    prompts = [rng.integers(0, vocab, prompt_len).astype(np.int32)
               for _ in range(n_requests)]

    def window(srv):
        """Warm EVERY compile variant off-window — the full-budget
        submit covers the largest scan/round length and the drain
        tail, the n_new=1 submit forces the k=1 / single-round
        variant the concurrent phase hits whenever admission is
        pending (left cold, its ~seconds compile lands inside the
        measured window and dwarfs the dispatches) — then decode
        every prompt concurrently; returns (tokens/s, outputs)."""
        srv.submit(prompts[0], n_new=n_new)
        srv.submit(prompts[0], n_new=1)
        srv.submit(prompts[0], n_new=2)
        t0 = time.perf_counter()
        handles = [srv.submit_async(p, n_new=n_new) for p in prompts]
        outs = [h.result(timeout=600) for h in handles]
        dt = time.perf_counter() - t0
        return n_requests * n_new / dt, outs

    base_kw = dict(n_slots=n_slots, max_len=max_len,
                   compute_dtype=compute_dtype, tick_batch=tick_batch,
                   tick_timeout_s=None)
    with GenerationServer(net, **base_kw) as srv:
        base_tps, base_outs = window(srv)

    ladder = []
    for k in ks:
        for depth, tag in ((trunc_depth, "self_trunc"),
                           (n_layers, "self_full")):
            rounds = 2
            with GenerationServer(net, speculative={
                    "k": k, "rounds": rounds, "draft_layers": depth},
                    **base_kw) as srv:
                tps, outs = window(srv)
                st = srv.stats()
            for a, b in zip(outs, base_outs):
                if not np.array_equal(a, b):
                    raise AssertionError(
                        f"speculative K={k} {tag} output diverged "
                        "from the non-speculative baseline")
            ladder.append({
                "k": k, "draft": tag, "draft_layers": depth,
                "rounds": rounds,
                "accepted_tokens_per_sec": round(tps, 1),
                "acceptance_rate": round(st["spec_acceptance_rate"],
                                         4),
                "proposed": st["spec_proposed"],
                "accepted": st["spec_accepted"],
                "vs_nonspec": round(tps / base_tps, 3),
            })

    best = max(ladder, key=lambda r: r["accepted_tokens_per_sec"])
    return {"metric": "serving_speculative_decode",
            "value": best["accepted_tokens_per_sec"],
            "unit": "accepted_tokens_per_sec",
            "model": ("tiny CPU-smoke Gpt" if smoke
                      else "zoo.Gpt GPT-2-small-shaped"),
            "smoke": smoke, "n_slots": n_slots,
            "prompt_len": prompt_len, "n_new": n_new,
            "n_requests": n_requests, "tick_batch": tick_batch,
            "nonspec_tokens_per_sec": round(base_tps, 1),
            "best_k": best["k"], "best_draft": best["draft"],
            "vs_baseline": best["vs_nonspec"],
            "ladder": ladder,
            "parity": "byte-checked vs non-speculative in-window",
            "note": "value is accepted-tokens/s at the best rung; "
                    "vs_baseline is the x-over the non-speculative "
                    "tick_batch-fused server on identical geometry, "
                    "outputs byte-checked.  acceptance_rate is exact "
                    "draft/target argmax agreement, MEASURED per "
                    "rung: 1.0 for the full self-draft by "
                    "construction; the truncated rungs run against a "
                    "smoke target whose upper blocks are residual-"
                    "scaled so the truncation is predictive (the "
                    "trained-model regime — random upper blocks "
                    "would make any draft a coin flip).  Acceptance "
                    "needs vs_baseline > 1 on a self-draft rung"}


def bench_spec_sampled(ks=(2, 4), k_max=4, n_slots=4, prompt_len=12,
                       n_new=48, n_requests=8, tick_batch=8,
                       temps=(0.4, 0.8), smoke=False):
    """Sampled speculative decode sweep -> SERVING_SPEC_r20.json:
    rejection-resampling speculation (ISSUE 20) on a MIXED
    greedy+sampled trace with two tenants, at temperature in
    {0.4, 0.8} x {fixed K in {2, 4}, acceptance-adaptive K within
    [1, k_max]} vs the non-speculative sampled baseline on identical
    geometry.

    The trace is 3/4 sampled (pinned per-request seeds, alternating
    tenants) and 1/4 greedy: every spec window exercises the mixed
    ``accept_mixed`` pool, and the greedy rows are byte-compared
    against the non-speculative baseline in-window (sampled rows
    cannot byte-compare across servers — the spec and plain PRNG
    paths differ while both drawing the exact target law, which the
    tier-1 distribution tests pin).  Every compile variant is warmed
    off-window as in the r11 bench — including, for the adaptive
    rung, each ("spec", R, K, sampled) program in [1, k_max] by
    sweeping ``set_draft_k_cap`` before the measured window.

    Acceptance bar (ISSUE 20): sampled tokens/s >= 1.3x the non-spec
    sampled baseline at temperature 0.8 on the CPU smoke config, and
    the adaptive rung matching or beating every fixed K on the same
    trace.  ``smoke=True`` shrinks to the small CPU config (the
    artifact CI records); the default geometry is the TPU run."""
    import jax
    from deeplearning4j_tpu.parallel import GenerationServer
    from deeplearning4j_tpu.zoo.gpt import Gpt

    if smoke:
        # a longer window than the r11 smoke: the sampled-vs-plain
        # ratio is the acceptance bar here, and a ~50ms window is
        # all timer noise on a shared CPU host
        n_slots, prompt_len, n_new, n_requests = 2, 8, 32, 6
        m = Gpt(vocab_size=50, max_len=64, d_model=128, n_layers=4,
                n_heads=4, d_ff=256, seq_len=8, compute_dtype=None,
                seed=3)
        compute_dtype = None
    else:
        if jax.default_backend() not in ("tpu",):
            raise RuntimeError(
                "spec_sampled bench requires a TPU backend "
                "(smoke=True for the CPU config)")
        m = Gpt(seq_len=prompt_len, max_len=prompt_len + n_new)
        compute_dtype = "bfloat16"
    net = m.init_graph()
    n_layers = m.n_layers if hasattr(m, "n_layers") else 4
    trunc_depth = max(1, n_layers // 4)
    # residual-scale the blocks above the truncation depth so the
    # self-draft is PREDICTIVE (see bench_speculative — the same
    # trained-model stand-in; acceptance is still measured)
    pt = net.params_tree
    for li in range(trunc_depth + 1, n_layers + 1):
        for w in ("Wo", "bo", "W2", "b2"):
            pt[f"layer_{li}"][w] = pt[f"layer_{li}"][w] * 0.05
    max_len = prompt_len + n_new
    rng = np.random.default_rng(0)
    vocab = m.vocab_size
    prompts = [rng.integers(0, vocab, prompt_len).astype(np.int32)
               for _ in range(n_requests)]
    # request i: greedy every 4th, else sampled with a pinned seed;
    # tenants alternate so the per-tenant acceptance series populate
    greedy_ix = [i for i in range(n_requests) if i % 4 == 0]

    def sampling(i, temp):
        if i % 4 == 0:
            return None
        return {"temperature": temp, "top_k": 8, "seed": 1000 + i}

    def window(srv, temp):
        """Warm every variant off-window (full budget + n_new=1/2,
        greedy AND sampled — the scan/spec/drain programs for both
        pool flavours), then decode the whole trace concurrently."""
        for kw in (dict(), dict(sampling={"temperature": temp,
                                          "top_k": 8, "seed": 1})):
            srv.submit(prompts[0], n_new=n_new, **kw)
            srv.submit(prompts[0], n_new=1, **kw)
            srv.submit(prompts[0], n_new=2, **kw)
        t0 = time.perf_counter()
        handles = [srv.submit_async(p, n_new=n_new,
                                    sampling=sampling(i, temp),
                                    tenant=("a" if i % 2 else "b"))
                   for i, p in enumerate(prompts)]
        outs = [h.result(timeout=600) for h in handles]
        dt = time.perf_counter() - t0
        return n_requests * n_new / dt, outs

    base_kw = dict(n_slots=n_slots, max_len=max_len,
                   compute_dtype=compute_dtype, tick_batch=tick_batch,
                   tick_timeout_s=None)
    rounds = 2
    ladder = []
    base_tps = {}
    for temp in temps:
        with GenerationServer(net, **base_kw) as srv:
            tps, base_outs = window(srv, temp)
        base_tps[temp] = tps
        rungs = [(f"k{k}", {"k": k, "rounds": rounds,
                            "draft_layers": trunc_depth})
                 for k in ks]
        rungs.append(("adaptive", {"k": 2, "rounds": rounds,
                                   "draft_layers": trunc_depth,
                                   "adaptive": True, "k_max": k_max}))
        for tag, spec in rungs:
            with GenerationServer(net, speculative=spec,
                                  **base_kw) as srv:
                if spec.get("adaptive"):
                    # warm every per-depth spec program the
                    # controller can pick: under a cap a COLD
                    # controller pins k to the cap, so reset before
                    # each submit and sweep the cap upward (any
                    # lower depth a warm pick drifts to is already
                    # compiled from the earlier cap)
                    for c in range(1, k_max + 1):
                        srv.set_draft_k_cap(c)
                        for kw in (dict(),
                                   dict(sampling={"temperature": temp,
                                                  "top_k": 8,
                                                  "seed": 1})):
                            for nn in (n_new, 1, 2):
                                srv._spec_ctl.reset()
                                srv.submit(prompts[0], n_new=nn,
                                           **kw)
                    srv.set_draft_k_cap(None)
                tps, outs = window(srv, temp)
                st = srv.stats()
            for i in greedy_ix:
                if not np.array_equal(outs[i], base_outs[i]):
                    raise AssertionError(
                        f"spec_sampled {tag} temp={temp}: greedy row "
                        f"{i} diverged from the non-spec baseline")
            ladder.append({
                "temperature": temp, "mode": tag,
                "tokens_per_sec": round(tps, 1),
                "acceptance_rate": round(st["spec_acceptance_rate"],
                                         4),
                "proposed": st["spec_proposed"],
                "accepted": st["spec_accepted"],
                "vs_nonspec": round(tps / base_tps[temp], 3),
            })

    def rung(temp, tag):
        return next(r for r in ladder
                    if r["temperature"] == temp and r["mode"] == tag)

    # adaptive "matches or beats": within timing noise (3%) of every
    # fixed rung at the same temperature
    adaptive_ok = all(
        rung(t, "adaptive")["tokens_per_sec"]
        >= 0.97 * max(rung(t, f"k{k}")["tokens_per_sec"] for k in ks)
        for t in temps)
    hot = max(temps)
    best_hot = max((r for r in ladder if r["temperature"] == hot),
                   key=lambda r: r["tokens_per_sec"])
    return {"metric": "serving_speculative_sampled",
            "value": rung(hot, "adaptive")["tokens_per_sec"],
            "unit": "tokens_per_sec",
            "model": ("tiny CPU-smoke Gpt" if smoke
                      else "zoo.Gpt GPT-2-small-shaped"),
            "smoke": smoke, "n_slots": n_slots,
            "prompt_len": prompt_len, "n_new": n_new,
            "n_requests": n_requests, "tick_batch": tick_batch,
            "k_max": k_max, "rounds": rounds,
            "trace": f"{n_requests - len(greedy_ix)} sampled + "
                     f"{len(greedy_ix)} greedy, 2 tenants",
            "nonspec_tokens_per_sec": {
                str(t): round(base_tps[t], 1) for t in temps},
            "vs_baseline": rung(hot, "adaptive")["vs_nonspec"],
            "best_hot_mode": best_hot["mode"],
            "adaptive_matches_fixed": adaptive_ok,
            "ladder": ladder,
            "parity": "greedy rows byte-checked vs non-spec in-window",
            "note": "value is the adaptive rung's mixed-trace "
                    "tokens/s at the hottest temperature; "
                    "vs_baseline is the x-over the non-speculative "
                    "sampled server on the identical trace.  "
                    "Sampled rows follow the exact target law by "
                    "rejection resampling (tier-1 distribution "
                    "tests); greedy rows byte-match the baseline "
                    "in-window.  Acceptance needs vs_baseline >= "
                    "1.3 at temp 0.8 (smoke) and "
                    "adaptive_matches_fixed"}


def bench_serving_fleet(replica_ladder=(1, 2, 4), n_slots=8,
                        sys_len=384, user_len=32, n_new=64,
                        block_size=16, tick_batch=8,
                        hot_requests=12, cold_requests=6, smoke=False):
    """Multi-tenant fleet ladder -> SERVING_FLEET_r09.json: 1/2/4
    replicas under a mixed 2-tenant load — a hot tenant whose requests
    share one long system prompt (unique user tails; affinity should
    route them to the replica whose prefix cache is warm) and a cold
    tenant with unique prompts (least-loaded spread).  Per rung:
    aggregate new-tokens/s, per-tenant TTFT p50/p99, and the affinity
    hit rate (affinity dispatches / all dispatches).  ``smoke=True``
    shrinks to a tiny CPU config (the artifact CI records); on a
    shared-host CPU the replica ladder measures the ROUTER's overhead
    and fairness, not chip scaling — replicas share the same silicon,
    so vs_baseline ~ 1 is expected there and the TPU run is where the
    ladder climbs."""
    import jax
    from deeplearning4j_tpu import telemetry
    from deeplearning4j_tpu.serving import ServingFleet, TenantQuota
    from deeplearning4j_tpu.zoo.gpt import Gpt

    if smoke:
        replica_ladder = (1, 2)
        n_slots, sys_len, user_len, n_new, block_size = 2, 12, 4, 8, 4
        hot_requests, cold_requests = 6, 3
        m = Gpt(vocab_size=50, max_len=64, d_model=32, n_layers=2,
                n_heads=4, d_ff=64, seq_len=8, compute_dtype=None,
                seed=3)
        compute_dtype = None
    else:
        if jax.default_backend() not in ("tpu",):
            raise RuntimeError(
                "serving_fleet bench requires a TPU backend "
                "(smoke=True for the CPU config)")
        m = Gpt(seq_len=sys_len + user_len,
                max_len=sys_len + user_len + n_new)
        compute_dtype = "bfloat16"
    net = m.init_graph()
    max_len = sys_len + user_len + n_new
    rng = np.random.default_rng(0)
    vocab = m.vocab_size
    disp = telemetry.get_registry().counter(
        "fleet_replica_dispatch_total", labelnames=("replica", "reason"))

    def disp_totals():
        tot = {}
        for (_, reason), child in disp._items():
            tot[reason] = tot.get(reason, 0.0) + child.value
        return tot

    def prompt(prefix):
        tail = rng.integers(0, vocab, user_len).astype(np.int32)
        return np.concatenate([prefix, tail])

    def pct(ttfts, q):
        vals = [t for t in ttfts if t is not None]
        return round(float(np.percentile(vals, q)), 4) if vals else None

    ladder = []
    for n_rep in replica_ladder:
        with ServingFleet(
                net, n_replicas=n_rep, n_slots=n_slots,
                max_len=max_len, compute_dtype=compute_dtype,
                block_size=block_size, tick_batch=tick_batch,
                quotas={"hot": TenantQuota(
                    max_concurrent=max(2, n_rep * n_slots))}) as fleet:
            # warm every replica's compile caches off-window (miss +
            # hit admission paths and the scan chain) on a throwaway
            # prefix, so the measured window is steady-state
            warm = rng.integers(0, vocab, sys_len).astype(np.int32)
            for i in range(n_rep):
                srv = fleet.replica(i)
                srv.submit(prompt(warm), n_new=n_new)
                srv.submit(prompt(warm), n_new=n_new)
            sysp = rng.integers(0, vocab, sys_len).astype(np.int32)
            fleet.submit(prompt(sysp), n_new=n_new, tenant="hot")
            d0 = disp_totals()
            handles = []
            t0 = time.perf_counter()
            for _ in range(hot_requests):
                handles.append(fleet.submit_async(
                    prompt(sysp), n_new=n_new, tenant="hot"))
            for _ in range(cold_requests):
                cp = rng.integers(0, vocab, sys_len + user_len) \
                    .astype(np.int32)
                handles.append(fleet.submit_async(cp, n_new=n_new,
                                                  tenant="cold"))
            for h in handles:
                h.result(timeout=600)
            dt = time.perf_counter() - t0
            d1 = disp_totals()
        hot_ttfts = [h.ttft for h in handles[:hot_requests]]
        cold_ttfts = [h.ttft for h in handles[hot_requests:]]
        n_disp = sum(d1.values()) - sum(d0.values())
        aff = d1.get("affinity", 0.0) - d0.get("affinity", 0.0)
        ladder.append({
            "replicas": n_rep,
            "requests": len(handles),
            "new_tokens_per_sec": round(len(handles) * n_new / dt, 1),
            "hot_ttft_p50_s": pct(hot_ttfts, 50),
            "hot_ttft_p99_s": pct(hot_ttfts, 99),
            "cold_ttft_p50_s": pct(cold_ttfts, 50),
            "cold_ttft_p99_s": pct(cold_ttfts, 99),
            "affinity_hit_rate": round(aff / max(n_disp, 1), 4),
        })
    return {"metric": "serving_fleet_throughput",
            "value": ladder[-1]["new_tokens_per_sec"],
            "unit": "new_tokens_per_sec",
            "model": ("tiny CPU-smoke Gpt" if smoke
                      else "zoo.Gpt GPT-2-small-shaped"),
            "smoke": smoke, "n_slots": n_slots,
            "block_size": block_size, "sys_len": sys_len,
            "user_len": user_len, "n_new": n_new,
            "hot_requests": hot_requests,
            "cold_requests": cold_requests,
            "vs_baseline": round(
                ladder[-1]["new_tokens_per_sec"]
                / max(ladder[0]["new_tokens_per_sec"], 1e-9), 3),
            "ladder": ladder,
            "note": "value is aggregate new-tokens/s at the largest "
                    "rung; vs_baseline is the x-over the 1-replica "
                    "rung (replica scaling — meaningful on TPU where "
                    "replicas map to chips; ~1 on the shared-host CPU "
                    "smoke).  affinity_hit_rate > 0 proves the "
                    "repeated-system-prompt tenant rides the warm "
                    "replica's prefix cache"}


def bench_serving_disagg(n_replicas=2, n_slots=8, long_len=384,
                         short_len=16, n_new_long=32, n_new_short=64,
                         n_long=8, n_short=16, block_size=16,
                         tick_batch=8, smoke=False):
    """Disaggregated prefill/decode + tiered KV bench ->
    SERVING_DISAGG_r14.json (ISSUE 14).  Two measurements:

    1. MIXED TRACE — long-prompt admissions interleaved with
       short-prompt decode streams through (a) a unified fleet
       (every replica prefills AND decodes: a long admission stalls
       that replica's decode ticks behind its compute-bound prefill)
       and (b) a role-split fleet (longs stage through the prefill
       replica, handing their finished prefix blocks to the decode
       replica; shorts never wait behind a long prefill).  Reported:
       short-stream TTFT p50/p99 under both, long TTFT, aggregate
       tokens/s.  Acceptance: disagg short p99 <= unified short p99.
    2. TIERED PREFIX CACHE — a prefix footprint LARGER than the
       device pool, landed via the handoff/import path so every
       measured admission restores its blocks from the host tier
       with one batched H2D (``nfill`` deterministic -> no compile
       jitter in-window): tier-hit TTFT vs the cold full re-prefill
       of same-length fresh prompts.  Acceptance: tier-hit TTFT <
       cold re-prefill TTFT.

    Outputs are byte-checked in-window: the disagg fleet's decode of
    the probe prompt must equal the unified fleet's.  ``smoke=True``
    shrinks to the tiny CPU config (the artifact CI records); on the
    shared-host CPU the fleets contend for one core, so the disagg
    win is scheduler-serialization relief, not chip isolation — the
    TPU geometry is where the split maps to real chips."""
    import jax
    from deeplearning4j_tpu.parallel import GenerationServer
    from deeplearning4j_tpu.serving import ServingFleet
    from deeplearning4j_tpu.zoo.gpt import Gpt

    if smoke:
        n_slots, long_len, short_len = 2, 44, 4
        n_new_long, n_new_short = 4, 12
        n_long, n_short, block_size = 6, 12, 4
        m = Gpt(vocab_size=50, max_len=64, d_model=32, n_layers=2,
                n_heads=4, d_ff=64, seq_len=8, compute_dtype=None,
                seed=3)
        compute_dtype = None
    else:
        if jax.default_backend() not in ("tpu",):
            raise RuntimeError(
                "serving_disagg bench requires a TPU backend "
                "(smoke=True for the CPU config)")
        m = Gpt(seq_len=long_len, max_len=long_len + n_new_long)
        compute_dtype = "bfloat16"
    net = m.init_graph()
    max_len = max(long_len + n_new_long, short_len + n_new_short)
    rng = np.random.default_rng(0)
    vocab = m.vocab_size

    def long_prompt():
        return rng.integers(0, vocab, long_len).astype(np.int32)

    def short_prompt():
        return rng.integers(0, vocab, short_len).astype(np.int32)

    def pct(vals, q):
        vals = [v for v in vals if v is not None]
        return round(float(np.percentile(vals, q)), 4) if vals else None

    def run_trace(fleet):
        """Interleave long admissions into a stream of shorts; returns
        (short ttfts, long ttfts, tokens/s, one probe output)."""
        # off-window warm: both admission paths + the scan chain on
        # every replica (throwaway prompts)
        for i in range(fleet.n_replicas):
            srv = fleet.replica(i)
            srv.submit(long_prompt(), n_new=2)
            srv.submit(short_prompt(), n_new=2)
        fleet.submit(long_prompt(), n_new=2)     # fleet path (handoff
        fleet.submit(short_prompt(), n_new=2)    # compile, disagg)
        probe = long_prompt()
        handles, kinds = [], []
        t0 = time.perf_counter()
        li = 0
        for i in range(n_short):
            handles.append(fleet.submit_async(short_prompt(),
                                              n_new=n_new_short))
            kinds.append("short")
            if i % 2 == 0 and li < n_long:
                p = probe if li == 0 else long_prompt()
                handles.append(fleet.submit_async(p,
                                                  n_new=n_new_long))
                kinds.append("long")
                li += 1
        outs = [h.result(timeout=600) for h in handles]
        dt = time.perf_counter() - t0
        n_toks = sum(n_new_short if k == "short" else n_new_long
                     for k in kinds)
        shorts = [h.ttft for h, k in zip(handles, kinds)
                  if k == "short"]
        longs = [h.ttft for h, k in zip(handles, kinds) if k == "long"]
        probe_out = next(o for o, k in zip(outs, kinds) if k == "long")
        return shorts, longs, n_toks / dt, probe_out

    common = dict(n_slots=n_slots, max_len=max_len,
                  compute_dtype=compute_dtype, block_size=block_size,
                  tick_batch=tick_batch, tick_timeout_s=None)
    rng = np.random.default_rng(7)
    with ServingFleet(net, n_replicas=n_replicas, **common) as fleet:
        (uni_short, uni_long, uni_tps, uni_probe) = run_trace(fleet)
    rng = np.random.default_rng(7)     # identical trace
    roles = ["prefill"] + ["decode"] * (n_replicas - 1)
    with ServingFleet(net, n_replicas=n_replicas, roles=roles,
                      **common) as fleet:
        (dis_short, dis_long, dis_tps, dis_probe) = run_trace(fleet)
    if not np.array_equal(uni_probe, dis_probe):
        raise AssertionError(
            "disaggregated decode diverged from the unified fleet's "
            "decode of the same prompt")

    # -- tiered prefix cache: footprint >> device pool ----------------
    # the tier-hit-vs-re-prefill comparison needs prefill COMPUTE to
    # dominate dispatch overhead (at toy width the paged gather's
    # extra ops outweigh the saved FLOPs), so the smoke runs this
    # half on a wider net than the trace half
    if smoke:
        tm = Gpt(vocab_size=50, max_len=128, d_model=256, n_layers=2,
                 n_heads=4, d_ff=1024, seq_len=8, compute_dtype=None,
                 seed=5)
        tier_net = tm.init_graph()
        t_long, t_new, t_bs = 96, 4, 8
        t_max = t_long + t_new
    else:
        tier_net, t_max = net, max_len
        t_long, t_new, t_bs = long_len, n_new_long, block_size
    tcommon = dict(n_slots=2, max_len=t_max,
                   compute_dtype=compute_dtype, block_size=t_bs,
                   tick_batch=tick_batch, tick_timeout_s=None)
    full_blocks = (t_long - 1) // t_bs
    blocks_per = -(-(t_long + t_new) // t_bs)
    kv_blocks = max(-(-t_max // t_bs),                # >= one max req
                    blocks_per + 2)
    n_prefixes = max(3, (2 * kv_blocks) // full_blocks + 1)
    prefixes = [rng.integers(0, vocab, t_long).astype(np.int32)
                for _ in range(n_prefixes)]
    warm_p = rng.integers(0, vocab, t_long).astype(np.int32)
    # the prefix footprint is built OFF the bench server (a stand-in
    # prefill replica), then imported — every measured admission
    # restores full_blocks spilled blocks: deterministic nfill, so
    # the one in-window compile variant is warmed by the throwaway
    with GenerationServer(tier_net, **tcommon) as src:
        payloads = []
        for p in (warm_p, *prefixes):
            src.prefill_async(p).result(timeout=600)
            payloads.append(src.export_prefix(p))
    with GenerationServer(tier_net, kv_blocks=kv_blocks,
                          host_tier_blocks=4 * kv_blocks,
                          **tcommon) as srv:
        srv.submit(rng.integers(0, vocab, t_long).astype(np.int32),
                   n_new=t_new)                       # cold compile
        # warm the tier-hit compile variant with the SAME key the
        # measured admissions hit (dev_matched=0, nfill=full_blocks):
        # warm_p was imported but never submitted here, so its
        # admission restores every block from the tier
        srv.import_blocks(payloads[0])
        srv.submit(warm_p, n_new=t_new)
        for pay in payloads[1:]:
            srv.import_blocks(pay)
        hit_ttfts, cold_ttfts = [], []
        for p in prefixes:
            h = srv.submit_async(p, n_new=t_new)
            h.result(timeout=600)
            hit_ttfts.append(h.ttft)
        for _ in range(len(prefixes)):
            h = srv.submit_async(
                rng.integers(0, vocab, t_long).astype(np.int32),
                n_new=t_new)
            h.result(timeout=600)
            cold_ttfts.append(h.ttft)
        tier_stats = srv.stats()
    ttft_tier_hit = float(np.median(hit_ttfts))
    ttft_cold = float(np.median(cold_ttfts))

    dis_p99 = pct(dis_short, 99)
    uni_p99 = pct(uni_short, 99)
    return {"metric": "serving_disagg_prefill_decode",
            "value": dis_p99, "unit": "short_stream_ttft_p99_s",
            "model": ("tiny CPU-smoke Gpt" if smoke
                      else "zoo.Gpt GPT-2-small-shaped"),
            "smoke": smoke, "n_replicas": n_replicas,
            "roles": roles, "n_slots": n_slots,
            "block_size": block_size, "long_len": long_len,
            "short_len": short_len, "n_long": n_long,
            "n_short": n_short, "n_new_long": n_new_long,
            "n_new_short": n_new_short,
            "unified": {
                "short_ttft_p50_s": pct(uni_short, 50),
                "short_ttft_p99_s": uni_p99,
                "long_ttft_p50_s": pct(uni_long, 50),
                "long_ttft_p99_s": pct(uni_long, 99),
                "new_tokens_per_sec": round(uni_tps, 1)},
            "disagg": {
                "short_ttft_p50_s": pct(dis_short, 50),
                "short_ttft_p99_s": dis_p99,
                "long_ttft_p50_s": pct(dis_long, 50),
                "long_ttft_p99_s": pct(dis_long, 99),
                "new_tokens_per_sec": round(dis_tps, 1)},
            "vs_baseline": round(uni_p99 / dis_p99, 3)
            if dis_p99 else None,
            "tier": {
                "kv_blocks_device": kv_blocks,
                "prefix_footprint_blocks":
                    n_prefixes * full_blocks,
                "ttft_tier_hit_s": round(ttft_tier_hit, 4),
                "ttft_cold_reprefill_s": round(ttft_cold, 4),
                "tier_hit_ttft_ratio": round(
                    ttft_tier_hit / ttft_cold, 4),
                "tier_fetches": tier_stats["tier_fetches"],
                "tier_spills": tier_stats["tier_spills"],
                "host_tier_blocks": tier_stats["host_tier_blocks"]},
            "parity": "disagg probe byte-checked vs unified in-window",
            "note": "value is the disagg fleet's short-stream TTFT "
                    "p99 under the mixed trace; vs_baseline is the "
                    "unified fleet's p99 over it (>= 1 means the "
                    "role split kept short streams out of the long "
                    "admissions' shadow).  tier_hit_ttft_ratio < 1 "
                    "means reviving a spilled prefix (one batched "
                    "H2D) beats re-prefilling it, at a prefix "
                    "footprint of prefix_footprint_blocks >> "
                    "kv_blocks_device"}


def bench_serving_mesh(tp_ladder=(1, 2), n_slots=4, prompt_len=12,
                       n_new=48, n_requests=8, tick_batch=8,
                       block_size=16, smoke=False):
    """Mesh-sharded decode ladder -> SERVING_MESH_r17.json (ISSUE 17):
    ONE replica spanning chips.  Per tp rung: the same trace through a
    ``GenerationServer`` on ``tp`` devices (tp=1 is the unsharded
    baseline, tp=2 builds the data x tp NamedSharding mesh) —
    new-tokens/s, TTFT p50/p99, and a speculative pass (full-depth
    self-draft) whose acceptance rate proves draft + verify run
    through the sharded programs.  Outputs are byte-compared across
    rungs AND against the non-speculative baseline inside the window:
    the bench fails rather than report a rate that broke parity.
    ``smoke=True`` shrinks to the small CPU config (the artifact CI
    records); on a shared-host CPU both rungs run the same silicon,
    so vs_baseline ~ 1x minus the all-gather overhead is the expected
    reading — the TPU run is where tp=2 buys real HBM bandwidth.
    Acceptance: vs_baseline >= 0.7 (sharding overhead never costs
    more than 30% of the single-chip rate, even where it buys no
    extra silicon)."""
    import jax
    from deeplearning4j_tpu.parallel import GenerationServer
    from deeplearning4j_tpu.zoo.gpt import Gpt

    if smoke:
        n_slots, prompt_len, n_new, n_requests = 2, 8, 24, 4
        block_size = 4
        # deliberately the FAT smoke net (~6.4M params — ~1.5x the
        # notional 16MB fp32 virtual-chip budget the README recipe
        # documents): the per-tick matmuls must dominate the mesh
        # all-gathers or the smoke measures dispatch overhead, and
        # the whole point of the rung is a net one chip can't hold
        m = Gpt(vocab_size=50, max_len=64, d_model=256, n_layers=4,
                n_heads=4, d_ff=1024, seq_len=8, compute_dtype=None,
                seed=3)
        compute_dtype = None
    else:
        if jax.default_backend() not in ("tpu",):
            raise RuntimeError(
                "serving_mesh bench requires a TPU backend "
                "(smoke=True for the CPU config)")
        m = Gpt(seq_len=prompt_len, max_len=prompt_len + n_new)
        compute_dtype = "bfloat16"
    net = m.init_graph()
    max_len = prompt_len + n_new
    rng = np.random.default_rng(0)
    vocab = m.vocab_size
    prompts = [rng.integers(0, vocab, prompt_len).astype(np.int32)
               for _ in range(n_requests)]

    def pct(ttfts, q):
        vals = [t for t in ttfts if t is not None]
        return round(float(np.percentile(vals, q)), 4) if vals else None

    def window(srv):
        # warm every compile variant off-window (full budget + the
        # short-round variants admission can hit), then decode the
        # whole trace concurrently; _trials puts a variance band on
        # the rate — the 4x24-token window is short enough that a
        # single trial swings past the 0.7 acceptance line on noise
        srv.submit(prompts[0], n_new=n_new)
        srv.submit(prompts[0], n_new=1)
        outs_box, ttfts_box = [], []

        def trial():
            t0 = time.perf_counter()
            handles = [srv.submit_async(p, n_new=n_new)
                       for p in prompts]
            outs_box[:] = [h.result(timeout=600) for h in handles]
            dt = time.perf_counter() - t0
            ttfts_box[:] = [h.ttft for h in handles]
            return n_requests * n_new / dt

        mean, sigma, _ = _trials(trial)
        return mean, sigma, outs_box, ttfts_box

    n_layers = m.n_layers if hasattr(m, "n_layers") else 4
    base_kw = dict(n_slots=n_slots, max_len=max_len,
                   compute_dtype=compute_dtype, block_size=block_size,
                   tick_batch=tick_batch, tick_timeout_s=None)
    ladder, base_outs = [], None
    for tp in tp_ladder:
        if tp > 1 and len(jax.devices()) < tp:
            ladder.append({"tp": tp, "skipped":
                           f"only {len(jax.devices())} devices"})
            continue
        dev = None if tp == 1 else jax.devices()[:tp]
        with GenerationServer(net, devices=dev, **base_kw) as srv:
            tps, sigma, outs, ttfts = window(srv)
            st = srv.stats()
        with GenerationServer(net, devices=dev, speculative={
                "k": 2, "rounds": 2, "draft_layers": n_layers},
                **base_kw) as srv:
            spec_tps, _, spec_outs, _ = window(srv)
            spec_st = srv.stats()
        if base_outs is None:
            base_outs = outs
        for a, b, c in zip(outs, spec_outs, base_outs):
            if not (np.array_equal(a, c) and np.array_equal(b, c)):
                raise AssertionError(
                    f"tp={tp} output diverged from the tp=1 "
                    "non-speculative baseline — sharding broke parity")
        ladder.append({
            "tp": tp,
            "devices": st["devices"],
            "route": "reference_tp" if st["tp"] > 1 else "pallas",
            "new_tokens_per_sec": round(tps, 1),
            "sigma": round(sigma, 1),
            "ttft_p50_s": pct(ttfts, 50),
            "ttft_p99_s": pct(ttfts, 99),
            "spec_tokens_per_sec": round(spec_tps, 1),
            "spec_acceptance_rate": round(
                spec_st["spec_acceptance_rate"], 4),
        })
    ran = [r for r in ladder if "skipped" not in r]
    top = ran[-1]
    return {"metric": "serving_mesh_decode",
            "value": top["new_tokens_per_sec"],
            "unit": "new_tokens_per_sec",
            "model": ("tiny CPU-smoke Gpt" if smoke
                      else "zoo.Gpt GPT-2-small-shaped"),
            "smoke": smoke, "n_slots": n_slots,
            "prompt_len": prompt_len, "n_new": n_new,
            "n_requests": n_requests, "tick_batch": tick_batch,
            "block_size": block_size,
            "vs_baseline": round(
                top["new_tokens_per_sec"]
                / max(ran[0]["new_tokens_per_sec"], 1e-9), 3),
            "ladder": ladder,
            "parity": "byte-checked across rungs and vs non-spec "
                      "baseline in-window",
            "note": "value is new-tokens/s at the largest tp rung; "
                    "vs_baseline is the x-over the tp=1 rung on the "
                    "SAME trace, outputs byte-checked (parity by "
                    "construction: weights shard output axes only, "
                    "rep() all-gathers before every contraction).  "
                    "On the shared-host CPU smoke both rungs run the "
                    "same silicon, so >= 0.7 (all-gather overhead "
                    "bounded) is the acceptance; on TPU tp=2 halves "
                    "per-chip KV residency and the ladder should "
                    "climb toward the HBM-bandwidth roofline"}


def main():
    enable_compile_cache()
    peak_flops()    # a device without a peak on record fails here
    result = bench_resnet50()
    # bench_serving_mesh is not here: multi-chip replicas cannot run
    # beside the persistent compile cache on this installation
    # (GenerationServer refuses) — scripts/bench_serving_mesh.py runs
    # it with the cache off
    result["secondary"] = [
        fn() for fn in (bench_bert, bench_bert_imported, bench_gpt,
                        bench_serving_decode, bench_speculative,
                        bench_spec_sampled, bench_serving_fleet,
                        bench_serving_disagg)]
    print(json.dumps(result))


if __name__ == "__main__":
    sys.exit(main())
