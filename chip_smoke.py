#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives both hot paths once, through the entry points a user calls, at
the full width of ``zoo.Gpt()`` (12 x 768, 6 heads x 128, ff 3072, vocab
32000) with seeded random weights and data:

* *train* — ``init_graph()`` + ``fit`` on a repeated seeded batch
  (b=8, t=2048): losses finite, the first update lowers the loss, the
  flash kernel routed.
* *serve* — ``GenerationServer(net, n_slots=8, max_len=2048)`` answering
  concurrent greedy requests of mixed lengths, a prefix-cache hit, and
  one speculative (full-depth self-draft) request on a second server:
  every request completes, tokens are held to offline ``generate()``,
  only the Pallas paged kernels routed, no tick failure, no watchdog
  restart.
* ``--chips 4`` runs *only* the mesh phase and what it is compared
  with: ``ShardedTrainer`` over a 2 x 2 (data x model) mesh against the
  same steps on one device, then a two-replica tp=2 ``ServingFleet``
  against a one-device server — with the persistent compile cache off:
  a multi-chip replica's decode program loaded from it halts the chip
  on this installation (PERF.md, PR 21).

This is a smoke, not a benchmark: its seconds say that the programs
compiled and ran, nothing about speed.

One process owns the chip: nothing here starts a child.  Without a TPU
the script exits non-zero before it builds a model — there is no CPU
fallback and it forces no platform.  Each phase prints one JSON line;
the LAST line of stdout is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any phase that raises or fails a check ends the run non-zero first.

The phases are importable and take a size preset, so
``tests/test_chip_smoke.py`` rehearses the control flow on the CPU at
``TINY``; the chip-only assertions (platform, ``flash`` / ``pallas``
routes) live in ``main()`` alone.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

#: what the driver runs: zoo.Gpt() as it stands
FULL = dict(
    gpt={}, batch=8, seq=2048, steps=5, n_slots=8, max_len=2048,
    # (prompt tokens, new tokens) per concurrent greedy request
    requests=((24, 32), (100, 48), (333, 24), (17, 64), (700, 16)),
    # a full-depth self-draft reads the target's own weights: what it
    # proposes through the decode kernel, the verify kernel must accept
    spec={"k": 4, "draft_layers": 12}, spec_request=(40, 32),
    # relative: fit reports the loss summed over t (~2048 x 10.4), and
    # in bf16 a split contraction rounds partial sums differently
    # (first four-chip run: 2e-5)
    mesh_steps=3, mesh_loss_rtol=1e-3)

#: the CPU rehearsal: same control flow, nothing is measured
TINY = dict(
    gpt=dict(vocab_size=64, max_len=64, d_model=32, n_layers=2,
             n_heads=4, d_ff=64, seq_len=16, compute_dtype=None),
    batch=4, seq=16, steps=4, n_slots=2, max_len=64,
    requests=((20, 6), (9, 4), (5, 8), (3, 10)),
    spec={"k": 2, "draft_layers": 2}, spec_request=(7, 9),
    mesh_steps=3, mesh_loss_rtol=1e-4)

#: GenerationServer's default block_size: the second-wave prompt
#: shares one whole block with the first (both presets' first prompt is
#: longer); a changed default shows as a missed prefix hit
BLOCK = 16

#: first-divergence tolerance, in nats of the float32 "highest"
#: precision full-forward reference (see ``token_gaps``)
TOKEN_GAP_TOL = 5e-3

_WATCHED = ('paged_route_total{path="pallas"}',
            'paged_route_total{path="reference"}',
            'paged_route_total{path="reference_tp"}',
            "generation_server_tick_failures_total",
            "serve_watchdog_restarts_total",
            "flash_fallback_above_threshold_total")


class SmokeFailure(RuntimeError):
    """A phase ran but what it observed is wrong."""


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def counters() -> dict:
    """The watched counters' current values (absent series read 0)."""
    from deeplearning4j_tpu import telemetry
    snap = telemetry.get_registry().snapshot()["counters"]
    return {k: snap.get(k, 0.0) for k in _WATCHED}


def delta(before: dict) -> dict:
    return {k: v - before[k] for k, v in counters().items()}


def healthy_servers() -> float:
    """Sum of the ``server_healthy`` gauge over every server instance
    the process has built: the number that read 1 right now."""
    from deeplearning4j_tpu import telemetry
    gauges = telemetry.get_registry().snapshot()["gauges"]
    return sum(v for k, v in gauges.items()
               if k.startswith("server_healthy"))


def build_net(size: dict, **overrides):
    from deeplearning4j_tpu.zoo.gpt import Gpt
    return Gpt(**{**size["gpt"], **overrides}).init_graph()


def lm_batch(size: dict, seed: int):
    rng = np.random.default_rng(seed)
    vocab = size["gpt"].get("vocab_size", 32000)
    x = rng.integers(0, vocab, (size["batch"], size["seq"])).astype(np.int32)
    return x, np.roll(x, -1, axis=1)


def prompts_for(size: dict, shapes, seed: int):
    rng = np.random.default_rng(seed)
    vocab = size["gpt"].get("vocab_size", 32000)
    return [rng.integers(0, vocab, (t0,)).astype(np.int32)
            for t0, _ in shapes]


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------
def train_phase(net, size: dict, seed: int = 0) -> dict:
    """``fit`` on ONE repeated seeded batch, ``size['steps']`` times.
    Every step's seconds end in the scalar readback ``fit`` returns;
    the first holds the compile."""
    from deeplearning4j_tpu import kernels
    from deeplearning4j_tpu.data.dataset import DataSet

    ds = DataSet(*lm_batch(size, seed))
    kernels.reset_route_log()
    before = counters()
    losses, seconds = [], []
    for _ in range(size["steps"]):
        t0 = time.perf_counter()
        losses.append(float(net.fit(ds)))
        seconds.append(time.perf_counter() - t0)
    steady = float(np.median(seconds[1:]))
    return {"phase": "train", "batch": size["batch"], "seq": size["seq"],
            "losses": losses,
            "routes": sorted(set(kernels.route_log())),
            "first_step_s": seconds[0], "step_s": steady,
            "compile_s": seconds[0] - steady,
            "counters": delta(before)}


def check_train(obs: dict) -> None:
    losses = obs["losses"]
    check(len(losses) >= 4, f"fewer than 4 steps: {losses}")
    check(bool(np.all(np.isfinite(losses))), f"loss not finite: {losses}")
    # the FIRST update is what the gradients decide; at the default
    # Adam 3e-4 without warm-up the later steps of this post-LN stack
    # swing (first chip run, PERF.md), so they are reported, not held
    check(losses[1] < losses[0],
          f"the first update did not lower the loss on the same "
          f"batch: {losses}")


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------
def offline_tokens(net, prompts, shapes):
    """The same requests through ``TransformerGenerator.generate()``."""
    from deeplearning4j_tpu.models.generation import TransformerGenerator
    gen = TransformerGenerator(net)
    return [gen.generate(p[None], n_new=n_new)[0]
            for p, (_, n_new) in zip(prompts, shapes)]


def token_gaps(net, size: dict, got, want) -> list:
    """For each request whose tokens differ from the reference: where
    they first part, and how far apart a float32 highest-precision full
    forward of the SHARED prefix scores the two candidates (nats).  Two
    correct decode paths that order a reduction differently may break a
    near-tie differently; a wrong one picks a token the reference
    scores far from its own."""
    import jax

    twin = None
    gaps = []
    for i, (g, w) in enumerate(zip(got, want)):
        g, w = np.asarray(g), np.asarray(w)
        if np.array_equal(g, w):
            continue
        if g.shape != w.shape:
            gaps.append({"request": i, "at": None, "gap": float("inf")})
            continue
        j = int(np.argmax(g != w))
        if twin is None:
            twin = build_net(size, compute_dtype=None, use_flash=False)
            twin.params_tree = net.params_tree
        with jax.default_matmul_precision("highest"):
            probs = twin.output(g[None, :j])[0, -1]
        logp = np.log(np.asarray(probs, np.float64))
        gaps.append({"request": i, "at": j,
                     "gap": float(abs(logp[w[j]] - logp[g[j]]))})
    return gaps


def collect(handles, before: dict, limit_s: float = 600.0) -> list:
    """Every handle's result.  A server whose dispatch can never
    succeed (a kernel the compiler refuses) salvages its slots and
    retries for ever, and a watchdog restart hides a stuck tick: both
    are counted, so fail at the first count instead of waiting."""
    deadline = time.monotonic() + limit_s
    while not all(h.done() for h in handles):
        d = delta(before)
        check(d["generation_server_tick_failures_total"] == 0
              and d["serve_watchdog_restarts_total"] == 0,
              f"the server failed a dispatch or was restarted: {d}")
        check(time.monotonic() < deadline,
              f"requests unanswered after {limit_s:.0f} s")
        time.sleep(0.2)
    return [h.result() for h in handles]


def served(net, size: dict, waves, **server_kw):
    """One default-but-for-``server_kw`` ``GenerationServer``'s life:
    each wave of ``(prompts, shapes)`` is submitted at once and
    collected before the next.  Returns (tokens, what was observed)."""
    from deeplearning4j_tpu.parallel import GenerationServer

    before = counters()
    t0 = time.perf_counter()
    got = []
    with GenerationServer(net, n_slots=size["n_slots"],
                          max_len=size["max_len"], **server_kw) as srv:
        for prompts, shapes in waves:
            got += collect([srv.submit_async(p, n_new=n_new)
                            for p, (_, n_new) in zip(prompts, shapes)],
                           before)
        seconds = time.perf_counter() - t0
        stats = srv.stats()
        healthy = healthy_servers()
    return got, {"seconds": seconds, "server_healthy": healthy,
                 "prefix_hits": stats["prefix_hits"],
                 "proposed": stats["spec_proposed"],
                 "accepted": stats["spec_accepted"],
                 "counters": delta(before)}


def emitted(got, shapes) -> list:
    return [int(len(g) - t0) for g, (t0, _) in zip(got, shapes)]


def serve_phase(net, size: dict, seed: int = 1) -> dict:
    """A default server under concurrent mixed requests, then a second
    wave that repeats the first prompt's opening block under another
    tail (admitted through the prefix-hit path); then ONE speculative
    request on a self-draft server.  All held to offline
    ``generate()``."""
    shapes = list(size["requests"])
    prompts = prompts_for(size, shapes, seed)
    hit = np.concatenate([prompts[0][:BLOCK + 1],
                          prompts_for(size, [(8, 0)], seed + 1)[0]])
    hit_shape = (len(hit), shapes[0][1])
    got, obs = served(net, size,
                      [(prompts, shapes), ([hit], [hit_shape])])
    prompts, shapes = prompts + [hit], shapes + [hit_shape]

    sshape = size["spec_request"]
    sprompt = prompts_for(size, [sshape], seed + 2)
    sgot, sobs = served(net, size, [(sprompt, [sshape])],
                        speculative=dict(size["spec"]))

    want = offline_tokens(net, prompts + sprompt, shapes + [sshape])
    return {"phase": "serve", "n_slots": size["n_slots"],
            "max_len": size["max_len"],
            "requests": [list(s) for s in shapes],
            "emitted": emitted(got, shapes), **obs,
            "spec": {"request": list(sshape),
                     "emitted": emitted(sgot, [sshape])[0], **sobs},
            "token_gaps": token_gaps(net, size, got + sgot, want),
            "token_gap_tol": TOKEN_GAP_TOL}


def check_tokens(obs: dict) -> None:
    """Tokens equal the reference (``token_gaps`` empty), or every
    request that differs parts from it at a near-tie — stated on the
    phase line, never silent."""
    for g in obs["token_gaps"]:
        check(g["gap"] <= obs["token_gap_tol"],
              f"request {g['request']} leaves the reference at token "
              f"{g['at']} by {g['gap']:.4g} nats "
              f"(> {obs['token_gap_tol']})")


def check_serve(obs: dict) -> None:
    """What holds on any platform (``main`` adds the routes)."""
    want = [n for _, n in obs["requests"]]
    check(obs["emitted"] == want,
          f"requests emitted {obs['emitted']}, wanted {want}")
    check(obs["spec"]["emitted"] == obs["spec"]["request"][1],
          f"speculative request emitted {obs['spec']['emitted']}")
    check(obs["spec"]["accepted"] > 0,
          f"the verify pass accepted none of the full-depth "
          f"self-draft's {obs['spec']['proposed']} proposals")
    check(obs["prefix_hits"] >= 1, "the repeated prefix did not hit")
    check_tokens(obs)
    for part in (obs, obs["spec"]):
        c = part["counters"]
        check(c["generation_server_tick_failures_total"] == 0,
              f"tick failures: {c}")
        check(c["serve_watchdog_restarts_total"] == 0,
              f"watchdog restarts: {c}")
        check(part["server_healthy"] == 1,
              f"server_healthy reads {part['server_healthy']}")


# ---------------------------------------------------------------------------
# four chips: one program across a mesh, replicas across slices
# ---------------------------------------------------------------------------
def mesh_phase(size: dict, devices, seed: int = 2) -> dict:
    """``ShardedTrainer`` on a 2 x 2 (data x model) mesh against the
    same steps on one device, then a two-replica tp=2 ``ServingFleet``
    against a one-device server, over ``devices[:4]``."""
    from deeplearning4j_tpu import telemetry
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.parallel.mesh import MeshConfig
    from deeplearning4j_tpu.parallel.trainer import ShardedTrainer
    from deeplearning4j_tpu.serving import ServingFleet

    devices = list(devices)[:4]
    x, y = lm_batch(size, seed)
    one = build_net(size)
    one_losses = [float(one.fit(DataSet(x, y)))
                  for _ in range(size["mesh_steps"])]

    net = build_net(size)           # same seed: identical init
    trainer = ShardedTrainer(net, MeshConfig(data=2, model=2),
                             devices=devices)
    t0 = time.perf_counter()
    losses = [float(trainer.fit_batch(x, y))
              for _ in range(size["mesh_steps"])]
    train_s = time.perf_counter() - t0
    w = net.params_tree["layer_1"]["Wqkv"]
    param_devices = sorted(f"{s.device.platform}:{s.device.id}"
                           for s in w.addressable_shards)

    shapes = list(size["requests"])
    prompts = prompts_for(size, shapes, seed + 1)
    want, _ = served(one, size, [(prompts, shapes)])
    before = counters()
    slices = [devices[:2], devices[2:]]
    fleet = ServingFleet(one, n_replicas=2, devices=slices,
                         n_slots=size["n_slots"], max_len=size["max_len"])
    try:
        handles = [fleet.submit_async(p, n_new=n_new)
                   for p, (_, n_new) in zip(prompts, shapes)]
        got = collect(handles, before)
        placed = [h.replica for h in handles]
        fstats = fleet.stats()
        tp_gauge = telemetry.get_registry().snapshot()["gauges"].get(
            "generation_server_tp_degree")
    finally:
        fleet.shutdown()
    return {"phase": "mesh", "mesh": {"data": 2, "model": 2},
            "losses": losses, "one_device_losses": one_losses,
            "loss_rtol": size["mesh_loss_rtol"],
            "wqkv_spec": [str(a) for a in w.sharding.spec],
            "param_devices": param_devices, "train_seconds": train_s,
            "replica_devices": [r["devices"] for r in fstats["replicas"]],
            "replica_tp": [r["tp"] for r in fstats["replicas"]],
            "want_replica_devices": [
                [f"{d.platform}:{d.id}" for d in s] for s in slices],
            "tp_degree_gauge": tp_gauge,
            "healthy_replicas": fstats["healthy_replicas"],
            "placed_on": placed,
            "emitted": emitted(got, shapes),
            "requests": [list(s) for s in shapes],
            "counters": delta(before),
            "token_gaps": token_gaps(one, size, got, want),
            "token_gap_tol": TOKEN_GAP_TOL}


def check_mesh(obs: dict) -> None:
    one = np.asarray(obs["one_device_losses"])
    check(bool(np.all(np.isfinite(obs["losses"]))),
          f"sharded loss not finite: {obs['losses']}")
    check(bool(np.all(np.abs(obs["losses"] - one)
                      <= obs["loss_rtol"] * np.abs(one))),
          f"sharded losses {obs['losses']} leave one device's "
          f"{obs['one_device_losses']} by more than "
          f"{obs['loss_rtol']} (relative)")
    check(len(set(obs["param_devices"])) == 4,
          f"Wqkv shards sit on {obs['param_devices']}, not four devices")
    check("model" in obs["wqkv_spec"],
          f"Wqkv spec {obs['wqkv_spec']} does not name 'model'")
    check(obs["replica_devices"] == obs["want_replica_devices"],
          f"replicas span {obs['replica_devices']}, were given "
          f"{obs['want_replica_devices']}")
    check(obs["replica_tp"] == [2, 2] and obs["tp_degree_gauge"] == 2,
          f"tp degree {obs['replica_tp']} / gauge {obs['tp_degree_gauge']}")
    check(obs["healthy_replicas"] == 2, "a replica is not healthy")
    check(obs["emitted"] == [n for _, n in obs["requests"]],
          f"fleet requests emitted {obs['emitted']}")
    check(obs["counters"]["generation_server_tick_failures_total"] == 0
          and obs["counters"]["serve_watchdog_restarts_total"] == 0,
          f"fleet tick failures / restarts: {obs['counters']}")
    check_tokens(obs)


# ---------------------------------------------------------------------------
def cache_entries(path) -> int:
    return len(os.listdir(path)) if path and os.path.isdir(path) else 0


def emit(obs: dict) -> None:
    print(json.dumps(obs), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh phase and its one-device "
                         "comparison (default 1: train + serve)")
    args = ap.parse_args(argv)

    import jax

    from deeplearning4j_tpu.runtime.backend import enable_compile_cache
    if args.chips == 4:
        # the fleet's multi-chip replicas cannot run beside the
        # persistent compile cache on this installation (PERF.md)
        jax.config.update("jax_enable_compilation_cache", False)
        cache_dir = None
    else:
        cache_dir = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r} "
              "— no CPU fallback", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} on {len(devices)} "
              "device(s)", file=sys.stderr)
        return 1
    emit({"phase": "start", "chips": args.chips,
          "device_kind": dev.device_kind, "compile_cache": cache_dir,
          "cache_entries": cache_entries(cache_dir)})

    if args.chips == 4:
        obs = mesh_phase(FULL, devices)
        emit(obs)
        check_mesh(obs)
        # tp > 1 serves through the gather path by construction; the
        # one-device comparison server is what takes the kernel
        check(obs["counters"]['paged_route_total{path="reference_tp"}']
              > 0, "the tp=2 replicas did not take the reference_tp route")
    else:
        net = build_net(FULL)
        obs = train_phase(net, FULL)
        emit(obs)
        check_train(obs)
        check(("flash", FULL["seq"], 128) in
              [tuple(r) for r in obs["routes"]],
              f"flash kernel not routed: {obs['routes']}")
        check(obs["counters"]["flash_fallback_above_threshold_total"]
              == 0, "attention fell back to XLA above the threshold")
        obs = serve_phase(net, FULL)
        emit(obs)
        check_serve(obs)
        for part in (obs, obs["spec"]):
            c = part["counters"]
            check(c['paged_route_total{path="pallas"}'] > 0
                  and c['paged_route_total{path="reference"}'] == 0,
                  f"paged kernels not routed to pallas only: {c}")

    emit({"phase": "end", "compile_cache": cache_dir,
          "cache_entries": cache_entries(cache_dir)})
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
