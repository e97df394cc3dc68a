"""The two window loops (``train``, ``serve_closed``), the seeded traffic
generators and the weights' way into the program.  From the program a
driver takes only the system under test (a zoo model's ``fit``, a
``GenerationServer``) and its counters; everything that decides a
number -- the clock, the traffic, the arithmetic -- is here, or in the
configuration's family (``families/post_ln.py`` says what that gives):
the harness knows no architecture.
"""
from __future__ import annotations

import gc
import importlib
import itertools
import time

import numpy as np


def family_of(config: dict):
    """The module the configuration's ``family`` names: shape, weights,
    reference and costs of its architecture."""
    if "family" not in config:
        raise KeyError("the configuration names no \"family\" "
                       "(e.g. \"benchmark.families.post_ln\")")
    return importlib.import_module(config["family"])


def master_dtype(config: dict) -> str:
    """The dtype the configuration states for the weights the program
    is handed (``precision.master_weights``)."""
    return config.get("precision", {}).get("master_weights", "float32")


def build_net(config: dict):
    """The zoo model's own configuration, initialised as ``init_graph()``
    does -- after ``layer_overrides`` ({layer class: {field: value}})
    set what the configuration states and the zoo class has no argument
    for (the blocks' GELU: see PERF.md, Open questions)."""
    from deeplearning4j_tpu.models.multi_layer_network import MultiLayerNetwork
    module, cls = config["zoo_class"].rsplit(".", 1)
    ctor = dict(config["ctor"])
    if "updater" in config:          # {"type": "Adam", "learning_rate": ...}
        from deeplearning4j_tpu.optimize.updaters import updater_from_dict
        ctor["updater"] = updater_from_dict(config["updater"])
    conf = getattr(importlib.import_module(module), cls)(**ctor).conf()
    for ly in conf.layers:
        for field, value in config.get("layer_overrides", {}).get(
                type(ly).__name__, {}).items():
            if not hasattr(ly, field):
                raise AttributeError(f"{type(ly).__name__} has no {field!r}")
            setattr(ly, field, value)
    return MultiLayerNetwork(conf).init()


def seed_tree(family, shape: dict, key, dtype: str = "float32"):
    """The family's reference tree from a key (jit-safe).  Under a
    master dtype below float32 every leaf is rounded to it and carried
    as float32: the reference gets the values the program is handed."""
    import jax
    import jax.numpy as jnp
    w = family.weights_from_key(shape, key)
    if dtype == "float32":
        return w
    return jax.tree_util.tree_map(
        lambda a: a.astype(dtype).astype(jnp.float32), w)


def seed_weights(net, family, shape: dict, seed: int,
                 dtype: str = "float32") -> None:
    """The seed's weights, made on the device in one jitted call, in
    the program's own layout and in the master dtype the configuration
    states: only leaves of that dtype leave the call."""
    import jax
    layout = family.layout_of(net)

    def make(key):
        w = family.weights_from_key(shape, key)
        if dtype != "float32":
            w = jax.tree_util.tree_map(lambda a: a.astype(dtype), w)
        return family.to_program(w, layout)
    net.params_tree = jax.jit(make)(family.seed_key(seed))
    # a fresh optimizer too (the solver, once built, only builds it lazily once)
    net.opt_state = (net._solver.init_opt_state(net.params_tree)
                     if net._solver is not None else None)
    net.iteration_count = 0


def registry_snapshot() -> dict:
    from deeplearning4j_tpu import telemetry
    return telemetry.get_registry().snapshot()


# ---------------------------------------------------------------------------
# train: one call of net.fit(iterator) is the window
# ---------------------------------------------------------------------------
def train_batches(traffic: dict, vocab: int, seed: int) -> list:
    """A ring of distinct host batches from the seed: token ids without
    padding, one-hot labels.  ``labels: "one_class"`` gives every row
    of the run the class the seed draws, so the rows' error signals add
    up instead of cancelling (PERF.md, the look at the seeds that read
    ten times the others); ``"mixed"`` draws a class per row."""
    rng = np.random.default_rng(seed)
    b, t, c = traffic["batch"], traffic["seq"], traffic["classes"]
    one = int(rng.integers(0, c)) if traffic.get("labels") == "one_class" else None
    return [(rng.integers(0, vocab, (b, t)).astype(np.int32),
             np.eye(c, dtype=np.float32)[
                 rng.integers(0, c, b) if one is None else np.full(b, one)])
            for _ in range(traffic["ring"])]


class RingIterator:
    """Hands out the ring's batches from ``start`` on: ``count`` of
    them, or until the host clock passes ``deadline``."""

    def __init__(self, ring, start, count=None, deadline=None):
        from deeplearning4j_tpu.data.dataset import DataSet
        self.ring = [DataSet(x, y) for x, y in ring]
        self.start, self.count, self.deadline = start, count, deadline
        self.pre_processor = None

    def __iter__(self):
        i = self.start
        while (i - self.start < self.count if self.count is not None
               else time.perf_counter() < self.deadline):
            yield self.ring[i % len(self.ring)]
            i += 1

    def reset(self):
        pass

    def batch_size(self):
        return self.ring[0].num_examples()

    def total_outcomes(self):
        return None


class LossTap:
    """A listener as a logging job has one: keeps every step's loss (a
    device scalar) and reads back the one of ``lag`` steps ago, so the
    host never runs more than ``lag`` steps ahead of the device.  In a
    traced run it also opens and closes the traced sub-window, each at
    a step whose loss it has waited for."""

    def __init__(self, lag: int = 2):
        self.lag, self.losses = lag, []
        self.on_step = None            # called with the step count
        self._span = None              # host span: this step's end to the next's

    def iteration_done(self, model, iteration, epoch, score):
        import jax
        if self._span is not None:
            self._span.__exit__(None, None, None)
        self.losses.append(score)
        if len(self.losses) > self.lag:
            with jax.profiler.TraceAnnotation("bench/loss_readback"):
                self.losses[-1 - self.lag].block_until_ready()
        if self.on_step is not None:
            self.on_step(len(self.losses))
        self._span = jax.profiler.TraceAnnotation("bench/next_batch_and_dispatch")
        self._span.__enter__()

    def on_epoch_start(self, model, epoch):
        pass

    def on_epoch_end(self, model, epoch):
        pass


def first_steps(net, family, tap, ring, adam: dict, shape: dict, seed: int,
                steps: int = 3) -> dict:
    """Drive the net from the seed's weights through its first steps by
    the window's own call and feed; read what the reference follows:
    each loss, the first gradient's norms as Adam got them (its first
    moment after one step is (1 - beta1) * g) and the norms of the
    parameters' change after the last step."""
    import jax
    layout = family.layout_of(net)
    norms = jax.jit(lambda tree: family.leaf_norms(
        family.from_program(tree, layout)))
    net.fit(RingIterator(ring, 0, count=1))
    grad_norms = {k: np.asarray(v) / (1.0 - adam["beta1"])
                  for k, v in jax.device_get(norms(net.opt_state["m"])).items()}
    net.fit(RingIterator(ring, 1, count=steps - 1))
    change = jax.jit(lambda tree, key: family.leaf_norms(
        jax.tree_util.tree_map(
            jax.numpy.subtract, family.from_program(tree, layout),
            family.weights_from_key(shape, key))))
    change_norms = jax.device_get(change(net.params_tree,
                                         family.seed_key(seed)))
    return {"losses": [float(l) for l in tap.losses[:steps]],
            "grad_norms": grad_norms, "change_norms": change_norms}


def run_train(family, config, cell, seed, seconds, tracer, note_setup_done):
    """Returns (facts, first-steps readings, a closure that frees the
    program's state)."""
    import jax
    shape, traffic = family.shape_of(config), cell["traffic"]
    net = build_net(config)
    seed_weights(net, family, shape, seed, master_dtype(config))
    ring = train_batches(traffic, shape["vocab"], seed)
    tap = LossTap(traffic.get("loss_lag", 2))
    net.set_listeners(tap)
    first = first_steps(net, family, tap, ring, config["adam"], shape, seed)
    done_before = len(tap.losses)

    traced = {}
    if tracer is not None:
        a = done_before + traffic["trace"]["after_steps"]
        b = a + traffic["trace"]["steps"]

        def on_step(n):
            if n in (a, b):
                tap.losses[-1].block_until_ready()
                if n == a:     # snapshots INSIDE the profiler's start and stop,
                    tracer.start()  # each mark on the profile's clock too
                    traced["before"] = registry_snapshot()
                    with jax.profiler.TraceAnnotation("bench/trace0"):
                        traced["t0"] = time.perf_counter()
                else:
                    with jax.profiler.TraceAnnotation("bench/trace1"):
                        traced["t1"] = time.perf_counter()
                    traced["after"] = registry_snapshot()
                    tracer.stop()
        tap.on_step = on_step

    note_setup_done()
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench/fit"):
        net.fit(RingIterator(ring, done_before, deadline=t0 + seconds))
    t1 = time.perf_counter()         # fit has read the last loss back
    tap._span.__exit__(None, None, None)
    losses = np.asarray([float(l) for l in tap.losses[done_before:]])
    tokens_per_step = traffic["batch"] * traffic["seq"]
    facts = {"window_s": t1 - t0, "steps": len(losses),
             "failed": int(np.sum(~np.isfinite(losses))),
             "tokens": len(losses) * tokens_per_step,
             "last_loss": float(losses[-1])}
    if "t1" in traced:
        n = traffic["trace"]["steps"]
        facts["traced"] = {
            "window_s": traced["t1"] - traced["t0"], "steps": n,
            "tokens": n * tokens_per_step,
            "flops": n * tokens_per_step * family.train_flops_per_token(
                shape, traffic["seq"]),
            "before": traced["before"], "after": traced["after"]}

    def free():
        net.params_tree = net.opt_state = net._solver = None
        tap.losses.clear()
        gc.collect()
    return facts, first, free


# ---------------------------------------------------------------------------
# serve: a closed loop of clients over one GenerationServer
# ---------------------------------------------------------------------------
def _draw(spec: dict, rng, n: int) -> np.ndarray:
    if spec["dist"] == "uniform":
        return rng.integers(spec["lo"], spec["hi"] + 1, n)
    if spec["dist"] == "lognormal":
        x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
        return np.clip(np.rint(x), spec.get("lo", 1), spec["cap"]).astype(int)
    if spec["dist"] == "fixed":
        return np.full(n, spec["value"], int)
    raise ValueError(f"unknown distribution {spec['dist']!r}")


def serve_requests(traffic: dict, vocab: int, seed: int) -> list:
    """One sequence of (prompt ids, n_new) per client.  The sizes come
    from the mix's own ``sizes_seed``: every seed sends the SAME
    sequences of sizes, so every seed does the same work; the run's
    seed decides which client gets which sequence and every token id.
    ``shared_prefix`` tokens open every prompt alike."""
    clients = traffic["clients"]
    per = max(1, traffic["n_sizes"] // clients)
    sizes = np.random.default_rng(traffic["sizes_seed"])
    lens = _draw(traffic["prompt_len"], sizes, clients * per).reshape(clients, per)
    news = _draw(traffic["n_new"], sizes, clients * per).reshape(clients, per)
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, vocab, traffic.get("shared_prefix", 0))
    out = []
    for row in rng.permutation(clients):
        seq = []
        for n, new in zip(lens[row], news[row]):
            p = rng.integers(0, vocab, int(n)).astype(np.int32)
            k = min(len(prefix), len(p) - 1)
            p[:k] = prefix[:k]
            seq.append((p, int(new)))
        out.append(seq)
    return out


def admit_bucket(n: int, limit: int, block: int) -> int:
    """The prefill length the server pads a prompt of ``n`` to: the
    next power of two (at most ``limit``), rounded up to whole blocks.
    A copy of the program's rule, used only to choose warm-up prompts."""
    b = 1
    while b < n and b < limit:
        b *= 2
    return -(-min(b, limit) // block) * block


def warm_server(srv, traffic: dict, server_kw: dict, vocab: int) -> None:
    """One solo request per admit bucket the mix's prompt lengths hit;
    its 15 new tokens run the decode scans of 8, 4, 2 and 1 ticks."""
    spec = traffic["prompt_len"]
    lo, hi = spec.get("lo", 1), spec.get("hi", spec.get("cap"))
    block, limit = server_kw["block_size"], server_kw["max_len"]
    by_bucket = {}
    for n in range(lo, hi + 1):
        by_bucket[admit_bucket(n, limit, block)] = n
    rng = np.random.default_rng(0)
    for n in by_bucket.values():
        srv.submit_async(rng.integers(0, vocab, n).astype(np.int32),
                         n_new=15).result(timeout=900)
    ran = {k for k, v in registry_snapshot()["counters"].items()
           if k.startswith("generation_server_scan_ticks_total") and v > 0}
    want = set()
    k = 1
    while k <= server_kw["tick_batch"]:
        want.add(f'generation_server_scan_ticks_total{{k="{k}"}}')
        k *= 2
    if not want <= ran:
        raise RuntimeError(f"warm-up left scan lengths cold: {want - ran}")


class _Req:
    __slots__ = ("prompt", "n_new", "h", "t_submit", "t_first", "t_done",
                 "marks", "tokens", "error", "seen")


def _submit(srv, item, now):
    import jax
    r = _Req()
    r.prompt, r.n_new = item
    r.t_submit, r.t_first, r.t_done = now, None, None
    r.marks, r.tokens, r.error, r.seen = {}, None, None, 0
    with jax.profiler.TraceAnnotation("bench/submit"):
        r.h = srv.submit_async(r.prompt, n_new=r.n_new)
    return r


def closed_loop(srv, requests, ramp_s: float, seconds: float,
                trace_s: float, tracer, on_open, poll_s: float = 0.004,
                settle_s: float = 1.0):
    """One caller per sequence in ``requests``, each sending its next
    request when the last one returned; one thread polls them all, every
    ``poll_s`` seconds (the mix's ``poll_ms``).  Each poll takes the
    interpreter lock from the scheduler's thread: at 1 ms that cost cell
    ``closed-decode`` nothing on one machine and 6% of its tokens on
    another, at 4 ms nothing on either (PERF.md, PR 27), so a first
    token is stamped up to 4 ms late.  The window opens ``ramp_s``
    seconds into the same traffic and closes ``seconds`` later, each
    boundary ON THE FIRST POLL THAT SEES NO NEW TOKEN AFTER ONE THAT
    DID: tokens land a whole decode scan at a time (up to 8 ticks x 64
    slots), and a boundary at a random moment of a scan miscounts the
    window's tokens by up to a scan at each end (0.55% of 30 s of
    ``closed-decode``); just after a landing, the tokens between two
    boundaries are those of the time between them.  A boundary waits at
    most ``settle_s`` for that.
    In a traced run the sub-window's two boundaries, 'trace0' and
    'trace1', are also host spans ``bench/trace0`` / ``bench/trace1`` on
    the profile's clock, each followed by a registry snapshot: the trace
    is cut to them and the counters cover the same interval.
    Returns (finished, in_flight, marks, snapshots): every request
    keeps, under ``marks``, the tokens it had emitted at each boundary
    it was in flight at ('open', 'close', and 'trace0'/'trace1' in a
    traced run); ``snapshots`` holds the registry at 'trace0' and
    'trace1' (empty when untraced).
    """
    import jax
    feeds = [itertools.cycle(seq) for seq in requests]
    clients = len(requests)
    live = [None] * clients
    finished, times, snaps = [], {}, {}
    t_begin, t_traced, landed_before = time.perf_counter(), None, False

    def mark(name):
        times[name] = time.perf_counter()
        for r in live:
            if r is not None:
                r.marks[name] = r.h.emitted

    def traced_mark(name):
        with jax.profiler.TraceAnnotation("bench/" + name):
            mark(name)
        snaps[name] = registry_snapshot()

    while True:
        now, landed = time.perf_counter(), False
        for c in range(clients):
            r = live[c]
            if r is not None:
                done, emitted = r.h.done(), r.h.emitted
                landed, r.seen = landed or emitted != r.seen, emitted
                if r.t_first is None and emitted > 0:
                    r.t_first = now
                if done:
                    r.t_done = now
                    if r.t_first is None:
                        r.t_first = now
                    try:
                        with jax.profiler.TraceAnnotation("bench/result"):
                            r.tokens = r.h.result(timeout=0)
                    except Exception as e:  # a failed request is counted
                        r.error = repr(e)
                    finished.append(r)
                    r = None
            if r is None:
                r = _submit(srv, next(feeds[c]), time.perf_counter())
            live[c] = r
        settled, landed_before = landed_before and not landed, landed

        def due(when):
            return now >= when and (settled or now >= when + settle_s)
        if "open" not in times:
            if due(t_begin + ramp_s):
                mark("open")
                on_open()
                if tracer is not None:
                    tracer.start()
                    t_traced = time.perf_counter()
        elif tracer is not None and "trace0" not in times:
            if due(t_traced):
                traced_mark("trace0")
        elif tracer is not None and "trace1" not in times:
            if due(times["trace0"] + trace_s):
                traced_mark("trace1")
                tracer.stop()
        if "open" in times and due(times["open"] + seconds):
            mark("close")
            break
        time.sleep(poll_s)      # no span: an idle gap is then named by
                                # what the scheduler's thread was doing
    return finished, [r for r in live if r is not None], times, snaps


def _emitted_between(reqs, a: str, b: str, t_a: float, t_b: float):
    """For each request, the new tokens it emitted between boundaries
    ``a`` and ``b``: [(request, first new token, last new token)],
    tokens counted from 1."""
    out = []
    for r in reqs:
        if r.error is not None or r.t_submit >= t_b:
            continue
        if r.t_done is not None and r.t_done < t_a:
            continue
        lo = r.marks.get(a, 0)
        hi = r.marks.get(b, r.n_new if r.t_done is not None else 0)
        if hi > lo:
            out.append((r, lo + 1, hi))
    return out


def _serve_work(family, shape, spans, t_a, t_b) -> dict:
    """Tokens of the work between two boundaries, and what the family
    counts for them: forward operations and its kernels' further facts."""
    triples = [(len(r.prompt), lo, hi) for r, lo, hi in spans]
    return {"window_s": t_b - t_a,
            "tokens": float(sum(hi - lo + 1 for _, lo, hi in triples)),
            **family.serve_work(shape, triples)}


def run_serve(family, config, cell, seed, seconds, tracer, note_setup_done):
    from deeplearning4j_tpu.parallel import GenerationServer
    shape, traffic, server_kw = family.shape_of(config), cell["traffic"], cell["server"]
    net = build_net(config)
    seed_weights(net, family, shape, seed, master_dtype(config))
    srv = GenerationServer(net, **server_kw)
    snaps = {}
    try:
        warm_server(srv, traffic, server_kw, shape["vocab"])
        requests = serve_requests(traffic, shape["vocab"], seed)

        def on_open():
            note_setup_done()
            snaps["open"] = registry_snapshot()

        finished, in_flight, times, traced_snaps = closed_loop(
            srv, requests, traffic["ramp_seconds"],
            seconds, traffic["trace_seconds"], tracer, on_open,
            poll_s=1e-3 * traffic.get("poll_ms", 4.0))
        snaps["close"] = registry_snapshot()
    finally:
        srv.shutdown(drain=False, timeout=30.0)

    t_open, t_close = times["open"], times["close"]
    done = [r for r in finished if r.t_done >= t_open]
    ok = [r for r in done if r.error is None]
    inf = float("inf")
    ttft = [r.t_first - r.t_submit if r.error is None else inf
            for r in finished + in_flight
            if r.t_first is not None and t_open <= r.t_first <= t_close
            or (r.error is not None and r.t_done >= t_open)]
    tpot = [(r.t_done - r.t_first) / max(1, r.n_new - 1)
            if r.error is None else inf for r in done]
    everyone = finished + in_flight
    facts = {"requests_done": len(done), "failed": len(done) - len(ok),
             "ttft_s": ttft, "tpot_s": tpot,
             # what the program counted over the window, for the run's
             # stderr: the scheduler's host seconds, scans by length ...
             "counted": {k: v - snaps["open"]["counters"].get(k, 0.0)
                         for k, v in snaps["close"]["counters"].items()
                         if v != snaps["open"]["counters"].get(k, 0.0)},
             "tick_failures": _delta(snaps["open"], snaps["close"],
                                     "generation_server_tick_failures_total"),
             **_serve_work(family, shape, _emitted_between(
                 everyone, "open", "close", t_open, t_close), t_open, t_close)}
    if "trace1" in times:
        facts["traced"] = {
            **_serve_work(family, shape, _emitted_between(
                everyone, "trace0", "trace1", times["trace0"],
                times["trace1"]), times["trace0"], times["trace1"]),
            # submitted and answered inside the sub-window: the
            # profiler's start and stop stall whoever spans them
            "ttft_s": [r.t_first - r.t_submit for r in everyone
                       if r.error is None and r.t_first is not None
                       and times["trace0"] <= r.t_submit
                       and r.t_first <= times["trace1"]],
            # first token and last inside the sub-window, for the same
            # reason
            "tpot_s": [(r.t_done - r.t_first) / max(1, r.n_new - 1)
                       for r in finished if r.error is None
                       and times["trace0"] <= r.t_first
                       and r.t_done <= times["trace1"]],
            "before": traced_snaps["trace0"], "after": traced_snaps["trace1"]}
    sample = [(np.asarray(r.tokens), len(r.prompt), r.n_new)
              for r in pick_sample(ok, traffic["compare_requests"], seed)]

    def free():
        nonlocal srv, net
        srv = net = None
        gc.collect()
    return facts, sample, free


def pick_sample(ok: list, n: int, seed: int) -> list:
    """The requests that are compared: the longest finished one and
    ``n - 1`` more drawn from the seed."""
    by_len = sorted(ok, key=lambda r: -len(r.tokens))
    if not by_len:
        return []
    rest = np.random.default_rng(seed).choice(
        np.arange(1, len(by_len)), min(n - 1, len(by_len) - 1), replace=False)
    return by_len[:1] + [by_len[i] for i in sorted(rest)]


def _delta(before: dict, after: dict, series: str, kind="counters") -> float:
    return after[kind].get(series, 0.0) - before[kind].get(series, 0.0)
