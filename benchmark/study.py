#!/usr/bin/env python3
"""Where the limits of ``correct`` come from.  In ONE process on the
chip, over several seeds at the cell's own size, read every compared
number three ways: the program against the family's reference (the
lower reading), the control -- that reference one precision down, the
family's ``CONTROL`` -- against the reference, and for training the
planted half-batch fault (the upper readings).  One JSON line per seed:

    python3 benchmark/study.py <cell> <first seed> <seeds> [<seconds>]

No benchmark run calls this; PERF.md quotes its readings.
"""
from __future__ import annotations

import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import correct, drivers, run  # noqa: E402


def _values(numbers: dict) -> dict:
    return {k: [float(v), str(about)] for k, (v, about) in numbers.items()}


def _worst(program: dict, ref: dict, n: int = 4) -> list:
    """The look at the worst leaves: [leaf, gap, the reference's norm of
    it, the median leaf's norm]."""
    import numpy as np
    gaps, r = correct.leaf_gaps(program, ref), correct._flat(ref)
    median = float(np.median(list(r.values())))
    return [[k, gaps[k], r[k], median]
            for k in sorted(gaps, key=gaps.get, reverse=True)[:n]]


def study_train(config, cell, seeds):
    family = drivers.family_of(config)
    shape, traffic = family.shape_of(config), cell["traffic"]
    net = drivers.build_net(config)
    tap = drivers.LossTap(traffic.get("loss_lag", 2))
    net.set_listeners(tap)
    for seed in seeds:
        t = time.perf_counter()
        drivers.seed_weights(net, family, shape, seed, drivers.master_dtype(config))
        tap.losses.clear()
        ring = drivers.train_batches(traffic, shape["vocab"], seed)
        program = drivers.first_steps(net, family, tap, ring, config["adam"],
                                      shape, seed)
        net.params_tree = net.opt_state = None      # room for the reference
        gc.collect()
        follow = lambda **kw: family.follow_training(
            shape, config["adam"], seed, ring[:3], cell["reference_rows"], **kw)
        ref = follow()
        raw = os.environ.get("STUDY_RAW_DIR")     # every leaf's norms, to look at
        if raw:
            flat = lambda r: {k: correct._flat(r[k]) for k in ("grad_norms", "change_norms")}
            with open(os.path.join(raw, f"norms_{seed}.json"), "w") as f:
                json.dump({"program": flat(program), "ref": flat(ref),
                           "control_fp8": flat(follow(quant=family.CONTROL)),
                           "half_batch": flat(follow(batch_rows=traffic["batch"] // 2))}, f)
        yield {"seed": seed, "losses": program["losses"], "ref_losses": ref["losses"],
               "program": _values(correct.training_numbers(program, ref)),
               "control_fp8": _values(correct.training_numbers(
                   follow(quant=family.CONTROL), ref)),
               "half_batch": _values(correct.training_numbers(
                   follow(batch_rows=traffic["batch"] // 2), ref)),
               "quiet_leaves": sorted(correct.quiet_leaves(ref["grad_norms"])),
               "worst_grad_leaves": _worst(program["grad_norms"], ref["grad_norms"]),
               "seconds": time.perf_counter() - t}


def study_serve(config, cell, seeds, seconds):
    from deeplearning4j_tpu.parallel import GenerationServer
    family, dtype = drivers.family_of(config), drivers.master_dtype(config)
    shape, traffic, kw = family.shape_of(config), cell["traffic"], cell["server"]
    net = drivers.build_net(config)
    drivers.seed_weights(net, family, shape, seeds[0], dtype)
    with GenerationServer(net, **kw) as srv:
        drivers.warm_server(srv, traffic, kw, shape["vocab"])
        for seed in seeds:
            t = time.perf_counter()
            drivers.seed_weights(net, family, shape, seed, dtype)
            srv.refresh_params()
            finished, in_flight, *_ = drivers.closed_loop(
                srv, drivers.serve_requests(traffic, shape["vocab"], seed),
                0.0, seconds, 0.0, None, lambda: None)
            for r in in_flight:
                r.h.cancel()
            while srv.stats()["live_slots"] or srv.stats()["queue_depth"]:
                time.sleep(0.05)
            picked = drivers.pick_sample(
                [r for r in finished if r.error is None],
                traffic["compare_requests"], seed)
            w = drivers.seed_tree(family, shape, family.seed_key(seed), dtype)
            gaps = lambda quant: [family.served_token_gaps(
                w, shape, r.tokens, len(r.prompt), quant) for r in picked]
            served, control = gaps(None), gaps(family.CONTROL)
            yield {"seed": seed, "finished": len(finished),
                   "failed": sum(r.error is not None for r in finished),
                   "tokens_compared": int(sum(len(g) for g in served)),
                   "program_token_gap": float(max(g.max() for g in served)),
                   "tokens_parted": int(sum((g > 0).sum() for g in served)),
                   "control_fp8_token_gap": float(max(g.max() for g in control)),
                   "control_fp8_parted": int(sum((g > 0).sum() for g in control)),
                   "program_gaps_top5": sorted(
                       float(x) for g in served for x in g)[-5:],
                   "seconds": time.perf_counter() - t}
            del w
            gc.collect()


def main(argv) -> int:
    import jax
    name, first, n = argv[0], int(argv[1]), int(argv[2])
    seconds = float(argv[3]) if len(argv) > 3 else 14.0
    _, cell, config = run.cell_files(name)
    if jax.devices()[0].platform != "tpu":
        print("study.py: needs a TPU", file=sys.stderr)
        return 1
    seeds = [first + 7919 * i for i in range(n)]
    rows = (study_train(config, cell, seeds) if cell["driver"] == "train"
            else study_serve(config, cell, seeds, seconds))
    for row in rows:
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
