#!/usr/bin/env python3
"""``study.py``'s serving readings for a configuration whose weights fit
the chip ONCE beside its pools: the last seed's tree is let go (by the
net and by the server's snapshot) before the next seed's is made, where
``study.study_serve`` holds both for a moment (6.06 + 6.06 GB of cell 3
fit beside its state; 6.86 + 6.86 GB beside 4.3 GB of pools do not).
Otherwise the same process, the same readings, one JSON line per seed:

    python3 benchmark/study_lean.py <cell> <first seed> <seeds> [<seconds>]

No benchmark run calls this; PERF.md quotes its readings.
"""
from __future__ import annotations

import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import drivers, run  # noqa: E402


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
            srv._params = net.params_tree = None    # idle: nothing reads them
            gc.collect()
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
                   "control_token_gap": float(max(g.max() for g in control)),
                   "control_token_gap_by_request": [float(g.max())
                                                    for g in control],
                   "control_parted": int(sum((g > 0).sum() for g in control)),
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
        print("study_lean.py: needs a TPU", file=sys.stderr)
        return 1
    for row in study_serve(config, cell, [first + 7919 * i for i in range(n)],
                           seconds):
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
