"""4-process 2x2 (data x model) distributed worker (round-3 review item 7):
tensor-parallel weight shards CROSS the process boundary; supports
abrupt death of a chosen rank and checkpoint-resume.

Usage: dist_tp_worker.py <rank> <nproc> <port> <out_dir> <n_steps>
       [--die-rank R --die-step N] [--resume]
"""
import json
import os
import sys

os.environ.setdefault("XLA_FLAGS", "")
import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

rank, nproc, port, out_dir, n_steps = (
    int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3]), sys.argv[4],
    int(sys.argv[5]))
die_rank = die_step = None
if "--die-rank" in sys.argv:
    die_rank = int(sys.argv[sys.argv.index("--die-rank") + 1])
    die_step = int(sys.argv[sys.argv.index("--die-step") + 1])
resume = "--resume" in sys.argv

from deeplearning4j_tpu.parallel import distributed  # noqa: E402

distributed.initialize(coordinator_address=f"127.0.0.1:{port}",
                       num_processes=nproc, process_id=rank)
assert jax.process_count() == nproc
assert jax.device_count() == nproc     # 1 CPU device per process

from deeplearning4j_tpu import (MultiLayerNetwork,  # noqa: E402
                                NeuralNetConfiguration)
from deeplearning4j_tpu.nn.conf.layers_core import (  # noqa: E402
    DenseLayer, OutputLayer)
from deeplearning4j_tpu.optimize.updaters import Sgd  # noqa: E402
from deeplearning4j_tpu.parallel.checkpoint import (  # noqa: E402
    ShardedCheckpointer)
from deeplearning4j_tpu.parallel.mesh import MeshConfig  # noqa: E402
from deeplearning4j_tpu.parallel.trainer import ShardedTrainer  # noqa: E402

conf = (NeuralNetConfiguration.builder().seed(11)
        .updater(Sgd(learning_rate=0.1)).list()
        .layer(DenseLayer(n_in=6, n_out=16, activation="tanh"))
        .layer(OutputLayer(n_out=2, activation="softmax", loss="mcxent"))
        .build())
model = MultiLayerNetwork(conf).init()
trainer = ShardedTrainer(model, MeshConfig(data=2, model=2))

# PROOF the TP axis crosses the process boundary: the hidden W must be
# sharded over 'model', and one replica's shards must live on MORE
# than one process.
w = model.params_tree["layer_0"]["W"]
assert "model" in str(w.sharding.spec), w.sharding.spec
w_procs = sorted({d.process_index for d in w.sharding.device_set})
assert len(w_procs) == nproc, w_procs     # fully spread over the mesh

ckpt = ShardedCheckpointer(os.path.join(out_dir, "ckpt"), keep_last=3,
                           async_save=False)
start = 0
if resume:
    _, restored = ckpt.restore_latest(
        {"params": model.params_tree, "opt": model.opt_state,
         "step": 0})
    assert restored is not None, "nothing to resume from"
    model.params_tree = restored["params"]
    model.opt_state = restored["opt"]
    start = int(restored["step"])
    model.iteration_count = start

rng = np.random.default_rng(7)
losses = {}
for step in range(n_steps):
    # identical global batch on every process; device_put scatters it.
    # Draws happen EVERY step so a resumed run replays the stream and
    # sees the same data at the same step index.
    gx = rng.normal(size=(8, 6)).astype(np.float32)
    gy = np.eye(2, dtype=np.float32)[rng.integers(0, 2, 8)]
    if step < start:
        continue
    loss = trainer.fit_batch(gx, gy)
    losses[step] = float(jax.device_get(loss))
    ckpt.save(step + 1, {"params": model.params_tree,
                         "opt": model.opt_state, "step": step + 1})
    if die_step is not None and rank == die_rank and \
            step + 1 >= die_step:
        os._exit(1)        # abrupt preemption of a NON-ZERO rank

with open(os.path.join(out_dir, f"rank{rank}.json"), "w") as f:
    json.dump({"rank": rank, "losses": {str(k): v
                                        for k, v in losses.items()},
               "w_procs": w_procs}, f)
print("TP_WORKER_OK", rank)
