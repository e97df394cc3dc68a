"""8-process DP x TP x PP distributed worker (round-4 review item 8's
multi-host depth): a config-built zoo.Gpt trains on a 2x2x2 global
mesh whose THREE axes all cross the OS-process boundary — data-sharded
batch, Megatron TP inside the pipeline stage body, GPipe stage params
spread over processes.

Usage: dist_3axis_worker.py <rank> <nproc> <port> <out_dir> <n_steps>
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

from deeplearning4j_tpu.parallel import distributed  # noqa: E402

distributed.initialize(coordinator_address=f"127.0.0.1:{port}",
                       num_processes=nproc, process_id=rank)
assert jax.process_count() == nproc
assert jax.device_count() == nproc

from deeplearning4j_tpu.parallel.mesh import MeshConfig  # noqa: E402
from deeplearning4j_tpu.parallel.trainer import ShardedTrainer  # noqa: E402
from deeplearning4j_tpu.zoo.gpt import Gpt  # noqa: E402

model = Gpt(vocab_size=64, max_len=16, d_model=32, n_layers=4,
            n_heads=4, d_ff=64, seq_len=16, compute_dtype=None,
            use_flash=False, seed=17).init_graph()
trainer = ShardedTrainer(model, MeshConfig(data=2, model=2, pipeline=2),
                         n_micro=2)

# PROOF all three axes cross the process boundary: the stacked block
# kernel is sharded over 'pipeline' (dim 0) AND 'model' (dim 2), and
# its shards live on every process.
wq = trainer._pipe_params["blocks"]["Wqkv"]
spec = str(wq.sharding.spec)
assert "pipeline" in spec and "model" in spec, spec
w_procs = sorted({d.process_index for d in wq.sharding.device_set})
assert len(w_procs) == nproc, w_procs

rng = np.random.default_rng(7)
losses = {}
for step in range(n_steps):
    x = rng.integers(0, 64, (16, 16)).astype(np.int32)
    y = np.roll(x, -1, axis=1)
    losses[step] = float(trainer.fit_batch(x, y))

with open(os.path.join(out_dir, f"rank{rank}.json"), "w") as f:
    json.dump({"losses": losses, "w_procs": w_procs}, f)
print("AXIS3_WORKER_OK")
