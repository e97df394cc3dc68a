"""Multi-process distributed + preemption tests (review item 7).

DL4J analogues: ``ModelParameterServerTest`` (multiple server instances
over loopback Aeron) and Spark ``local[N]`` tests — here they are REAL
separate OS processes joined by ``jax.distributed`` over loopback gRPC,
and a real SIGKILL mid-training with orbax resume.
"""
import json
import os
import signal
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

WORKERS = os.path.join(os.path.dirname(__file__), "workers")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env():
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)  # workers force their own CPU platform
    return env


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.slow
def test_two_process_distributed_dp(tmp_path):
    """2 OS processes, 1 CPU device each, global mesh data=2: both ranks
    must see process_count==2, train 5 steps, and report IDENTICAL
    global-loss sequences (the all-reduce crosses the process boundary)."""
    port = _free_port()
    procs = [subprocess.Popen(
        [sys.executable, os.path.join(WORKERS, "dist_train_worker.py"),
         str(rank), "2", str(port), str(tmp_path)],
        env=_env(), stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        for rank in range(2)]
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=300)
        outs.append(out.decode())
    for rank, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {rank} failed:\n{out[-3000:]}"
        assert "WORKER_OK" in out
    r0 = json.load(open(tmp_path / "rank0.json"))
    r1 = json.load(open(tmp_path / "rank1.json"))
    assert len(r0["losses"]) == 5
    np.testing.assert_allclose(r0["losses"], r1["losses"], rtol=1e-6)
    # and training made progress
    assert r0["losses"][-1] < r0["losses"][0]


@pytest.mark.slow
def test_preemption_kill_and_resume(tmp_path):
    """SIGKILL-style abrupt exit mid-training; resume from the orbax
    checkpoint must reproduce the uninterrupted run's loss trajectory
    exactly (dropout-free model, deterministic batch order)."""
    ck1, ck2 = str(tmp_path / "ck_ref"), str(tmp_path / "ck_preempt")
    ref_out = str(tmp_path / "ref.json")
    res_out = str(tmp_path / "resumed.json")
    run = lambda args: subprocess.run(
        [sys.executable, os.path.join(WORKERS, "preempt_worker.py"), *args],
        env=_env(), capture_output=True, timeout=300)

    # uninterrupted reference: 10 steps
    r = run([ck1, ref_out, "10"])
    assert r.returncode == 0, r.stdout.decode() + r.stderr.decode()

    # preempted run: dies abruptly (os._exit, no cleanup) after step >= 6
    r = run([ck2, str(tmp_path / "x.json"), "10", "--kill-after", "6"])
    assert r.returncode == 0
    assert not (tmp_path / "x.json").exists()  # really died mid-run

    # resume and finish
    r = run([ck2, res_out, "10", "--resume"])
    assert r.returncode == 0, r.stdout.decode() + r.stderr.decode()

    ref = json.load(open(ref_out))
    res = json.load(open(res_out))
    assert res["final_iteration"] == 10
    resumed_steps = sorted(int(k) for k in res["losses"])
    # The abrupt exit may kill an in-flight async orbax save; resume must
    # come from the last COMPLETE checkpoint (>= step 2), never step 0.
    assert resumed_steps[0] >= 2
    for k in res["losses"]:
        np.testing.assert_allclose(res["losses"][k], ref["losses"][k],
                                   rtol=1e-5, err_msg=f"step {k}")


def _launch_tp(port, out_dir, n_steps, extra=()):
    return [subprocess.Popen(
        [sys.executable, os.path.join(WORKERS, "dist_tp_worker.py"),
         str(rank), "4", str(port), str(out_dir), str(n_steps), *extra],
        env=_env(), stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        for rank in range(4)]


@pytest.mark.slow
def test_four_process_2x2_tp_across_boundary(tmp_path):
    """4 OS processes, 2x2 (data x model) global mesh: the hidden
    weight's TP shards live on ALL FOUR processes (tensor parallelism
    crosses the process boundary), every rank reports the identical
    loss sequence, and that sequence matches a single-process run of
    the same mesh semantics (round-3 review item 7)."""
    port = _free_port()
    out = tmp_path / "tp4"
    out.mkdir()
    procs = _launch_tp(port, out, 5)
    outs = [p.communicate(timeout=420)[0].decode() for p in procs]
    for rank, (p, o) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {rank}:\n{o[-3000:]}"
        assert "TP_WORKER_OK" in o
    ranks = [json.load(open(out / f"rank{r}.json")) for r in range(4)]
    for r in ranks:
        assert r["w_procs"] == [0, 1, 2, 3]      # TP spans processes
    for r in ranks[1:]:
        for k in ranks[0]["losses"]:
            np.testing.assert_allclose(r["losses"][k],
                                       ranks[0]["losses"][k], rtol=1e-6)

    # single-process reference with the same 2x2 mesh on 4 local
    # virtual devices: identical semantics => identical losses
    import jax
    from deeplearning4j_tpu import (MultiLayerNetwork,
                                    NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf.layers_core import (DenseLayer,
                                                        OutputLayer)
    from deeplearning4j_tpu.optimize.updaters import Sgd
    from deeplearning4j_tpu.parallel.mesh import MeshConfig
    from deeplearning4j_tpu.parallel.trainer import ShardedTrainer
    conf = (NeuralNetConfiguration.builder().seed(11)
            .updater(Sgd(learning_rate=0.1)).list()
            .layer(DenseLayer(n_in=6, n_out=16, activation="tanh"))
            .layer(OutputLayer(n_out=2, activation="softmax",
                               loss="mcxent"))
            .build())
    model = MultiLayerNetwork(conf).init()
    trainer = ShardedTrainer(model, MeshConfig(data=2, model=2),
                             devices=jax.devices()[:4])
    rng = np.random.default_rng(7)
    for step in range(5):
        gx = rng.normal(size=(8, 6)).astype(np.float32)
        gy = np.eye(2, dtype=np.float32)[rng.integers(0, 2, 8)]
        ref = float(trainer.fit_batch(gx, gy))
        np.testing.assert_allclose(ranks[0]["losses"][str(step)], ref,
                                   rtol=1e-5, err_msg=f"step {step}")


@pytest.mark.slow
def test_four_process_preempt_nonzero_rank_and_resume(tmp_path):
    """SIGKILL-style death of rank 2 (a NON-zero rank) mid-training;
    a fresh 4-process session resumes from the last complete sharded
    checkpoint and finishes with the uninterrupted run's losses."""
    # uninterrupted reference
    port = _free_port()
    ref_dir = tmp_path / "ref"
    ref_dir.mkdir()
    procs = _launch_tp(port, ref_dir, 6)
    for rank, p in enumerate(procs):
        o = p.communicate(timeout=420)[0].decode()
        assert p.returncode == 0, f"ref rank {rank}:\n{o[-3000:]}"
    ref = json.load(open(ref_dir / "rank0.json"))["losses"]

    # preempted run: rank 2 dies abruptly after step 3's checkpoint
    port = _free_port()
    out = tmp_path / "pre"
    out.mkdir()
    procs = _launch_tp(port, out, 6,
                       extra=("--die-rank", "2", "--die-step", "3"))
    procs[2].wait(timeout=420)
    assert procs[2].returncode == 1          # really died
    for rank in (0, 1, 3):                   # survivors block on the
        try:                                 # dead rank's collective
            procs[rank].wait(timeout=20)
        except subprocess.TimeoutExpired:
            procs[rank].kill()
            procs[rank].wait()
    assert not (out / "rank0.json").exists()  # run really incomplete

    # fresh session resumes from the last COMPLETE checkpoint
    port = _free_port()
    procs = _launch_tp(port, out, 6, extra=("--resume",))
    for rank, p in enumerate(procs):
        o = p.communicate(timeout=420)[0].decode()
        assert p.returncode == 0, f"resume rank {rank}:\n{o[-3000:]}"
    res = json.load(open(out / "rank0.json"))["losses"]
    assert res, "resume made no progress"
    for k, v in res.items():
        np.testing.assert_allclose(v, ref[k], rtol=1e-5,
                                   err_msg=f"step {k}")


def _launch_fleet(port, out_dir, mode, phase, n_epochs=2, nproc=2,
                  extra=()):
    return [subprocess.Popen(
        [sys.executable, os.path.join(WORKERS, "fleet_worker.py"),
         str(rank), str(nproc), str(port), str(out_dir), mode,
         str(n_epochs), phase, *extra],
        env=_env(), stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        for rank in range(nproc)]


def _fleet_kill_mid_step(tmp_path, mode):
    """Shared body: REAL SIGTERM to rank 1 mid-step -> the in-band flag
    or-reduce checkpoints EVERY rank at the SAME step -> a fresh fleet
    session rendezvouses, agrees the common checkpoint, and finishes
    with byte-identical final params vs. the uninterrupted run."""
    out = tmp_path / mode
    out.mkdir()

    # uninterrupted reference fleet
    port = _free_port()
    procs = _launch_fleet(port, out, mode, "ref")
    for rank, p in enumerate(procs):
        o = p.communicate(timeout=420)[0].decode()
        assert p.returncode == 0, f"ref rank {rank}:\n{o[-3000:]}"
        assert "FLEET_WORKER_OK" in o
    ref = json.load(open(out / "ref_rank0.json"))

    # preempted fleet: ONLY rank 1 receives the (self-delivered, real)
    # SIGTERM; coordination must stop BOTH ranks at the same step
    port = _free_port()
    procs = _launch_fleet(port, out, mode, "preempt",
                          extra=("--preempt-rank", "1",
                                 "--preempt-iter", "3"))
    for rank, p in enumerate(procs):
        o = p.communicate(timeout=420)[0].decode()
        assert p.returncode == 0, f"preempt rank {rank}:\n{o[-3000:]}"
        assert "FLEET_PREEMPTED" in o
    marks = [json.load(open(out / f"preempt_rank{r}.json"))
             for r in range(2)]
    assert marks[0]["step"] == marks[1]["step"] == 3, marks

    # fresh fleet session resumes from the agreed common checkpoint
    port = _free_port()
    procs = _launch_fleet(port, out, mode, "resume")
    for rank, p in enumerate(procs):
        o = p.communicate(timeout=420)[0].decode()
        assert p.returncode == 0, f"resume rank {rank}:\n{o[-3000:]}"
        assert "FLEET_WORKER_OK" in o
    res = json.load(open(out / "resume_rank0.json"))
    assert res["final_iteration"] == ref["final_iteration"]
    # the continuation replays the reference's loss trajectory exactly
    for k, v in res["losses"].items():
        np.testing.assert_allclose(v, ref["losses"][k], rtol=0,
                                   atol=0, err_msg=f"step {k}")
    # and the final parameters are BYTE-identical
    assert res["params_sha"] == ref["params_sha"]


def _fleet_elastic_resume(tmp_path, mode, n_from, n_to):
    """Shared body (ISSUE 10): an ``n_from``-process fleet is REALLY
    SIGTERM'd mid-step (coordinated checkpoint at one step, world
    recorded beside it), then resumes at ``n_to`` processes through
    the elastic path — survivor_rendezvous before initialize, fleet
    rendezvous + agreement, N→M state resharding — and must finish
    BYTE-IDENTICAL to a plain (fleet-machinery-free) ``n_to``-process
    resume of a copy of the same checkpoint."""
    import shutil
    out = tmp_path / f"{mode}_{n_from}to{n_to}"
    out.mkdir()

    # preempt phase: the LAST rank self-SIGTERMs at iteration 3; the
    # in-band or-reduce checkpoints every rank at the same step
    port = _free_port()
    procs = _launch_fleet(port, out, mode, "preempt", nproc=n_from,
                          extra=("--preempt-rank", str(n_from - 1),
                                 "--preempt-iter", "3"))
    for rank, p in enumerate(procs):
        o = p.communicate(timeout=420)[0].decode()
        assert p.returncode == 0, f"preempt rank {rank}:\n{o[-3000:]}"
        assert "FLEET_PREEMPTED" in o
    marks = [json.load(open(out / f"preempt_rank{r}.json"))
             for r in range(n_from)]
    assert len({m["step"] for m in marks}) == 1 and \
        marks[0]["step"] == 3, marks

    # independent copy for the no-fleet-machinery control restore
    ref_dir = tmp_path / f"{mode}_{n_from}to{n_to}_ref"
    shutil.copytree(out, ref_dir)

    # ELASTIC resume at n_to processes (survivor_rendezvous elects the
    # world; the restore reshards N→M)
    port = _free_port()
    procs = _launch_fleet(port, out, mode, "resume", nproc=n_to)
    for rank, p in enumerate(procs):
        o = p.communicate(timeout=420)[0].decode()
        assert p.returncode == 0, f"resume rank {rank}:\n{o[-3000:]}"
        assert "FLEET_WORKER_OK" in o
    res = json.load(open(out / "resume_rank0.json"))
    direction = "elastic_shrink" if n_to < n_from else "elastic_grow"
    assert res[direction] >= 1, res     # the transition was DETECTED

    # control: plain resume of the same checkpoint at n_to, no fleet
    port = _free_port()
    procs = _launch_fleet(port, ref_dir, mode, "plainresume",
                          nproc=n_to)
    for rank, p in enumerate(procs):
        o = p.communicate(timeout=420)[0].decode()
        assert p.returncode == 0, \
            f"plainresume rank {rank}:\n{o[-3000:]}"
        assert "FLEET_WORKER_OK" in o
    ref = json.load(open(ref_dir / "resume_rank0.json"))

    # the elastic fleet path is exactly the plain restore + training:
    # identical loss trajectory and BYTE-identical final params
    assert res["final_iteration"] == ref["final_iteration"]
    for k, v in res["losses"].items():
        np.testing.assert_allclose(v, ref["losses"][k], rtol=0, atol=0,
                                   err_msg=f"step {k}")
    assert res["params_sha"] == ref["params_sha"]


@pytest.mark.slow
def test_fleet_elastic_shrink_2_to_1_dp(tmp_path):
    """2-process DP fleet SIGTERM'd mid-step resumes on ONE survivor:
    the lost host is permanent, the world shrinks, and the survivor's
    continuation is byte-identical to a fresh 1-process run restored
    from the same checkpoint (the ROADMAP item 4 remainder)."""
    _fleet_elastic_resume(tmp_path, "dp", 2, 1)


@pytest.mark.slow
def test_fleet_elastic_shrink_2_to_1_pipeline(tmp_path):
    """2-process PIPELINE fleet (2 stages across the process boundary)
    resumes on ONE survivor as a plain 1-way trainer: the pipe-layout
    optimizer state unstacks byte-preserving into the survivor's
    per-layer layout, and the continuation matches the machinery-free
    1-process restore exactly."""
    _fleet_elastic_resume(tmp_path, "pipe", 2, 1)


@pytest.mark.slow
def test_fleet_elastic_grow_1_to_2_dp(tmp_path):
    """The mirror image: a 1-process run's checkpoint resumes on a
    GROWN 2-process fleet (repaired hosts rejoining), byte-identical
    to the plain 2-process restore of the same checkpoint."""
    _fleet_elastic_resume(tmp_path, "dp", 1, 2)


@pytest.mark.slow
def test_fleet_coordinated_preempt_and_resume_dp(tmp_path):
    """2-process DP fleet: kill one worker mid-step (real SIGTERM),
    coordinated checkpoint at one step, bit-identical fleet resume."""
    _fleet_kill_mid_step(tmp_path, "dp")


@pytest.mark.slow
def test_fleet_coordinated_preempt_and_resume_pipeline(tmp_path):
    """2-process PIPELINE fleet (stages span the process boundary):
    the same kill-mid-step chaos, with the resume restacking the
    restored tree into the pipe-sharded params."""
    _fleet_kill_mid_step(tmp_path, "pipe")


@pytest.mark.slow
def test_eight_process_dp_tp_pp(tmp_path):
    """8 OS processes, 2x2x2 (data x model x pipeline) global mesh on
    a config-built zoo.Gpt: all THREE parallelism axes cross the
    process boundary (asserted from the stacked block kernel's
    sharding), every rank reports the identical loss sequence, and the
    sequence matches the same mesh semantics single-process (which
    the dryrun separately proves equals the UNSHARDED model)."""
    port = _free_port()
    out = tmp_path / "axis3"
    out.mkdir()
    procs = [subprocess.Popen(
        [sys.executable, os.path.join(WORKERS, "dist_3axis_worker.py"),
         str(rank), "8", str(port), str(out), "3"],
        env=_env(), stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        for rank in range(8)]
    outs = [p.communicate(timeout=600)[0].decode() for p in procs]
    for rank, (p, o) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {rank}:\n{o[-3000:]}"
        assert "AXIS3_WORKER_OK" in o
    ranks = [json.load(open(out / f"rank{r}.json")) for r in range(8)]
    for r in ranks:
        assert r["w_procs"] == list(range(8))
    for r in ranks[1:]:
        for k in ranks[0]["losses"]:
            np.testing.assert_allclose(r["losses"][k],
                                       ranks[0]["losses"][k], rtol=1e-6)

    # single-process reference: same mesh shape on 8 virtual devices
    from deeplearning4j_tpu.parallel.mesh import MeshConfig
    from deeplearning4j_tpu.parallel.trainer import ShardedTrainer
    from deeplearning4j_tpu.zoo.gpt import Gpt
    model = Gpt(vocab_size=64, max_len=16, d_model=32, n_layers=4,
                n_heads=4, d_ff=64, seq_len=16, compute_dtype=None,
                use_flash=False, seed=17).init_graph()
    tr = ShardedTrainer(model, MeshConfig(data=2, model=2, pipeline=2),
                        n_micro=2)
    rng = np.random.default_rng(7)
    for step in range(3):
        x = rng.integers(0, 64, (16, 16)).astype(np.int32)
        y = np.roll(x, -1, axis=1)
        ref = float(tr.fit_batch(x, y))
        np.testing.assert_allclose(ranks[0]["losses"][str(step)], ref,
                                   rtol=1e-5, err_msg=f"step {step}")
