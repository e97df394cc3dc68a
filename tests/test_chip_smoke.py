"""CPU rehearsal of ``chip_smoke.py``: the same phases at the ``TINY``
preset, so a wrong path, argument or piece of control flow costs no
chip time.  The phases return what they observed; the platform and
kernel-route assertions live in ``chip_smoke.main()`` alone, so here
the CPU's own routes (``xla`` attention, ``reference`` paged reads) are
what is expected, without any switch in the program.

Also pins what lets one process own the chip: the script refuses to run
without a TPU before it builds anything, and importing the package
initialises no JAX backend (spawned decode workers import it while the
parent holds the chip).
"""
import os
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

TINY = chip_smoke.TINY


@pytest.fixture(scope="module")
def net():
    return chip_smoke.build_net(TINY)


def test_train_phase_rehearsal(net):
    obs = chip_smoke.train_phase(net, TINY)
    chip_smoke.check_train(obs)
    assert len(obs["losses"]) == TINY["steps"]
    assert obs["routes"] == [("xla", TINY["seq"], 8)]   # t < 512
    assert obs["first_step_s"] >= obs["step_s"] > 0


def test_serve_phase_rehearsal(net):
    obs = chip_smoke.serve_phase(net, TINY)
    chip_smoke.check_serve(obs)
    assert obs["token_gaps"] == []          # byte-equal on the CPU
    assert obs["prefix_hits"] == 1          # the second-wave request
    # a full-depth self-draft is accepted whole on the parity path
    assert obs["spec"]["accepted"] == obs["spec"]["proposed"] > 0
    for part in (obs, obs["spec"]):
        c = part["counters"]
        assert c['paged_route_total{path="reference"}'] > 0
        assert c['paged_route_total{path="pallas"}'] == 0


def test_mesh_phase_rehearsal():
    """Four of the suite's eight virtual devices stand in for the
    four-chip host."""
    obs = chip_smoke.mesh_phase(TINY, jax.devices()[:4])
    chip_smoke.check_mesh(obs)
    assert obs["token_gaps"] == []
    assert obs["counters"]['paged_route_total{path="reference_tp"}'] > 0
    assert sorted(set(obs["placed_on"])) == [0, 1]


def test_token_gaps_flag_a_wrong_token(net):
    """The stated-tolerance comparison: equal tokens report nothing, a
    wrong token is far from the float32 reference's own choice."""
    shapes = [(9, 4), (20, 6)]
    prompts = chip_smoke.prompts_for(TINY, shapes, seed=5)
    want = chip_smoke.offline_tokens(net, prompts, shapes)
    assert chip_smoke.token_gaps(net, TINY, want, want) == []
    got = [w.copy() for w in want]
    got[1][22] = (got[1][22] + 7) % TINY["gpt"]["vocab_size"]
    (gap,) = chip_smoke.token_gaps(net, TINY, got, want)
    assert gap["request"] == 1 and gap["at"] == 22
    obs = {"token_gaps": [gap],
           "token_gap_tol": chip_smoke.TOKEN_GAP_TOL}
    with pytest.raises(chip_smoke.SmokeFailure, match="leaves the"):
        chip_smoke.check_tokens(obs)


def test_a_failing_decode_kernel_fails_the_phase(net, monkeypatch):
    """No hidden fallback: a decode read that raises (a kernel the
    chip's compiler refuses) is a counted tick failure — the server
    salvages and retries for ever — and the phase fails at the first
    count, not with an answer from some other path."""
    from deeplearning4j_tpu import kernels

    def boom(*a, **kw):
        raise RuntimeError("decode kernel killed by the test")

    monkeypatch.setattr(kernels, "paged_decode_attention", boom)
    with pytest.raises(chip_smoke.SmokeFailure, match="failed a dispatch"):
        chip_smoke.serve_phase(net, TINY)


def _run(code_or_path, *, script: bool):
    cmd = [sys.executable] + ([code_or_path] if script
                              else ["-c", code_or_path])
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=300)


def test_refuses_to_run_without_a_tpu():
    """Non-zero exit, nothing on stdout: no phase line, no result —
    the refusal comes before a model is built."""
    r = _run(os.path.join(ROOT, "chip_smoke.py"), script=True)
    assert r.returncode != 0
    assert r.stdout == ""
    assert "needs a TPU" in r.stderr


def test_importing_the_package_initialises_no_backend():
    code = (
        "import importlib, pkgutil\n"
        "import deeplearning4j_tpu as pkg\n"
        "from jax._src import xla_bridge\n"
        "for m in pkgutil.walk_packages(pkg.__path__, pkg.__name__ + '.'):\n"
        "    if not m.name.endswith('__main__'):\n"
        "        importlib.import_module(m.name)\n"
        "        assert not xla_bridge.backends_are_initialized(), m.name\n"
        "print('IMPORTED_WITHOUT_BACKEND')\n")
    r = _run(code, script=False)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "IMPORTED_WITHOUT_BACKEND" in r.stdout


@pytest.mark.parametrize("placed", ["from_outside", "default"])
def test_compile_cache_is_placed_from_outside(placed, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the program sets no cache
    path in code (JAX reads the variable itself); without it the path
    is the fixed <checkout>/.jax_cache — never a tempfile, pid or time.
    In a child: the helper changes process-wide JAX config."""
    code = (
        "import jax\n"
        "from deeplearning4j_tpu.runtime.backend import (\n"
        "    enable_compile_cache)\n"
        "calls = []\n"
        "update = jax.config.update\n"
        "jax.config.update = lambda k, v: (calls.append(k), update(k, v))\n"
        "print(enable_compile_cache())\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        "print('jax_compilation_cache_dir' in calls)\n")
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = os.path.join(ROOT, ".jax_cache")
    if placed == "from_outside":
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cc")
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.split() == [want, want,
                                str(placed == "default")]
