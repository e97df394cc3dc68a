#!/usr/bin/env python
"""Flash-vs-XLA crossover sweep: measure fwd+bwd attention time over
d in {64,128}, t in {256,512,1024,2048}, with and without bias /
causal, on the real chip — plus a block-size sweep at the causal
flagship shape.  Writes chiprun_out/FLASH_SWEEP.json.

Protocol: DIFFERENTIAL TWO-SCAN-LENGTH timing.  Each config runs the
kernel inside a single jitted ``lax.scan`` over rotating buffers at
two scan lengths (8 and 72 iterations; configs measuring under 1.5 ms
re-measure at 8 and 200 so the signal dominates call-to-call jitter,
and a non-positive differential is an error, not a number) with a
seed-perturbed input and a scalar readback.  Per-iteration time =
(T_long - T_short) / (n_long - n_short), which cancels every fixed
per-call cost (dispatch, readback, first-call effects).
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np

_SEED = [0]


def _wall(run, args, repeats=3):
    import jax.numpy as jnp
    best = 1e9
    for _ in range(repeats):
        _SEED[0] += 1
        t0 = time.perf_counter()
        _ = float(run(*args, 1e-6 * _SEED[0]))   # readback flushes
        best = min(best, time.perf_counter() - t0)
    return best


def measure(step_fn, bufs, n1=8, n2=72):
    """step_fn(q, k, v) -> scalar; bufs = (qs, ks, vs) each [4, ...].
    Returns ms/iteration via the differential protocol."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def make_run(n_iter):
        @jax.jit
        def run(qs, ks, vs, seed):
            qs = qs + seed
            def body(c, i):
                return c + step_fn(qs[i % 4], ks[i % 4], vs[i % 4]), None
            c, _ = lax.scan(body, 0.0, jnp.arange(n_iter))
            return c
        return run

    r1, r2 = make_run(n1), make_run(n2)
    _SEED[0] += 1
    _ = float(r1(*bufs, 1e-6 * _SEED[0]))        # compile
    _SEED[0] += 1
    _ = float(r2(*bufs, 1e-6 * _SEED[0]))
    ms = (_wall(r2, bufs) - _wall(r1, bufs)) / (n2 - n1) * 1e3
    if ms < 1.5 and n2 <= 72:
        # sub-1.5 ms/iter: the 64-iteration difference (~100 ms) is the
        # same order as the call-to-call jitter — stretch to a
        # 192-iteration difference so the signal dominates
        return measure(step_fn, bufs, n1=8, n2=200)
    if ms <= 0:
        # a negative differential is a failed measurement, never a
        # time — refuse to record it (r4's harness silently accepted
        # these and they ended up in the routing artifact)
        raise RuntimeError(
            f"non-positive differential ({ms:.3f} ms) at n2={n2}; "
            "call-to-call jitter swamped the signal")
    return ms


def main():
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.runtime.backend import enable_compile_cache
    enable_compile_cache()
    import deeplearning4j_tpu.kernels  # noqa: F401  (registers module)
    fa = sys.modules["deeplearning4j_tpu.kernels.flash_attention"]

    assert jax.default_backend() == "tpu", "sweep needs the real chip"
    rng = np.random.default_rng(0)
    rows = []
    BATCH_FOR_T = {256: 64, 512: 32, 1024: 16, 2048: 8}

    def grad_of(f):
        # all three cotangents: flash's custom_vjp always computes
        # dq/dk/dv, so differentiating only argnums=0 would let XLA
        # DCE its dK/dV matmuls and skew the comparison against flash
        def step(q, k, v):
            dq, dk, dv = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
            return (jnp.sum(dq.astype(jnp.float32))
                    + jnp.sum(dk.astype(jnp.float32))
                    + jnp.sum(dv.astype(jnp.float32)))
        return step

    for d in (64, 128):
        h = 12 if d == 64 else 6
        for t in (256, 512, 1024, 2048):
            b = BATCH_FOR_T[t]
            mk = lambda: jnp.asarray(
                rng.normal(size=(4, b, h, t, d)), jnp.bfloat16)
            bufs = (mk(), mk(), mk())
            bias = jnp.zeros((b, 1, 1, t), jnp.float32)
            for causal in (False, True):
                for use_bias in (False, True):
                    bi = bias if use_bias else None
                    blocks = fa._auto_blocks(t, causal=causal)

                    def fl(q, k, v, _bl=blocks, _bi=bi, _c=causal):
                        return jnp.sum(fa.flash_attention(
                            q, k, v, *_bl, bias=_bi,
                            causal=_c).astype(jnp.float32))

                    def xl(q, k, v, _bi=bi, _c=causal):
                        return jnp.sum(fa.xla_attention(
                            q, k, v, bias=_bi,
                            causal=_c).astype(jnp.float32))

                    try:
                        t_fl = measure(grad_of(fl), bufs)
                    except Exception:
                        t_fl = None
                    try:
                        t_xl = measure(grad_of(xl), bufs)
                    except Exception:
                        t_xl = None
                    ok = t_fl is not None and t_xl is not None
                    rows.append({
                        "d": d, "h": h, "t": t, "b": b,
                        "causal": causal, "bias": use_bias,
                        "blocks": list(blocks),
                        "flash_ms": (None if t_fl is None
                                     else round(t_fl, 3)),
                        "xla_ms": (None if t_xl is None
                                   else round(t_xl, 3)),
                        "flash_speedup": (round(t_xl / t_fl, 3)
                                          if ok else None)})
                    print(json.dumps(rows[-1]), flush=True)

    # block sweep at the causal flagship shape (t=2048, d=128)
    b, h, t, d = 8, 6, 2048, 128
    mk = lambda: jnp.asarray(rng.normal(size=(4, b, h, t, d)),
                             jnp.bfloat16)
    bufs = (mk(), mk(), mk())
    blocks = []
    for bq in (256, 512, 1024):
        for bk in (256, 512, 1024):
            if t % bq or t % bk:
                continue
            try:
                def f(q, k, v, _bq=bq, _bk=bk):
                    return jnp.sum(fa.flash_attention(
                        q, k, v, _bq, _bk,
                        causal=True).astype(jnp.float32))
                ms = measure(grad_of(f), bufs)
                blocks.append({"blk_q": bq, "blk_k": bk,
                               "ms": round(ms, 3)})
                print(json.dumps(blocks[-1]), flush=True)
            except Exception as e:
                blocks.append({"blk_q": bq, "blk_k": bk,
                               "error": str(e)[:120]})

    # bthd layout at the flagship shape: the kernels read [b, t, h, d]
    # in place (production path for d=128 models) — vs the transposed
    # bhtd call.  Same data as the block sweep, re-viewed (buffers are
    # [4, b, h, t, d]; one device-side transpose).
    bufs4 = tuple(x.swapaxes(2, 3) for x in bufs)
    blocks_flag = fa._auto_blocks(t, causal=True)
    bthd_rows = []
    for lay in ("bthd", "bhtd"):
        def f(q, k, v, _l=lay, _bl=blocks_flag):
            if _l == "bhtd":
                q, k, v = (x.swapaxes(1, 2) for x in (q, k, v))
            return jnp.sum(fa.flash_attention(
                q, k, v, *_bl, causal=True,
                layout=_l).astype(jnp.float32))
        try:
            ms = measure(grad_of(f), bufs4)
            bthd_rows.append({"layout": lay, "blocks": list(blocks_flag),
                              "note": ("in-place [b,t,h,d]" if
                                       lay == "bthd" else
                                       "transpose + flat kernel"),
                              "ms": round(ms, 3)})
            print(json.dumps(bthd_rows[-1]), flush=True)
        except Exception as e:
            bthd_rows.append({"layout": lay, "error": str(e)[:120]})

    out = {"rows": rows, "causal_t2048_block_sweep": blocks,
           "bthd_flagship_causal_fwd_bwd": bthd_rows,
           "protocol": "fwd+bwd sum(dq)+sum(dk)+sum(dv) grad-of-sum "
                       "(argnums 0,1,2 — symmetric work for flash's "
                       "custom_vjp vs XLA autodiff) inside one jitted "
                       "lax.scan over 4 rotating seed-perturbed "
                       "buffers; per-iter ms = (T(scan 72) - "
                       "T(scan 8)) / 64, re-measured at (200-8) when "
                       "under 1.5 ms, best of 3, scalar-readback "
                       "flush; non-positive differentials error out "
                       "rather than record — fixed per-call costs "
                       "(dispatch/readback) cancel in the "
                       "difference"}
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chiprun_out", "FLASH_SWEEP.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print("wrote", path)


if __name__ == "__main__":
    main()
