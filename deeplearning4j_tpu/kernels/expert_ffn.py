"""The feed-forward of the experts ONE chip holds, for the rows a router
sent to them.

A routed layer picks ``top_k`` of its experts for every row; under
expert parallelism a chip holds a few of them (``held``) and computes
its own part of the sum,

    out[t] = sum over the picks (t, e) with e held here of
             weight[t, e] * W_down[e] (silu(W_gate[e] x[t]) * (W_up[e] x[t]))

At a decode tick the rows are few (``slots * top_k * held / experts``
pairs: 8 rows an expert at 256 slots, 8 of 256 experts a row, 16 held)
and the weights are not (three ``d x ff`` matrices an expert, 805 MB a
layer at 16 x 4096 x 2048 in bfloat16): the layer is a WEIGHT-STREAMING
kernel, and its time is the time to read every expert that has a row,
once.

``_expert_ffn_pallas`` (``%expert_ffn`` in a profile, one event a
layer a call): XLA sorts the pairs by held expert into row tiles of
``_ROW_TILE`` (a group starts on a tile; the plan is a one-hot cumsum,
no sort) and gathers their rows; the kernel's grid is the held experts
THAT HAVE A ROW, in order, an expert a step.  The weights stay in HBM
and the kernel fetches them ``_FF_TILE`` columns of ``ff`` at a time
into two buffers -- the next tile (this expert's, or the next live
expert's first) is on its way while one is multiplied -- so an expert
with no row costs no copy and the DMA queue never drains between
experts.  An expert with more rows than a tile (never, at a decode
tick's shape) streams its weights once a tile.  Rows no pick landed on
are never read back.

``expert_ffn_reference`` is the same sum as a masked einsum over every
held expert in ``jax.numpy``: the CPU's route and the kernel's
reference in tests.  ``DL4J_TPU_EXPERT_KERNEL=reference|pallas``
overrides the choice (pallas off-TPU runs in interpret mode).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.kernels.flash_attention import _interpret

_ROUTE_TOTAL = telemetry.counter(
    "expert_route_total",
    "expert_ffn route decisions at trace time, by path",
    labelnames=("path",))

#: rows of one tile of the sorted pairs (a group starts on a tile), the
#: columns of ``ff`` one weight fetch holds, and the VMEM the two weight
#: buffers, a row tile and its accumulator may take
_ROW_TILE = 64
_FF_TILE = 512
_VMEM_BYTES = 48 << 20


def expert_route() -> str:
    """'pallas' | 'reference' -- what an ``expert_ffn`` traced now takes."""
    forced = os.environ.get("DL4J_TPU_EXPERT_KERNEL", "")
    if forced in ("reference", "pallas"):
        return forced
    return "pallas" if jax.default_backend() == "tpu" else "reference"


def _silu(x):
    return x * jax.nn.sigmoid(x)


def expert_ffn_reference(x, expert, weight, w_gate, w_up, w_down,
                         layer=None):
    """Every held expert over every row, masked: operands in x's dtype,
    products accumulated in float32."""
    if layer is not None:
        w_gate, w_up, w_down = (
            lax.dynamic_index_in_dim(m, layer, 0, keepdims=False)
            for m in (w_gate, w_up, w_down))
    held, f32 = w_gate.shape[0], jnp.float32
    share = jnp.sum((expert[..., None] == jnp.arange(held))
                    * weight[..., None].astype(f32), axis=1)   # [T, held]
    # operands rounded to x's dtype, then multiplied as float32: what a
    # product of two bfloat16 accumulated in float32 gives, on a backend
    # whose batched dot has no such mode
    w = lambda m: m.astype(x.dtype).astype(f32)
    xf = x.astype(f32)
    g = jnp.einsum("td,edf->tef", xf, w(w_gate))
    u = jnp.einsum("td,edf->tef", xf, w(w_up))
    out = jnp.einsum("tef,efd->ted", w(_silu(g) * u), w(w_down))
    return jnp.einsum("ted,te->td", out, share).astype(x.dtype)


def expert_row_plan(expert, held: int, tile: int):
    """Where each pick's row goes once the picks are grouped by held
    expert, a group starting on a row tile.  ``expert`` [T, k] int32,
    ``held`` for a pick of an expert held elsewhere.  Returns (``dest``
    [T, k]: the pick's row, M for none; ``row_tok`` [M]: the token each
    row is of; ``first_tile`` / ``tiles`` [held]: a group's tiles;
    ``live`` [held]: the experts that have a row, in order, then the
    rest; their count [1]) with M = ``expert_rows(T, k, held, tile)``."""
    T, k = expert.shape
    M = expert_rows(T, k, held, tile)
    flat = expert.reshape(-1)
    mine = flat[:, None] == jnp.arange(held)                   # [P, held]
    upto = jnp.cumsum(mine.astype(jnp.int32), axis=0)
    counts = upto[-1]
    rank = jnp.sum(jnp.where(mine, upto - 1, 0), axis=1)       # in its group
    tiles = (counts + tile - 1) // tile
    first_tile = jnp.cumsum(tiles) - tiles
    start = jnp.concatenate([first_tile * tile, jnp.full((1,), M)])
    dest = jnp.where(flat < held, start[jnp.minimum(flat, held)] + rank, M)
    row_tok = jnp.zeros((M,), jnp.int32).at[dest].set(
        jnp.arange(T * k, dtype=jnp.int32) // k, mode="drop")
    has = counts > 0
    live = jnp.argsort(~has, stable=True).astype(jnp.int32)
    return (dest.reshape(T, k), row_tok, first_tile.astype(jnp.int32),
            tiles.astype(jnp.int32), live,
            jnp.sum(has).astype(jnp.int32)[None])


def expert_rows(T: int, k: int, held: int, tile: int) -> int:
    """Rows of the sorted pairs at most: every pick a row, and under a
    tile of padding a group."""
    return T * min(k, held) + held * tile


def _kernel(live_ref, n_live_ref, first_ref, tiles_ref, lay_ref,
            xs_hbm, wg_hbm, wu_hbm, wd_hbm, out_hbm,
            wg_buf, wu_buf, wd_buf, wsem, x_buf, o_buf, acc, rsem, cnt,
            *, tile: int, ff_tile: int, n_ff: int):
    """Grid (held,), "arbitrary": step i is live expert ``live[i]``
    (nothing past the ``n_live``-th).  ``cnt`` counts the weight tiles
    read so far: tile n sits in buffer n % 2, and tile n + 1 is fetched
    before n is waited for."""
    i, held = pl.program_id(0), pl.num_programs(0)
    n_live = n_live_ref[0]
    e = live_ref[i]
    lay = lay_ref[0]

    def weights(ex, j, slot):
        cols = pl.ds(pl.multiple_of(j * ff_tile, ff_tile), ff_tile)
        return (pltpu.make_async_copy(wg_hbm.at[lay, ex, :, cols],
                                      wg_buf.at[slot], wsem.at[0, slot]),
                pltpu.make_async_copy(wu_hbm.at[lay, ex, :, cols],
                                      wu_buf.at[slot], wsem.at[1, slot]),
                pltpu.make_async_copy(wd_hbm.at[lay, ex, cols, :],
                                      wd_buf.at[slot], wsem.at[2, slot]))

    def fetch(ex, j, slot):
        for copy in weights(ex, j, slot):
            copy.start()

    @pl.when(i < n_live)
    def _expert():
        @pl.when(i == 0)
        def _first():
            cnt[0] = 0
            fetch(e, 0, 0)

        n_rows = tiles_ref[e]
        nxt = live_ref[jnp.minimum(i + 1, held - 1)]

        def row_tile(r, carry):
            rows = pl.ds(pl.multiple_of((first_ref[e] + r) * tile, tile),
                         tile)
            load = pltpu.make_async_copy(xs_hbm.at[rows], x_buf, rsem.at[0])
            load.start()
            acc[...] = jnp.zeros_like(acc)
            load.wait()
            x = x_buf[...]
            for j in range(n_ff):
                slot = cnt[0] % 2
                if j + 1 < n_ff:
                    fetch(e, j + 1, 1 - slot)
                else:
                    again = r + 1 < n_rows

                    @pl.when(again)
                    def _same():
                        fetch(e, 0, 1 - slot)

                    @pl.when(jnp.logical_not(again) & (i + 1 < n_live))
                    def _next():
                        fetch(nxt, 0, 1 - slot)

                for copy in weights(e, j, slot):
                    copy.wait()
                g = jnp.dot(x, wg_buf[slot],
                            preferred_element_type=jnp.float32)
                u = jnp.dot(x, wu_buf[slot],
                            preferred_element_type=jnp.float32)
                acc[...] += jnp.dot((_silu(g) * u).astype(x.dtype),
                                    wd_buf[slot],
                                    preferred_element_type=jnp.float32)
                cnt[0] = cnt[0] + 1
            o_buf[...] = acc[...].astype(o_buf.dtype)
            store = pltpu.make_async_copy(o_buf, out_hbm.at[rows], rsem.at[1])
            store.start()
            store.wait()
            return carry

        lax.fori_loop(0, n_rows, row_tile, 0)


def _ff_tile(d: int, ff: int, itemsize: int) -> int:
    """Columns of ``ff`` a weight fetch holds: ``_FF_TILE`` where it
    divides ``ff`` and two buffers of three ``d x tile`` matrices fit,
    else all of ``ff`` (a test's widths)."""
    if ff % _FF_TILE == 0 and 6 * d * _FF_TILE * itemsize <= _VMEM_BYTES // 2:
        return _FF_TILE
    return ff


# one trace of the kernel for every layer and program that calls it at
# one shape (the decode scans of 8, 4, 2 and 1 ticks; every bucket's
# prefill has its own)
@functools.partial(jax.jit, static_argnames=("interpret",), inline=True)
def _expert_ffn_call(x, expert, weight, w_gate, w_up, w_down, layer, *,
                     interpret: bool):
    T, d = x.shape
    _, held, _, ff = w_gate.shape
    k = expert.shape[1]
    tile = _ROW_TILE
    ff_tile = _ff_tile(d, ff, jnp.dtype(w_gate.dtype).itemsize)
    dest, row_tok, first_tile, tiles, live, n_live = expert_row_plan(
        expert, held, tile)
    M = row_tok.shape[0]
    xs = jnp.take(x, row_tok, axis=0).astype(w_gate.dtype)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    wdt = w_gate.dtype
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(held,),
        in_specs=[hbm, hbm, hbm, hbm],
        out_specs=hbm,
        scratch_shapes=[
            pltpu.VMEM((2, d, ff_tile), wdt),         # W_gate tiles
            pltpu.VMEM((2, d, ff_tile), wdt),         # W_up tiles
            pltpu.VMEM((2, ff_tile, d), wdt),         # W_down tiles
            pltpu.SemaphoreType.DMA((3, 2)),          # (matrix, buffer)
            pltpu.VMEM((tile, d), wdt),               # a row tile
            pltpu.VMEM((tile, d), x.dtype),           # its result
            pltpu.VMEM((tile, d), jnp.float32),       # accumulator
            pltpu.SemaphoreType.DMA((2,)),            # rows in, out
            pltpu.SMEM((1,), jnp.int32),              # weight tiles read
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, tile=tile, ff_tile=ff_tile,
                          n_ff=ff // ff_tile),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
        name="expert_ffn",
    )(live, n_live, first_tile, tiles,
      jnp.asarray(layer, jnp.int32).reshape(1), xs, w_gate, w_up, w_down)
    # a pick's row, weighted; rows of tiles no pick landed on were never
    # written, so a pick held elsewhere selects 0 and multiplies nothing
    picked = jnp.take(out, jnp.minimum(dest, M - 1), axis=0)   # [T, k, d]
    return jnp.sum(jnp.where((dest < M)[..., None],
                             weight[..., None] * picked.astype(jnp.float32),
                             0.0), axis=1).astype(x.dtype)


def expert_ffn(x, expert, weight, w_gate, w_up, w_down, layer=None):
    """This chip's part of a routed feed-forward.

    ``x`` [T, d] rows; ``expert`` [T, k] int32, a pick's expert counted
    from the first one held, ``held`` for an expert held elsewhere or a
    row that takes none; ``weight`` [T, k] float32; ``w_gate`` / ``w_up``
    [held, d, ff], ``w_down`` [held, ff, d] -- or, with ``layer`` (an
    int32 index, traced), a whole RUN's stacked [layers, held, ., .]:
    the kernel then reads that layer's experts where they lie, and no
    program slices 800 MB out of a run to hand it over.  Returns [T, d]
    in x's dtype."""
    route = expert_route()
    _ROUTE_TOTAL.labels(path=route).inc()
    if route != "pallas":
        return expert_ffn_reference(x, expert, weight, w_gate, w_up, w_down,
                                    layer)
    if layer is None:
        layer, (w_gate, w_up, w_down) = 0, (
            m[None] for m in (w_gate, w_up, w_down))
    with jax.named_scope("expert_ffn"):
        return _expert_ffn_call(x, expert, weight, w_gate, w_up, w_down,
                                layer, interpret=_interpret())
