"""One decode tick of a selective state-space (Mamba-1) layer, for
every slot at once.

A recurrent layer keeps, per slot, a state ``h`` [d_state, d_inner] in
float32.  A tick reads and writes ALL of it whatever the context
length -- at 256 slots of 16 x 5120 that is 84 MB in and 84 MB out per
layer, the layer's largest traffic after its weights -- so the update
is one kernel over the WHOLE stacked state [layers, slots, d_state,
d_inner], aliased through the call (the paged pool's discipline,
``paged_attention._paged_decode_write_pallas``): XLA never slices a
layer's state out of the decode scan's carry or writes it back.

    delta = softplus(dt + dt_bias)            (0 for an inactive slot)
    h     = exp(delta (x) A) * h + (delta * u) (x) B
    out   = (h . C + D * u) * silu(z)

``d_inner`` rides the lanes: a minor dimension of ``d_state`` = 16
would pad to 128 lanes and move 8x the bytes.  An inactive slot's
``delta`` is 0, so its ``h`` is multiplied by exp(0) = 1 and gains 0:
left bit for bit as it was, whatever its inputs hold.

Two routes behind one entry, chosen at trace time as the paged
kernel's is: ``_ssm_step_pallas`` on TPU (``%ssm_step`` in a profile),
``ssm_step_reference`` -- the same arithmetic in ``jax.numpy`` --
elsewhere.  ``DL4J_TPU_SSM_KERNEL=reference|pallas`` overrides (pallas
off-TPU runs in interpret mode).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu import telemetry
from deeplearning4j_tpu.kernels.flash_attention import (_LANES, _dimsem,
                                                        _interpret)

_ROUTE_TOTAL = telemetry.counter(
    "ssm_route_total",
    "ssm_step route decisions at trace time, by path",
    labelnames=("path",))

#: slots and lanes of one grid step's state block [slots, d_state, lanes]
_SLOT_TILE, _LANE_TILE = 8, 1280


def ssm_route() -> str:
    """'pallas' | 'reference' -- what an ``ssm_step`` traced now takes."""
    forced = os.environ.get("DL4J_TPU_SSM_KERNEL", "")
    if forced in ("reference", "pallas"):
        return forced
    return "pallas" if jax.default_backend() == "tpu" else "reference"


def _update(h, delta, u, b, c, a):
    """The recurrence for a [..., n, d] state: (h', y)."""
    h = jnp.exp(delta[..., None, :] * a) * h \
        + (delta * u)[..., None, :] * b[..., :, None]
    return h, jnp.sum(h * c[..., :, None], axis=-2)


def ssm_step_reference(h, layer, dt, u, b, c, z, a, d, dt_bias, active):
    """The ``jax.numpy`` route; see :func:`ssm_step`."""
    f32 = jnp.float32
    uf, zf = u.astype(f32), z.astype(f32)
    delta = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
    delta = jnp.where(active[:, None], delta, 0.0)
    hl = jax.lax.dynamic_index_in_dim(h, layer, 0, keepdims=False)
    hl, y = _update(hl.astype(f32), delta, uf, b.astype(f32),
                    c.astype(f32), a.astype(f32))
    out = (y + d.astype(f32) * uf) * zf * jax.nn.sigmoid(zf)
    return out.astype(u.dtype), jax.lax.dynamic_update_index_in_dim(
        h, hl.astype(h.dtype), layer, 0)


def _kernel(lay_ref, h_ref, dt_ref, u_ref, z_ref, b_ref, c_ref, a_ref,
            d_ref, bias_ref, act_ref, o_ref, ho_ref, y_ref):
    """One block of slots x one stripe of lanes.  The slots of a block
    are walked one by one: a slot's state is a [d_state, lanes] tile,
    its ``delta`` row broadcasts over the sublanes and its B and C
    columns over the lanes -- every operation is two-dimensional."""
    del lay_ref                      # steers the index maps only
    f32 = jnp.float32
    n = h_ref.shape[1]
    u = u_ref[...].astype(f32)                              # (bt, dk)
    delta = jax.nn.softplus(dt_ref[...].astype(f32) + bias_ref[...]) \
        * act_ref[...]
    du, a = delta * u, a_ref[...]                           # (n, dk)
    for s in range(h_ref.shape[0]):
        row = lambda x: jnp.broadcast_to(x[s:s + 1, :], (n, x.shape[1]))
        h = jnp.exp(row(delta) * a) * h_ref[s] + row(du) * b_ref[s]
        ho_ref[s] = h
        y_ref[s:s + 1, :] = jnp.sum(h * c_ref[s], axis=0, keepdims=True)
    zf = z_ref[...].astype(f32)
    o_ref[...] = ((y_ref[...] + d_ref[...] * u) * zf
                  * jax.nn.sigmoid(zf)).astype(o_ref.dtype)


def _tile(size: int, want: int, unit: int) -> int:
    """The largest divisor of ``size`` that is at most ``want`` and a
    multiple of ``unit``; the whole axis where there is none."""
    t = (min(want, size) // unit) * unit
    while t >= unit and size % t:
        t -= unit
    return t if t >= unit else size


# the outer scope keeps the kernel's ``name=`` whole under any
# transformation (``%ssm_step.N`` in a profile): see the note at
# flash_attention._flash_fwd
@jax.named_scope("ssm_update")
def _ssm_step_pallas(h, layer, dt, u, b, c, z, a, d, dt_bias, active):
    L, B, n, di = h.shape
    f32 = jnp.float32
    # sub-32-bit rows pack 16 to a tile
    bt = _tile(B, 2 * _SLOT_TILE if u.dtype.itemsize < 4 else _SLOT_TILE,
               16 if u.dtype.itemsize < 4 else 8)
    dk = _tile(di, _LANE_TILE, _LANES)
    nbt = B // bt
    rows = pl.BlockSpec((bt, dk), lambda i, j, lay: (i, j))
    cols = pl.BlockSpec((bt, n, 1), lambda i, j, lay: (i, 0, 0))
    lane = pl.BlockSpec((1, dk), lambda i, j, lay: (0, j))
    # the state as [L * B, n, di] (a bitcast), in units of bt slots
    state = pl.BlockSpec((bt, n, dk),
                         lambda i, j, lay: (lay[0] * nbt + i, 0, j))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nbt, di // dk),
        in_specs=[state, rows, rows, rows, cols, cols,
                  pl.BlockSpec((n, dk), lambda i, j, lay: (0, j)),
                  lane, lane,
                  pl.BlockSpec((bt, 1), lambda i, j, lay: (i, 0))],
        out_specs=[rows, state],
        scratch_shapes=[pltpu.VMEM((bt, dk), f32)],
    )
    flat = jax.ShapeDtypeStruct((L * B, n, di), h.dtype)
    out, h_flat = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, di), u.dtype), flat],
        input_output_aliases={1: 1},     # counts the prefetch operand
        compiler_params=_dimsem("parallel", "parallel"),
        interpret=_interpret(),
        name="ssm_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), h.reshape(flat.shape),
      dt, u, z, b.astype(f32)[:, :, None], c.astype(f32)[:, :, None],
      a.astype(f32), d.astype(f32)[None], dt_bias.astype(f32)[None],
      active.astype(f32)[:, None])
    return out, h_flat.reshape(h.shape)


def ssm_step(h, layer, dt, u, b, c, z, a, d, dt_bias, active):
    """One tick of recurrent layer ``layer`` for every slot.

    ``h`` [layers, B, d_state, d_inner] float32, the WHOLE stacked
    state; ``layer`` an int32 index into it (traced); ``dt`` [B,
    d_inner] the step size before its bias and softplus; ``u`` [B,
    d_inner] the convolved input; ``b`` / ``c`` [B, d_state]; ``z``
    [B, d_inner] the gate; ``a`` [d_state, d_inner] = -exp(A_log);
    ``d`` / ``dt_bias`` [d_inner]; ``active`` [B] bool.  Returns
    (gated output [B, d_inner] in ``u``'s dtype, ``h`` with that
    layer's rows of the active slots advanced)."""
    route = ssm_route()
    _ROUTE_TOTAL.labels(path=route).inc()
    fn = _ssm_step_pallas if route == "pallas" else ssm_step_reference
    return fn(h, layer, dt, u, b, c, z, a, d, dt_bias, active)
