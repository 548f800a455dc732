"""Grouped SwiGLU over the experts held on this chip — Pallas TPU kernel.

Rows arrive sorted by expert, each expert's group padded to a multiple of
the row tile ``tm`` (``models.moe`` builds this layout), so every tile
belongs to one expert. Per tile: y = (silu(x W_gate[l, e]) * (x W_up[l, e]))
W_down[l, e], each expert's three matrices whole in VMEM. The weights come
stacked over layers and the kernel reads layer ``l``'s from them, so a
layer scan copies none of them out. Consecutive tiles of one expert keep
its weights resident, so each held expert that has rows is read once per
call whatever the routing. Tiles past the last group do no
work: their inputs map to the last valid tile and their output to a spare
tile at the end, so they fetch and store nothing new.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Two copies of one expert's three (2048, 1408) bf16 matrices are 35 MB;
# the default scoped VMEM limit is 16 MiB.
VMEM_LIMIT = 100 << 20


def _kernel(te_ref, nv_ref, layer_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref):
    @pl.when(pl.program_id(0) < nv_ref[0])
    def _tile():
        x = x_ref[...]
        g = jnp.dot(x, wg_ref[0, 0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[0, 0], preferred_element_type=jnp.float32)
        h = (jax.nn.silu(g) * u).astype(x.dtype)
        o_ref[...] = jnp.dot(h, wd_ref[0, 0],
                             preferred_element_type=jnp.float32
                             ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def moe_gmm(x, w_gate, w_up, w_down, tile_expert, n_valid, layer, *,
            tm: int, interpret: bool = False):
    """x: (M, D) rows in tile layout (M a multiple of tm); w_gate/w_up:
    (L, E, D, F), w_down: (L, E, F, D); tile_expert: (M // tm,) int32, the
    expert of each tile (for tiles past the last group, that of the last
    valid tile); n_valid: (1,) int32, the tiles that hold rows; layer:
    (1,) int32, the layer whose experts run. Returns
    (M + tm, D): the rows of the first ``n_valid`` tiles, then rows that
    are unspecified (the tiles past them and the spare tile)."""
    M, D = x.shape
    F = w_gate.shape[-1]
    nt = M // tm

    def rows(i, te, nv, lay):
        return (jnp.minimum(i, jnp.maximum(nv[0] - 1, 0)), 0)

    def weight(i, te, nv, lay):
        return (lay[0], te[i], 0, 0)

    def out(i, te, nv, lay):
        return (jnp.where(i < nv[0], i, nt), 0)

    return pl.pallas_call(
        _kernel,
        name="moe_gmm",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(nt,),
            in_specs=[
                pl.BlockSpec((tm, D), rows),
                pl.BlockSpec((1, 1, D, F), weight),
                pl.BlockSpec((1, 1, D, F), weight),
                pl.BlockSpec((1, 1, F, D), weight),
            ],
            out_specs=pl.BlockSpec((tm, D), out)),
        out_shape=jax.ShapeDtypeStruct((M + tm, D), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(tile_expert, n_valid, layer, x, w_gate, w_up, w_down)
