"""Pallas TPU Mamba selective scan.

Grid (B, n_channel_blocks, n_time_blocks): each program owns a (bd, N) state
slab in VMEM fp32, carried across the time blocks (the innermost,
sequential axis), and walks its tb positions with a fori loop:
    h <- exp(dt_t·A)⊙h + (dt_t·x_t)·B_t ;  y_t = h·C_t + D⊙x_t
Per-step work is elementwise over (bd, N) plus an N-reduction — VPU-shaped,
channel-parallel across the grid (d_inner is large: 16K for Jamba, so the
grid supplies ample parallelism).  x/dt are streamed per channel block;
B_t/C_t are shared across channel blocks (re-read per program — the
recorded trade-off vs. broadcasting through VMEM).  Blocking time keeps the
VMEM working set at (tb, bd) whatever the length: the x, dt and y blocks of
a whole 2048-position prompt at bd 512, double-buffered, need 24 MiB of the
16 MiB a kernel may use.

Step t reads and writes one row of each (tb, ·) block at a dynamic sublane
offset, which the TPU compiler accepts only for 32-bit data: the wrapper
streams every sequence input in fp32 and casts y back afterwards.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, A_ref, B_ref, C_ref, D_ref, h0_ref, y_ref, hT_ref,
            h, *, tb, bd, N):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        h[...] = h0_ref[0].astype(jnp.float32)               # (bd, N)

    A = A_ref[...].astype(jnp.float32)                       # (bd, N)
    D = D_ref[...].astype(jnp.float32)                       # (1, bd)

    def step(t, _):
        x_t = x_ref[0, t, :].astype(jnp.float32)             # (bd,)
        dt_t = dt_ref[0, t, :].astype(jnp.float32)
        B_t = B_ref[0, t, :].astype(jnp.float32)             # (N,)
        C_t = C_ref[0, t, :].astype(jnp.float32)
        a = jnp.exp(dt_t[:, None] * A)
        b = (dt_t * x_t)[:, None] * B_t[None, :]
        h_new = a * h[...] + b
        h[...] = h_new
        y = jnp.einsum("dn,n->d", h_new, C_t,
                       preferred_element_type=jnp.float32) + D[0] * x_t
        y_ref[0, t, :] = y.astype(y_ref.dtype)
        return 0

    jax.lax.fori_loop(0, tb, step, 0)

    @pl.when(ti == pl.num_programs(2) - 1)
    def _done():
        hT_ref[0] = h[...]


@functools.partial(jax.jit, static_argnames=("d_block", "t_block",
                                             "interpret"))
def ssm_scan(x, dt, A, Bm, Cm, D, h0, *, d_block: int = 512,
             t_block: int = 256, interpret: bool = False):
    """x/dt: (B,T,Din); A: (Din,N); Bm/Cm: (B,T,N); D: (Din,);
    h0: (B,Din,N).  Time blocks of ``t_block`` positions where they divide
    T, else one block of T."""
    B, T, Din = x.shape
    N = A.shape[-1]
    bd = min(d_block, Din)
    assert Din % bd == 0
    nd = Din // bd
    tb = t_block if T % t_block == 0 else T
    nt = T // tb
    f32 = lambda a: a.astype(jnp.float32)
    y, hT = pl.pallas_call(
        functools.partial(_kernel, tb=tb, bd=bd, N=N),
        grid=(B, nd, nt),
        in_specs=[
            pl.BlockSpec((1, tb, bd), lambda b, d, t: (b, t, d)),
            pl.BlockSpec((1, tb, bd), lambda b, d, t: (b, t, d)),
            pl.BlockSpec((bd, N), lambda b, d, t: (d, 0)),
            pl.BlockSpec((1, tb, N), lambda b, d, t: (b, t, 0)),
            pl.BlockSpec((1, tb, N), lambda b, d, t: (b, t, 0)),
            pl.BlockSpec((1, bd), lambda b, d, t: (0, d)),
            pl.BlockSpec((1, bd, N), lambda b, d, t: (b, d, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, tb, bd), lambda b, d, t: (b, t, d)),
            pl.BlockSpec((1, bd, N), lambda b, d, t: (b, d, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, Din), jnp.float32),
            jax.ShapeDtypeStruct((B, Din, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bd, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(f32(x), f32(dt), A, f32(Bm), f32(Cm), D[None], h0)
    return y.astype(x.dtype), hT
