"""Pallas TPU Mamba selective scan.

Grid (B, n_channel_blocks, n_time_blocks): each program owns an (N, bd)
state slab in VMEM fp32, carried across the time blocks (the innermost,
sequential axis):
    h <- exp(dt_t·Aᵀ)⊙h + B_tᵀ·(dt_t·x_t) ;  y_t = C_t·h + D⊙x_t
The state is laid out (d_state, channels): channels fill the 128 lanes and
the N states the sublanes, so at N = 16 a 512-channel slab is 8 full
vector registers, and a position's x_t, dt_t and y_t are (1, bd) rows that
broadcast over sublanes with no relayout; y_t is a sublane reduction.

A loop turn takes ``ROWS`` positions: x, dt and y move as aligned
(ROWS, bd) tiles, and B and C as a (ROWS, N) tile transposed once into
(N, ROWS), whose static columns broadcast over lanes.  Inside a turn only
h <- a⊙h + b is carried from one position to the next, so the exp, b and y
of neighbouring positions overlap.

Blocking time keeps the VMEM working set at (tb, bd) whatever the length,
and the wrapper takes the widest channel block whose double-buffered blocks
fit ``_VMEM_BYTES``: wider blocks give a position more independent vector
registers of work.  The wrapper streams every sequence input in fp32 and
casts y back afterwards.  Outside the kernel the state stays (B, Din, N),
as A stays (Din, N): the kernel transposes h0, hT and its A block once per
program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROWS = 8                # positions a loop turn: one f32 sublane tile
_LANES = 128
_VMEM_BYTES = 12 * 2**20    # of the 16 MiB of VMEM a kernel may use on v5e


def _kernel(x_ref, dt_ref, A_ref, B_ref, C_ref, D_ref, h0_ref, y_ref, hT_ref,
            h, *, tb):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        h[...] = h0_ref[0].T                                 # (N, bd)

    At = A_ref[...].T                                        # (N, bd)
    D = D_ref[...]                                           # (1, bd)

    def turn(g, h_val):
        t0 = pl.multiple_of(g * ROWS, ROWS)
        x = x_ref[0, pl.ds(t0, ROWS), :]                     # (ROWS, bd)
        dt = dt_ref[0, pl.ds(t0, ROWS), :]
        Bt = B_ref[0, pl.ds(t0, ROWS), :].T                  # (N, ROWS)
        Ct = C_ref[0, pl.ds(t0, ROWS), :].T
        u = dt * x
        ys = []
        for j in range(ROWS):
            a = jnp.exp(dt[j:j + 1] * At)                    # (N, bd)
            h_val = a * h_val + Bt[:, j:j + 1] * u[j:j + 1]
            ys.append(jnp.sum(Ct[:, j:j + 1] * h_val, axis=0, keepdims=True))
        y_ref[0, pl.ds(t0, ROWS), :] = jnp.concatenate(ys, axis=0) + D * x
        return h_val

    h[...] = jax.lax.fori_loop(0, tb // ROWS, turn, h[...])

    @pl.when(ti == pl.num_programs(2) - 1)
    def _done():
        hT_ref[0] = h[...].T                                 # (bd, N)


def _channel_block(Din: int, N: int, tb: int) -> int:
    """The widest multiple of 128 lanes that divides Din and keeps the
    double-buffered fp32 blocks within ``_VMEM_BYTES``: x, dt and y of tb
    positions, and A, h0 and hT, whose N columns fill whole 128-lane tiles;
    all of Din where it is no multiple of 128."""
    if Din % _LANES:
        return Din
    per_channel = 2 * 4 * (3 * tb + 3 * -(-N // _LANES) * _LANES)
    return max((bd for bd in range(_LANES, Din + 1, _LANES)
                if Din % bd == 0 and bd * per_channel <= _VMEM_BYTES),
               default=_LANES)


@functools.partial(jax.jit, static_argnames=("d_block", "t_block",
                                             "interpret"))
def ssm_scan(x, dt, A, Bm, Cm, D, h0, *, d_block: int | None = None,
             t_block: int = 256, interpret: bool = False):
    """x/dt: (B,T,Din); A: (Din,N); Bm/Cm: (B,T,N); D: (Din,);
    h0: (B,Din,N).  Returns y (B,T,Din) in x's dtype and hT (B,Din,N) fp32.
    T is padded with dt = 0, which leaves h as it is, to whole time blocks
    of ``t_block`` positions (a multiple of ROWS), or to one block of T
    rounded up to ROWS where that is shorter.  ``d_block`` (tests): the
    channel block, else the wrapper's choice."""
    B, T, Din = x.shape
    N = A.shape[-1]
    assert t_block % ROWS == 0
    tb = min(t_block, -(-T // ROWS) * ROWS)
    Tp = -(-T // tb) * tb
    bd = d_block or _channel_block(Din, N, tb)
    assert Din % bd == 0
    f32 = lambda a: a.astype(jnp.float32)
    pad = lambda a: jnp.pad(f32(a), ((0, 0), (0, Tp - T), (0, 0)))
    y, hT = pl.pallas_call(
        functools.partial(_kernel, tb=tb),
        grid=(B, Din // bd, Tp // tb),
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
            jax.ShapeDtypeStruct((B, Tp, Din), jnp.float32),
            jax.ShapeDtypeStruct((B, Din, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, bd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(pad(x), pad(dt), f32(A), pad(Bm), pad(Cm), f32(D)[None], f32(h0))
    return y[:, :T].astype(x.dtype), hT
