"""Pallas TPU kernel: draw factor rows x = Λ⁻¹η + L⁻ᵀz in one HBM pass.

XLA's batched Cholesky and triangular solves go back to HBM about once
per column of the (N, K, K) factor, some hundred passes at K=100.  This
kernel reads a tile of 128 rows' Λ, η and z in their natural layout once,
factors, solves and adds the noise in VMEM, and writes only the (128, K)
sample block.

Layout: rows on the 128 lanes.  The prologue turns the (128, K, K) input
block into the scratch ``l[c, r, n] = Λ_n[c, r]`` (a strided sublane load
and a 2-D transpose per column c), so slab ``l[c]`` is column c of row
n's Λ with K on sublanes.  The batch of rows is the vector width: every
Cholesky step is a full-vreg FMA with a sublane broadcast, and every
dynamic index is on a leading dimension or an 8-aligned sublane offset.
K pads to Kp (a multiple of 8) with an identity diagonal, so the padded
factor is block diagonal and pad entries never reach a real one.  The
last tile of rows may run past N: those lanes hold whatever the buffer
held, lanes never mix, and Pallas drops their writes.

Algorithm (per tile, all in VMEM):

  1. Cholesky of Λ + jitter·I, blocked right-looking over 8-column
     panels.  Panel b's 8x8 diagonal block is factored in registers; the
     blocks below it are solved against that factor; then every later
     column k is updated by the panel's 8 columns at once (one load and
     one store of l[k, 8ib:8ib+8] per 8 FMAs).  Only the upper triangle of
     each Λ_n is read (Λ_n[c, r] for r >= c, equal to the lower one for a
     symmetric Λ); the strictly upper part of each diagonal block of L is
     zeroed.
  2. One forward solve y = L⁻¹η, then ONE backward solve
     x = L⁻ᵀ(y + z).  That is μ + δ of the three-solve form
     (μ = L⁻ᵀL⁻¹η, δ = L⁻ᵀz), with one pass fewer.

Everything is f32 on the VPU; the kernel has no ``dot``, so no MXU
precision setting is involved.

VMEM (``vmem_bytes``): the double-buffered input block, 128·K8·K128·4
bytes each (K8, K128: K rounded up to 8 and to 128), plus the factor
scratch Kp²·128·4 bytes: 19.2 MB at K=100, 25.2 MB at K=128, plus the
small η, z, x blocks and 4 MiB of room.  That is above the default scoped
limit, so ``vmem_limit_bytes`` is set from K; v5e has 128 MiB of VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128     # rows per tile: one lane tile
SUB = 8         # f32 sublanes per vreg: the panel width and K's padding

__all__ = ["LANES", "sample_rows_kernel"]


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def vmem_bytes(K: int) -> int:
    """Scoped VMEM the kernel asks for at width K (see the module
    docstring)."""
    block = LANES * _ceil_to(K, SUB) * _ceil_to(K, LANES) * 4
    Kp = _ceil_to(K, SUB)
    vec = LANES * _ceil_to(K, LANES) * 4
    return 2 * block + Kp * Kp * LANES * 4 + 8 * vec + (4 << 20)


# The kernel's arithmetic is written with lax primitives on whole (8, 128)
# vregs: a jnp operator is an inlined jit, traced again at every call, and
# the unrolled panel loops would pay that at every set-up.

def _bcast(v, s: int):
    """Sublane s of an (8, 128) vreg, broadcast to all 8 sublanes."""
    return lax.broadcast_in_dim(lax.slice(v, (s, 0), (s + 1, LANES)),
                                (SUB, LANES), (0, 1))


def _colsum(v):
    """Sum over the 8 sublanes of an (8, 128) vreg, broadcast back."""
    return lax.broadcast_in_dim(lax.reduce(v, np.float32(0), lax.add, (0,)),
                                (SUB, LANES), (1,))


def _fms(acc, a, b):
    """acc - a·b."""
    return lax.sub(acc, lax.mul(a, b))


def _sample_kernel(lam_ref, eta_ref, z_ref, x_ref, l_ref, v_ref, *,
                   jitter: float):
    K, Kp = lam_ref.shape[1], l_ref.shape[0]
    nb = Kp // SUB
    sub = jax.lax.broadcasted_iota(jnp.int32, (SUB, LANES), 0)

    # Blocks are addressed by (column slab k, sublane offset o = 8·ib);
    # index arithmetic is done once per loop body, not per access.
    def off(ib):
        return pl.multiple_of(ib * SUB, SUB)

    def lget(k, o):                  # rows o..o+7 of column slab k
        return l_ref[k, pl.ds(o, SUB), :]

    def lset(k, o, value):
        l_ref[k, pl.ds(o, SUB), :] = value

    def vget(o):
        return v_ref[pl.ds(o, SUB), :]

    def vset(o, value):
        v_ref[pl.ds(o, SUB), :] = value

    # --- 0. column slabs of Λ + jitter·I; identity on the pads ----------
    kk = jax.lax.broadcasted_iota(jnp.int32, (K, LANES), 0)

    def gather(c, carry):
        col = lam_ref[:, c, :].T                        # (K, 128)
        l_ref[c, pl.ds(0, K), :] = jnp.where(kk == c, col + jitter, col)
        if Kp > K:
            l_ref[c, pl.ds(K, Kp - K), :] = jnp.zeros((Kp - K, LANES),
                                                      jnp.float32)
        return carry

    jax.lax.fori_loop(0, K, gather, 0)
    pk = jax.lax.broadcasted_iota(jnp.int32, (Kp, LANES), 0)
    for c in range(K, Kp):
        l_ref[c] = (pk == c).astype(jnp.float32)

    zero = jnp.zeros((SUB, LANES), jnp.float32)
    eq = [sub == t for t in range(SUB)]
    gt = [sub > t for t in range(SUB)]

    # --- 1. Cholesky: L overwrites l_ref column slab by column slab -----
    def panel(b, c):
        ob = off(b)
        ks = [ob + t for t in range(SUB)]         # the panel's columns
        cols = [lget(ks[t], ob) for t in range(SUB)]
        diag = []
        for t in range(SUB):
            d = lax.sqrt(_bcast(cols[t], t))
            col = lax.select(gt[t], lax.div(cols[t], d),
                             lax.select(eq[t], d, zero))
            cols[t] = col
            diag.append(d)
            for t2 in range(t + 1, SUB):
                cols[t2] = _fms(cols[t2], col, _bcast(col, t2))
        for t in range(SUB):
            lset(ks[t], ob, cols[t])
        # coef[t][tp] = L[ob + t, ob + tp], tp < t
        coef = [[_bcast(cols[tp], t) for tp in range(t)] for t in range(SUB)]

        def below(ib, c2):
            o = off(ib)
            xs = [lget(ks[t], o) for t in range(SUB)]
            for t in range(SUB):
                acc = xs[t]
                for tp in range(t):
                    acc = _fms(acc, xs[tp], coef[t][tp])
                xs[t] = lax.div(acc, diag[t])
            for t in range(SUB):
                lset(ks[t], o, xs[t])
            return c2

        jax.lax.fori_loop(b + 1, nb, below, 0)

        def trail(kb, c2):
            okb = off(kb)
            p = [lget(ks[t], okb) for t in range(SUB)]
            for u in range(SUB):
                cu = [_bcast(p[t], u) for t in range(SUB)]  # L[okb+u, ob+t]
                k = okb + u

                def update(ib, c3):
                    o = off(ib)
                    acc = lget(k, o)
                    for t in range(SUB):
                        acc = _fms(acc, lget(ks[t], o), cu[t])
                    lset(k, o, acc)
                    return c3

                jax.lax.fori_loop(kb, nb, update, 0)
            return c2

        jax.lax.fori_loop(b + 1, nb, trail, 0)
        return c

    jax.lax.fori_loop(0, nb, panel, 0)

    # --- 2a. forward solve y = L⁻¹η, in v_ref --------------------------
    def put_vec(ref):
        v_ref[pl.ds(0, K), :] = ref[...].T
        if Kp > K:
            v_ref[pl.ds(K, Kp - K), :] = jnp.zeros((Kp - K, LANES),
                                                   jnp.float32)

    put_vec(eta_ref)

    def fwd(b, c):
        ob = off(b)
        ks = [ob + t for t in range(SUB)]
        yb = vget(ob)
        for t in range(SUB):
            col = lget(ks[t], ob)
            yt = lax.div(_bcast(yb, t), _bcast(col, t))
            yb = lax.select(eq[t], yt, _fms(yb, col, yt))
        vset(ob, yb)
        ys = [_bcast(yb, t) for t in range(SUB)]

        def below(ib, c2):
            o = off(ib)
            acc = vget(o)
            for t in range(SUB):
                acc = _fms(acc, lget(ks[t], o), ys[t])
            vset(o, acc)
            return c2

        jax.lax.fori_loop(b + 1, nb, below, 0)
        return c

    jax.lax.fori_loop(0, nb, fwd, 0)

    # --- 2b. backward solve x = L⁻ᵀ(y + z) -----------------------------
    y = v_ref[...]
    put_vec(z_ref)
    v_ref[...] = v_ref[...] + y

    def bwd(i, c):
        b = nb - 1 - i
        ob = off(b)
        ks = [ob + t for t in range(SUB)]

        def below(ib, accs):
            o = off(ib)
            xb = vget(o)
            return tuple(lax.add(accs[t], lax.mul(lget(ks[t], o), xb))
                         for t in range(SUB))

        accs = jax.lax.fori_loop(b + 1, nb, below, (zero,) * SUB)
        wb = vget(ob)
        for t in range(SUB):
            wb = lax.sub(wb, lax.select(eq[t], _colsum(accs[t]), zero))
        xb = zero
        for t in reversed(range(SUB)):
            col = lget(ks[t], ob)
            # Σ_{s>t} L[ob+s, ob+t]·x_s: xb is still zero at s <= t
            st = _colsum(lax.mul(col, xb))
            xt = lax.div(lax.sub(_bcast(wb, t), st), _bcast(col, t))
            xb = lax.select(eq[t], xt, xb)
        vset(ob, xb)
        return c

    jax.lax.fori_loop(0, nb, bwd, 0)
    x_ref[...] = v_ref[...].T[:, :K]


def sample_rows_kernel(Lambda, eta, z, jitter: float = 1e-6, *,
                       interpret: bool = False):
    """x_n = Λ_n⁻¹η_n + L_n⁻ᵀz_n with L_n L_nᵀ = Λ_n + jitter·I: Lambda
    (N, K, K), eta and z (N, K), any N; returns (N, K) f32."""
    N, K = eta.shape
    Kp = _ceil_to(K, SUB)
    row = pl.BlockSpec((LANES, K), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_sample_kernel, jitter=float(jitter)),
        grid=(pl.cdiv(N, LANES),),
        in_specs=[pl.BlockSpec((LANES, K, K), lambda i: (i, 0, 0)), row,
                  row],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((N, K), jnp.float32),
        scratch_shapes=[pltpu.VMEM((Kp, Kp, LANES), jnp.float32),
                        pltpu.VMEM((Kp, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=vmem_bytes(K)),
        interpret=interpret,
    )(Lambda.astype(jnp.float32), eta.astype(jnp.float32),
      z.astype(jnp.float32))
