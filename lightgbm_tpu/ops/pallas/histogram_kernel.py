"""Pallas TPU histogram kernel.

TPU-native replacement for the reference's OpenCL histogram kernels
(reference: src/treelearner/ocl/histogram256.cl — per-workgroup local-memory
float atomics). TPUs have no scatter-atomics; instead each grid step builds
one-hot tiles in VMEM and contracts them with (grad, hess, count) on the MXU,
accumulating into an output block that stays resident in VMEM across the
row-chunk grid axis. The one-hot never touches HBM — that is the entire
point versus the plain-XLA formulation in ops/histogram.py, whose cost is
dominated by streaming the materialized (N, F*B) one-hot through HBM.

Numerics: the one-hot is bf16-exact (0/1); gh is split into bf16 hi + lo
parts, packed side by side into ONE (C, 6) operand so a single bf16 MXU
pass covers both halves (hi+lo recombined in f32 outside the kernel,
rel err ~8e-7 — the same split-precision scheme as ops/histogram.py).
A full-f32 HIGHEST-precision matmul costs ~6 bf16 passes and measured
~3x slower end to end (a dated v5e reading, round-2 kernel).

Mosaic tiling rules require the last two dims of every block to be
(8k, 128k) or span the whole array, so the codes come in TRANSPOSED (F, P)
layout: the feature axis rides sublanes (tile 8) and the row axis rides
lanes (tile 128). Layouts:

    codes (F, P) int8   -> block (8, C)
    gh6   (P, 6) f32    -> block (C, 6)      (6 spans the array: allowed)
    out   (F, B, 6) f32 -> block (8, B, 6), index ignores the row-chunk
                           grid dim, so Pallas keeps it in VMEM and we
                           accumulate across chunks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

FEAT_TILE = 8


def _hist_kernel(codes_ref, gh6_ref, out_ref, *, num_bins: int):
    p_idx = pl.program_id(1)

    @pl.when(p_idx == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    gh6 = gh6_ref[...].astype(jnp.bfloat16)            # (C, 6)
    codes = codes_ref[...].astype(jnp.int32)           # (Ft, C)
    ft, c = codes.shape
    iota = jax.lax.broadcasted_iota(jnp.int32, (ft, num_bins, c), 1)
    onehot = (codes[:, None, :] == iota).astype(jnp.bfloat16)  # (Ft, B, C)
    part = jax.lax.dot_general(
        onehot.reshape(ft * num_bins, c), gh6,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                  # (Ft*B, 6)
    out_ref[...] += part.reshape(ft, num_bins, 6)


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "chunk_rows", "interpret"))
def build_histogram_pallas(binned_rows: jax.Array, gh: jax.Array, num_bins: int,
                           chunk_rows: int = 2048,
                           interpret: bool = False) -> jax.Array:
    """(P, F) codes + (P, 3) gh -> (F, B, 3) f32 histogram."""
    return build_histogram_pallas_t(binned_rows.T, gh, num_bins,
                                    chunk_rows=chunk_rows, interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "chunk_rows", "interpret"))
def build_histogram_pallas_t(codes_t: jax.Array, gh: jax.Array, num_bins: int,
                             chunk_rows: int = 2048,
                             interpret: bool = False) -> jax.Array:
    """(F, P) transposed codes + (P, 3) gh -> (F, B, 3) f32 histogram.

    The layout the device tree learner stores natively (column-major codes),
    so no transpose sits on the hot path. Pad rows carry gh == 0 so padding
    never contributes mass.
    """
    f, p = codes_t.shape
    pad_p = (-p) % chunk_rows
    pad_f = (-f) % FEAT_TILE
    if pad_p or pad_f:
        codes_t = jnp.pad(codes_t, ((0, pad_f), (0, pad_p)))
    if pad_p:
        gh = jnp.pad(gh, ((0, pad_p), (0, 0)))
    pp, ff = p + pad_p, f + pad_f

    # split-precision operand: [bf16-hi | residual-lo], one MXU pass
    gh_hi = gh.astype(jnp.bfloat16).astype(jnp.float32)
    gh6 = jnp.concatenate([gh_hi, gh - gh_hi], axis=1)           # (P, 6)

    grid = (ff // FEAT_TILE, pp // chunk_rows)
    out = pl.pallas_call(
        functools.partial(_hist_kernel, num_bins=num_bins),
        grid=grid,
        in_specs=[
            pl.BlockSpec((FEAT_TILE, chunk_rows), lambda fi, pi: (fi, pi)),
            pl.BlockSpec((chunk_rows, 6), lambda fi, pi: (pi, 0)),
        ],
        out_specs=pl.BlockSpec((FEAT_TILE, num_bins, 6),
                               lambda fi, pi: (fi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((ff, num_bins, 6), jnp.float32),
        interpret=interpret,
    )(codes_t, gh6)
    out = out[:, :, :3] + out[:, :, 3:]                          # hi + lo
    if pad_f:
        out = out[:f]
    return out


def _hist_kernel_q(codes_ref, ghq_ref, out_ref, *, num_bins: int):
    """Integer variant of _hist_kernel: ONE i8 matmul per tile
    accumulating EXACT int32 per-bin sums — no hi/lo split operand, no
    recombination pass, and a (C, 4) operand instead of (C, 6)."""
    p_idx = pl.program_id(1)

    @pl.when(p_idx == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    ghq = ghq_ref[...].astype(jnp.int8)                # (C, 4)
    codes = codes_ref[...].astype(jnp.int32)           # (Ft, C)
    ft, c = codes.shape
    iota = jax.lax.broadcasted_iota(jnp.int32, (ft, num_bins, c), 1)
    onehot = (codes[:, None, :] == iota).astype(jnp.int8)  # (Ft, B, C)
    part = jax.lax.dot_general(
        onehot.reshape(ft * num_bins, c), ghq,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )                                                  # (Ft*B, 4)
    out_ref[...] += part.reshape(ft, num_bins, 4)


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "chunk_rows", "interpret"))
def build_histogram_pallas_quantized(binned_rows: jax.Array, ghq: jax.Array,
                                     num_bins: int, chunk_rows: int = 2048,
                                     interpret: bool = False) -> jax.Array:
    """(P, F) codes + (P, 3) int8 [qg, qh, valid] -> (F, B, 3) int32."""
    return build_histogram_pallas_quantized_t(
        binned_rows.T, ghq, num_bins, chunk_rows=chunk_rows,
        interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "chunk_rows", "interpret"))
def build_histogram_pallas_quantized_t(codes_t: jax.Array, ghq: jax.Array,
                                       num_bins: int, chunk_rows: int = 2048,
                                       interpret: bool = False) -> jax.Array:
    """(F, P) transposed codes + (P, 3) int8 [qg, qh, valid] ->
    (F, B, 3) int32 exact histogram.

    Same tiling contract as build_histogram_pallas_t; the operand rides
    as int32 blocks (Mosaic's narrow-int tiling is stricter) and is cast
    to int8 inside the kernel, so the MXU sees the native i8
    contraction. Wider quantization (grad_bits > 8 stores int32) has no
    kernel: Mosaic rejects an i32 x i32 matmul on the chip ("Bad lhs/rhs
    type", v5e, jax 0.9.0). Pad rows must carry ghq == 0.
    """
    if ghq.dtype != jnp.int8:
        raise ValueError(
            "the Pallas quantized histogram kernel takes int8 gradients "
            f"(grad_bits <= 8), got {ghq.dtype}; unset LGBM_TPU_PALLAS "
            "for wider quantization")
    f, p = codes_t.shape
    pad_p = (-p) % chunk_rows
    pad_f = (-f) % FEAT_TILE
    if pad_p or pad_f:
        codes_t = jnp.pad(codes_t, ((0, pad_f), (0, pad_p)))
    ghq4 = jnp.pad(ghq.astype(jnp.int32), ((0, pad_p), (0, 1)))  # (P, 4)
    pp, ff = p + pad_p, f + pad_f

    grid = (ff // FEAT_TILE, pp // chunk_rows)
    out = pl.pallas_call(
        functools.partial(_hist_kernel_q, num_bins=num_bins),
        grid=grid,
        in_specs=[
            pl.BlockSpec((FEAT_TILE, chunk_rows), lambda fi, pi: (fi, pi)),
            pl.BlockSpec((chunk_rows, 4), lambda fi, pi: (pi, 0)),
        ],
        out_specs=pl.BlockSpec((FEAT_TILE, num_bins, 4),
                               lambda fi, pi: (fi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((ff, num_bins, 4), jnp.int32),
        interpret=interpret,
    )(codes_t, ghq4)
    out = out[:, :, :3]
    if pad_f:
        out = out[:f]
    return out
