"""Pallas TPU flash attention (causal, GQA, optional sliding window).

TPU-native design (hardware adaptation per DESIGN.md):
* grid = (batch, q_heads, Sq/bq, Skv/bk) with the KV axis innermost — the
  sequential TPU grid carries the online-softmax state (m, l, acc) in VMEM
  scratch across KV tiles; output is written once on the final tile.
* BlockSpec tiling keeps one (bq, d) query tile, one (bk, d) KV tile, and
  the (bq, bk) score tile in VMEM; bq/bk default to 128/256 — multiples of
  the 128-wide MXU systolic dims, and a working set of
  (bq*d + 2*bk*d + bq*bk) * 4B ~ 0.6 MB for d=128, far under the ~16 MB
  VMEM budget, leaving room for double buffering.
* GQA is free: the KV BlockSpec index map folds q-head h onto kv-head
  h // (H/K), so no head replication ever materializes.
* Fully-masked KV tiles (beyond the causal frontier or outside the local
  window) are skipped with @pl.when — compiled FLOPs match the triangular/
  banded workload like the XLA path in models/attention.py.

Checked against kernels/ref.py in interpret mode on the CPU only
(tests/test_kernels_gp.py, and tests/test_kernels.py where hypothesis is
installed).  No model path calls it (``ModelConfig.use_pallas`` is not
read), and it has not been compiled for or run on a TPU.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  bq: int, bk: int, scale: float, causal: bool, window: int,
                  seq_q: int, seq_kv: int, softcap: float):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kv_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # tile visibility: skip tiles that the causal frontier / window excludes
    first_q = iq * bq
    last_q = first_q + bq - 1
    first_k = ik * bk
    last_k = first_k + bk - 1
    visible = True
    if causal:
        visible = jnp.asarray(first_k <= last_q)
    if window:
        visible = jnp.logical_and(visible,
                                  jnp.asarray(last_k >= first_q - window + 1))

    @pl.when(visible)
    def _tile():
        q = q_ref[0, :, 0, :].astype(jnp.float32)            # (bq, d)
        k = k_ref[0, :, 0, :].astype(jnp.float32)            # (bk, d)
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        mask = (kv_pos < seq_kv) & (q_pos < seq_q)
        if causal:
            mask &= q_pos >= kv_pos
        if window:
            mask &= (q_pos - kv_pos) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                                   # (bq,)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
        acc_scr[...] = (acc_scr[...] * alpha[:, None]
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, :, 0, :] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "bq", "bk", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, bq: int = 128, bk: int = 256,
                    interpret: bool = False):
    """q: (B, Sq, H, D); k,v: (B, Skv, K, D) with K | H. -> (B, Sq, H, D)."""
    B, Sq, H, D = q.shape
    _, Skv, K, _ = k.shape
    assert H % K == 0, "GQA requires kv_heads | q_heads"
    group = H // K
    scale = 1.0 / math.sqrt(D)

    bq_ = min(bq, max(Sq, 8))
    bk_ = min(bk, max(Skv, 8))
    # pad sequences up to tile multiples (masked out inside the kernel)
    pad_q = (-Sq) % bq_
    pad_k = (-Skv) % bk_
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))

    grid = (B, H, q.shape[1] // bq_, k.shape[1] // bk_)
    kernel = functools.partial(
        _flash_kernel, bq=bq_, bk=bk_, scale=scale, causal=causal,
        window=window, seq_q=Sq, seq_kv=Skv, softcap=softcap)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq_, 1, D), lambda b, h, i, j: (b, i, h, 0)),
            pl.BlockSpec((1, bk_, 1, D),
                         lambda b, h, i, j, g=group: (b, j, h // g, 0)),
            pl.BlockSpec((1, bk_, 1, D),
                         lambda b, h, i, j, g=group: (b, j, h // g, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq_, 1, D),
                               lambda b, h, i, j: (b, i, h, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            # VMEM online-softmax state, carried across KV tiles
            pltpu.VMEM((bq_,), jnp.float32),
            pltpu.VMEM((bq_,), jnp.float32),
            pltpu.VMEM((bq_, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    if pad_q:
        out = out[:, :Sq]
    return out
