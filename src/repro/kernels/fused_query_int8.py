"""Fused int8-row gather + dequantize + coarse score + running top-k.

The int8 shortlist stage of ``core.pipeline.rerank_fused_quantized`` used to
dequantize candidate blocks with a plain jnp gather — the (B, chunk, d) f32
block materialized in HBM.  This kernel is ``kernels/fused_query.py`` with
an int8 rerank source: candidate ids arrive as a scalar-prefetch operand
(SMEM), the packed int8 rows stay in HBM, and the kernel DMAs exactly the
rows a tile needs — 4 * w bytes per candidate (1024 at d = 784) instead of
the fp32 store's 4 * dp (3584) — dequantizing in VMEM registers
(``byte * scale``) right before the distance math.  The dequantized tensor
never exists anywhere.

Contract (mirrored by ``kernels.ref.fused_gather_topk_int8_ref``):
  q (B, d) f32, ids (B, M) int32 with -1 marking invalid slots,
  q8 (N, d) int8 (read as ``pack_int8_rows(q8)``, below), scale (N,) f32
  ->  (dists (B, k) f32, ids (B, k) int32);
  invalid slots: +inf / -1.  The metric (l2 | dot | chi2 | cosine) scores
  the DEQUANTIZED rows, so the coarse shortlist ranks under the same
  metric the fp32 rerank of record applies (DESIGN.md §13); the symmetric
  per-row quantization stays L2-calibrated (DESIGN.md §11) — for chi2 the
  dequantized values are promoted to f32 before the divide.

The -1-id masking vocabulary is identical to fused_query.py, so segment
tombstones compose unchanged: a dead row's slot is -1 before the kernel,
issues no DMA, scores +inf.

Row store: as in fused_query.py, one DMA must move whole (1, 128-lane)
tiles, and an int8 array tiles its sublanes by 4 or more.  So the kernel
reads ``pack_int8_rows(q8)``: (N, 1, w) int32, w = ceil(d / 4) rounded up
to 128 lanes.  Coordinate ``p * w + j`` of a row is byte ``p`` of word
``j`` (zero-padded past d), and the kernel scores the four byte planes
against the matching lane-aligned slices of the zero-padded query.  The
per-row scales are gathered by XLA outside the kernel (4 bytes per
candidate) and arrive as a (bq, bm) VMEM block beside the ids.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat import tpu_compiler_params
from repro.kernels.common import POS_INF, merge_topk, select_topk_block
from repro.tracing import KERNEL_RERANK_INT8

EPS = 1e-12
LANE = 128
PLANES = 4          # int8 bytes per int32 word


def pack_int8_rows(q8: jax.Array) -> jax.Array:
    """(N, d) int8 rows -> the kernel's (N, 1, w) int32 row store."""
    n, d = q8.shape
    w = -(-d // (PLANES * LANE)) * LANE
    x = jnp.pad(q8, ((0, 0), (0, PLANES * w - d))).astype(jnp.int32)
    word = jnp.zeros((n, w), jnp.int32)
    for p in range(PLANES):
        word = word | ((x[:, p * w:(p + 1) * w] & 0xFF) << (8 * p))
    return word.reshape(n, 1, w)


def _kernel(ids_smem, q_ref, ids_ref, s_ref, q8_ref, out_d_ref, out_i_ref,
            rows, sem, *, bq: int, bm: int, k: int, metric: str):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_d_ref[...] = jnp.full_like(out_d_ref, POS_INF)
        out_i_ref[...] = jnp.full_like(out_i_ref, -1)

    # ---- tile-by-tile HBM gather: one packed int8 row per candidate -------
    def _copy(t):
        rid = ids_smem[i * bq + t // bm, j * bm + t % bm]
        return rid, pltpu.make_async_copy(
            q8_ref.at[jnp.maximum(rid, 0)], rows.at[t], sem)

    def _start(t, _):
        rid, cp = _copy(t)

        @pl.when(rid >= 0)
        def _():
            cp.start()
        return 0

    def _wait(t, _):
        rid, cp = _copy(t)

        @pl.when(rid >= 0)
        def _():
            cp.wait()
        return 0

    jax.lax.fori_loop(0, bq * bm, _start, 0)
    jax.lax.fori_loop(0, bq * bm, _wait, 0)

    # ---- unpack byte planes, dequantize, score under the metric -----------
    w = rows.shape[-1]
    words = rows[...].reshape(bq, bm, w)
    scale = s_ref[...][:, :, None]                          # (bq, bm, 1)
    qf = q_ref[...].astype(jnp.float32)                     # (bq, 4w)
    acc = [jnp.zeros((bq, bm), jnp.float32) for _ in range(3)]
    for p in range(PLANES):
        # sign-extend byte p: shift it to the top, arithmetic-shift back
        byte = jax.lax.shift_right_arithmetic(
            jax.lax.shift_left(words, jnp.int32(24 - 8 * p)), jnp.int32(24))
        deq = byte.astype(jnp.float32) * scale              # (bq, bm, w)
        q = qf[:, p * w:(p + 1) * w][:, None, :]            # (bq, 1, w)
        if metric == "l2":
            diff = q - deq
            acc[0] += jnp.sum(diff * diff, axis=-1)
        elif metric == "dot":
            acc[0] -= jnp.sum(q * deq, axis=-1)
        elif metric == "chi2":
            acc[0] += jnp.sum((q - deq) ** 2 / (q + deq + EPS), axis=-1)
        elif metric == "cosine":
            acc[0] += jnp.sum(q * deq, axis=-1)
            acc[1] += jnp.sum(q * q, axis=-1)
            acc[2] += jnp.sum(deq * deq, axis=-1)
        else:
            raise ValueError(metric)
    if metric == "cosine":
        scores = 1.0 - acc[0] / ((jnp.sqrt(acc[1]) + EPS)
                                 * (jnp.sqrt(acc[2]) + EPS))
    else:
        scores = acc[0]
    ids_vec = ids_ref[...]
    scores = jnp.where(ids_vec >= 0, scores, POS_INF)

    # ---- fold into the running (bq, k) top-k ------------------------------
    bd, bi = select_topk_block(scores, ids_vec, k)
    md, mi = merge_topk(out_d_ref[...], out_i_ref[...], bd, bi, k)
    out_d_ref[...] = md
    out_i_ref[...] = mi


@functools.partial(jax.jit, static_argnames=("k", "metric", "bq", "bm",
                                             "interpret"))
def fused_gather_topk_int8(q: jax.Array, ids: jax.Array, q8: jax.Array,
                           scale: jax.Array, k: int, metric: str = "l2",
                           bq: int = 8, bm: int = LANE,
                           interpret: bool = False
                           ) -> tuple[jax.Array, jax.Array]:
    """q (B, d), ids (B, M) int32 (-1 = invalid), q8, scale (N,) f32 ->
    coarse top-k (B, k) under ``metric`` on the dequantized rows.

    ``q8`` is the (N, 1, w) ``pack_int8_rows`` store.  Never
    materializes the gathered or dequantized (B, M, d) tensor: packed rows
    are DMA'd HBM -> VMEM tile-by-tile inside the kernel.
    """
    b, d = q.shape
    w = q8.shape[-1]
    m = ids.shape[1]
    bq = min(bq, max(1, b))
    bm = min(bm, m)
    b_pad = -b % bq
    m_pad = -m % bm
    qp = jnp.pad(q, ((0, b_pad), (0, PLANES * w - d)))
    idsp = jnp.pad(ids, ((0, b_pad), (0, m_pad)), constant_values=-1)
    sp = jnp.where(idsp >= 0, scale[jnp.maximum(idsp, 0)], 0.0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                     # ids -> SMEM
        grid=((b + b_pad) // bq, (m + m_pad) // bm),
        in_specs=[
            pl.BlockSpec((bq, PLANES * w), lambda i, j, *_: (i, 0)),
            pl.BlockSpec((bq, bm), lambda i, j, *_: (i, j)),
            pl.BlockSpec((bq, bm), lambda i, j, *_: (i, j)),   # scales
            pl.BlockSpec(memory_space=pl.ANY),  # packed rows stay in HBM
        ],
        out_specs=[
            pl.BlockSpec((bq, k), lambda i, j, *_: (i, 0)),
            pl.BlockSpec((bq, k), lambda i, j, *_: (i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq * bm, 1, w), jnp.int32),   # packed candidate tile
            pltpu.SemaphoreType.DMA,
        ],
    )
    out_d, out_i = pl.pallas_call(
        functools.partial(_kernel, bq=bq, bm=bm, k=k, metric=metric),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b + b_pad, k), jnp.float32),
            jax.ShapeDtypeStruct((b + b_pad, k), jnp.int32),
        ],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=KERNEL_RERANK_INT8,
    )(idsp, qp, idsp, sp, q8)
    out_d, out_i = out_d[:b], out_i[:b]
    return out_d, jnp.where(jnp.isinf(out_d), -1, out_i)
