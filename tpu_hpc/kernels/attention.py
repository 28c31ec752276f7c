"""Blockwise (flash) attention with log-sum-exp output.

The compute core of the sequence-parallel family (SURVEY.md 5.7): both
Ring Attention (parallel/ring_attention.py) and Ulysses
(parallel/sp_ulysses.py) need an attention op that (a) handles a causal
mask expressed in *global* coordinates via q/kv offsets, and (b) returns
the per-row log-sum-exp so partial results from different KV chunks can
be merged exactly (the online-softmax identity the reference documents
in docs/guide/08_sequence_parallel.md:84-142 but never implements).

Two interchangeable implementations:
  * ``attention_reference`` -- pure jnp, differentiable, runs anywhere.
    XLA already fuses this well on TPU for moderate sequence lengths.
  * ``flash_attention`` -- a Pallas TPU kernel: online softmax over KV
    blocks, fp32 accumulators in VMEM scratch, bf16 matmuls on the MXU,
    causal blocks above the diagonal skipped. Gradients come from a
    custom_vjp whose backward runs the hand-written Pallas dq and
    dk/dv kernels below (``_flash_dq_kernel`` / ``_flash_dk_dv_kernel``),
    rematerialising p = softmax(qk) from the saved LSE instead of
    storing the attention matrix.

Layout convention: [B, S, H, D] (model order, models/llama2.py);
LSE is [B, S, H] fp32. Masking uses a large finite negative instead of
-inf so both forward and backward stay NaN-free on fully-masked rows.

Arbitrary sequence lengths are supported: inputs are zero-padded to a
block multiple, padded KV columns are masked in-kernel, and outputs
are sliced back. (The reference's SDPA has no length constraint; a
181x360 weather grid or an odd ring shard must work here too.)
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MASK_VALUE = -1e30


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _pad_seq(x: jax.Array, n: int) -> jax.Array:
    """Zero-pad the sequence axis (axis 1) by ``n``."""
    cfg = [(0, 0)] * x.ndim
    cfg[1] = (0, n)
    return jnp.pad(x, cfg)


def pick_block_sizes(
    block_q: int, block_k: int, sq: int, sk: int
) -> Tuple[int, int]:
    """Clamp requested flash block sizes to the (128-aligned) sequence
    lengths. Short sequences must not pad all the way up to the
    requested block -- a 37-token prompt under block 512 would burn
    ~14x the VMEM and MXU work on masked rows -- but blocks stay
    128-aligned so TPU lane tiling holds. The ONE selection rule for
    the flash kernels here, forward and backward; hand-synced copies
    drifted once already."""
    return (
        min(block_q, _round_up(sq, 128)),
        min(block_k, _round_up(sk, 128)),
    )


# ---------------------------------------------------------------------------
# Pure-XLA reference path (differentiable, runs on any backend)
# ---------------------------------------------------------------------------

def attention_reference(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    q_offset: jax.Array | int = 0,
    kv_offset: jax.Array | int = 0,
    sm_scale: Optional[float] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Softmax attention of a Q chunk against a KV chunk.

    q: [B, Sq, Hq, D]; k, v: [B, Sk, Hkv, D] with Hq a multiple of
    Hkv (GQA handled by a grouped query view -- K/V are broadcast
    over the group dim, never materialised repeated). Returns
    (out [B, Sq, Hq, D] in q.dtype, lse [B, Sq, Hq] fp32). ``causal``
    masks using global positions ``q_offset + i >= kv_offset + j``; a
    fully-masked row yields out=0, lse=MASK_VALUE (so it merges as a
    no-op).
    """
    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qf = q.astype(jnp.float32).reshape(b, sq, hkv, g, d)
    kf = k.astype(jnp.float32)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kf) * scale
    if causal:
        rows = q_offset + jnp.arange(q.shape[1])[:, None]
        cols = kv_offset + jnp.arange(k.shape[1])[None, :]
        s = jnp.where(rows >= cols, s, MASK_VALUE)
    m = jnp.max(s, axis=-1)
    m_safe = jnp.where(m <= MASK_VALUE * 0.5, 0.0, m)
    p = jnp.where(
        s > MASK_VALUE * 0.5, jnp.exp(s - m_safe[..., None]), 0.0
    )
    l = jnp.sum(p, axis=-1)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v)
    out = out.reshape(b, sq, hq, d)
    l_t = l_safe.transpose(0, 3, 1, 2).reshape(b, sq, hq)
    out = out / l_t[..., None].astype(out.dtype)
    lse = m + jnp.log(l_safe)  # fully masked: MASK_VALUE + 0
    return out.astype(q.dtype), lse.transpose(0, 3, 1, 2).reshape(b, sq, hq)


def lse_merge(
    o1: jax.Array, lse1: jax.Array, o2: jax.Array, lse2: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Exactly merge two attention partials over disjoint KV sets.

    o*: [B, S, H, D], lse*: [B, S, H]. The online-softmax identity
    (reference doc 08_sequence_parallel.md:120-139), written so that a
    MASK_VALUE (empty) side is an exact no-op and gradients are
    NaN-free.
    """
    m = jnp.maximum(lse1, lse2)
    m_safe = jnp.where(m <= MASK_VALUE * 0.5, 0.0, m)
    w1 = jnp.exp(lse1 - m_safe)
    w2 = jnp.exp(lse2 - m_safe)
    denom = w1 + w2
    denom_safe = jnp.where(denom == 0.0, 1.0, denom)
    lse = m + jnp.log(denom_safe)
    wo1 = (w1 / denom_safe)[..., None].astype(o1.dtype)
    wo2 = (w2 / denom_safe)[..., None].astype(o2.dtype)
    return o1 * wo1 + o2 * wo2, lse


# ---------------------------------------------------------------------------
# Pallas TPU flash kernel (forward)
# ---------------------------------------------------------------------------

def _flash_kernel(
    qo_ref,  # SMEM (1, 1) int32: global q offset
    ko_ref,  # SMEM (1, 1) int32: global kv offset
    q_ref,   # VMEM (1, block_q, D)
    k_ref,   # VMEM (1, block_k, D)
    v_ref,   # VMEM (1, block_k, D)
    o_ref,   # VMEM (1, block_q, D)
    lse_ref,  # VMEM (1, block_q, 1) -- trailing 1 keeps TPU tiling legal
    acc_ref,  # scratch (block_q, D) f32
    m_ref,    # scratch (block_q, 1) f32
    l_ref,    # scratch (block_q, 1) f32
    *,
    sm_scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    kv_len: int,
):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    qi = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, MASK_VALUE)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q_start = qo_ref[0, 0] + qi * block_q
    k_start = ko_ref[0, 0] + ki * block_k
    # Causal skip: KV block entirely in the future of this Q block.
    live = (
        (q_start + block_q - 1 >= k_start) if causal else (ki >= 0)
    )

    @pl.when(live)
    def _step():
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            cols = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(rows >= cols, s, MASK_VALUE)
        if kv_len % block_k:
            # Zero-padded KV tail (local coords, offset-independent).
            local = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(local < kv_len, s, MASK_VALUE)
        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        m_safe = jnp.where(m_new <= MASK_VALUE * 0.5, 0.0, m_new)
        p = jnp.where(s > MASK_VALUE * 0.5, jnp.exp(s - m_safe), 0.0)
        alpha = jnp.exp(m_prev - m_safe)
        l_ref[:] = alpha * l_ref[:] + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[:] = alpha * acc_ref[:] + pv
        m_ref[:] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_ref[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = m_ref[:] + jnp.log(l_safe)


def _flash_forward(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_offset: jax.Array,
    kv_offset: jax.Array,
    *,
    causal: bool,
    sm_scale: float,
    block_q: int,
    block_k: int,
    interpret: bool,
) -> Tuple[jax.Array, jax.Array]:
    """[B, Sq, Hq, D] x [B, Sk, Hkv, D] -> (out, lse [B, Sq, Hq]).

    GQA (Hkv < Hq): the grid runs over B*Hq query heads and the K/V
    BlockSpec index maps fold the group factor, so each group shares
    one K/V head straight out of HBM -- no repeated K/V is ever
    materialised.

    Arbitrary seq lens: pad to a block multiple (blocks clamp to the
    128-aligned length for short sequences, keeping TPU lane tiling),
    mask the padded KV tail in-kernel, slice the padded Q tail off the
    outputs.
    """
    b, sq, h, d = q.shape
    hkv = k.shape[2]
    if h % hkv:
        raise ValueError(f"GQA needs Hq % Hkv == 0, got {h} % {hkv}")
    g = h // hkv
    sk = k.shape[1]
    block_q, block_k = pick_block_sizes(block_q, block_k, sq, sk)
    sq_p = _round_up(sq, block_q)
    sk_p = _round_up(sk, block_k)
    if sq_p != sq:
        q = _pad_seq(q, sq_p - sq)
    if sk_p != sk:
        k = _pad_seq(k, sk_p - sk)
        v = _pad_seq(v, sk_p - sk)
    # [B, S, H, D] -> [B*H, S, D]: heads become the parallel grid dim.
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, sq_p, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * hkv, sk_p, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * hkv, sk_p, d)
    qo = jnp.asarray(q_offset, jnp.int32).reshape(1, 1)
    ko = jnp.asarray(kv_offset, jnp.int32).reshape(1, 1)

    # Query-head grid index -> shared KV head (head-major grouping:
    # q head hq maps to kv head hq // g).
    def kv_head(bh):
        return (bh // h) * hkv + (bh % h) // g

    grid = (b * h, sq_p // block_q, sk_p // block_k)
    kernel = functools.partial(
        _flash_kernel,
        sm_scale=sm_scale,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        kv_len=sk,
    )
    smem = pl.BlockSpec(
        (1, 1), lambda bh, i, j: (0, 0), memory_space=pltpu.SMEM
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            smem,
            smem,
            pl.BlockSpec(
                (1, block_q, d), lambda bh, i, j: (bh, i, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, block_k, d), lambda bh, i, j: (kv_head(bh), j, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, block_k, d), lambda bh, i, j: (kv_head(bh), j, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=[
            pl.BlockSpec(
                (1, block_q, d), lambda bh, i, j: (bh, i, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, block_q, 1), lambda bh, i, j: (bh, i, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq_p, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, sq_p, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(qo, ko, qt, kt, vt)
    out = out.reshape(b, h, sq_p, d).transpose(0, 2, 1, 3)[:, :sq]
    lse = lse.reshape(b, h, sq_p).transpose(0, 2, 1)[:, :sq]
    return out, lse  # lse [B, Sq, H]


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11)
)
def flash_attention(
    q, k, v, q_offset, kv_offset,
    causal=True, sm_scale=None, block_q=512, block_k=512,
    interpret=False, block_q_bwd=None, block_k_bwd=None,
):
    """Pallas flash attention: (out, lse), same contract as
    ``attention_reference``. Gradients come from the hand-written
    Pallas dq/dkv kernels below (_flash_bwd) -- no forward recompute,
    no [S, S] buffer. ``block_q_bwd``/``block_k_bwd`` tile the
    backward kernels independently of the forward (None = same as
    forward; the backward's dkv kernel transposes the score block, so
    its best tiling can differ -- see kernels/autotune.py)."""
    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    return _flash_forward(
        q, k, v, q_offset, kv_offset,
        causal=causal, sm_scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )


def _flash_fwd(q, k, v, q_offset, kv_offset,
               causal, sm_scale, block_q, block_k, interpret,
               block_q_bwd, block_k_bwd):
    out, lse = flash_attention(
        q, k, v, q_offset, kv_offset,
        causal, sm_scale, block_q, block_k, interpret,
        block_q_bwd, block_k_bwd,
    )
    return (out, lse), (q, k, v, out, lse, q_offset, kv_offset)


def _flash_bwd(causal, sm_scale, block_q, block_k, interpret,
               block_q_bwd, block_k_bwd,
               residuals, grads):
    """Backward from saved (out, lse) via the Pallas dq/dkv kernels --
    the standard flash-attention gradient identities with no forward
    recompute, no softmax, and no [S, S] buffer in HBM:
      P  = exp(S - lse)            (S rebuilt blockwise from q, k)
      dS = P * (dout @ v^T - (rowsum(dout*out) - dlse))
      dq = scale * dS @ k;  dk = scale * dS^T @ q;  dv = P^T @ dout
    The dlse term is the lse output's own cotangent (ring attention's
    merge differentiates through lse), folded into the per-row D.
    """
    q, k, v, out, lse, q_offset, kv_offset = residuals
    dout, dlse = grads
    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    dq, dk, dv = _flash_backward(
        q, k, v, out, lse, dout, dlse, q_offset, kv_offset,
        causal=causal, sm_scale=scale,
        block_q=block_q_bwd or block_q, block_k=block_k_bwd or block_k,
        interpret=interpret,
    )
    return dq, dk, dv, None, None


flash_attention.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# Pallas TPU flash backward: dq kernel + dkv kernel (flash-2 style).
# No [S, S] buffer ever reaches HBM -- the bandwidth win over an
# XLA-level backward, which materializes ~5 fp32 score-shaped arrays.
# ---------------------------------------------------------------------------

def _flash_dq_kernel(
    qo_ref, ko_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dm_ref,
    dq_ref, acc_ref, *, sm_scale, causal, block_q, block_k, kv_len,
):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    qi = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q_start = qo_ref[0, 0] + qi * block_q
    k_start = ko_ref[0, 0] + ki * block_k
    live = (q_start + block_q - 1 >= k_start) if causal else (ki >= 0)

    @pl.when(live)
    def _step():
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            cols = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(rows >= cols, s, MASK_VALUE)
        if kv_len % block_k:
            local = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(local < kv_len, s, MASK_VALUE)
        p = jnp.where(
            s > MASK_VALUE * 0.5, jnp.exp(s - lse_ref[0]), 0.0
        )
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - dm_ref[0])
        acc_ref[:] += sm_scale * jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _flash_dk_dv_kernel(
    qo_ref, ko_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dm_ref,
    dk_ref, dv_ref, dk_acc, dv_acc, *, sm_scale, causal, block_q, block_k,
    kv_len,
):
    qi = pl.program_id(2)
    nq = pl.num_programs(2)
    ki = pl.program_id(1)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_start = qo_ref[0, 0] + qi * block_q
    k_start = ko_ref[0, 0] + ki * block_k
    live = (q_start + block_q - 1 >= k_start) if causal else (qi >= 0)

    @pl.when(live)
    def _step():
        # s^T [block_k, block_q]: scores with K as rows.
        st = jax.lax.dot_general(
            k_ref[0], q_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale
        if causal:
            cols = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 0
            )
            rows = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 1
            )
            st = jnp.where(rows >= cols, st, MASK_VALUE)
        if kv_len % block_k:
            local = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 0
            )
            st = jnp.where(local < kv_len, st, MASK_VALUE)
        # lse/dm are per-q-row: broadcast along the k dim (axis 0).
        pt = jnp.where(
            st > MASK_VALUE * 0.5,
            jnp.exp(st - lse_ref[0][:, 0][None, :]),
            0.0,
        )
        dv_acc[:] += jax.lax.dot_general(
            pt.astype(do_ref.dtype), do_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dpt = jax.lax.dot_general(
            v_ref[0], do_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dst = pt * (dpt - dm_ref[0][:, 0][None, :])
        dk_acc[:] += sm_scale * jax.lax.dot_general(
            dst.astype(q_ref.dtype), q_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_backward(
    q, k, v, out, lse, dout, dlse, q_offset, kv_offset,
    *, causal, sm_scale, block_q, block_k, interpret,
):
    """[B, S, H, D] layouts in, (dq, dk, dv) out. GQA: k/v carry Hkv
    heads; dk/dv are computed per *query* head on the grid and
    group-summed at the end (matching d(repeat)/dk = sum-over-group),
    while K/V themselves are read via the shared-head index map."""
    b, sq, h, d = q.shape
    hkv = k.shape[2]
    g = h // hkv
    sk = k.shape[1]
    block_q, block_k = pick_block_sizes(block_q, block_k, sq, sk)
    sq_p = _round_up(sq, block_q)
    sk_p = _round_up(sk, block_k)
    # Zero-pad to block multiples. Padded q rows contribute exactly
    # zero to dk/dv (dout rows are zero), and padded kv rows to dq
    # (k rows are zero); padded dk/dv/dq rows are sliced off below.
    # The in-kernel kv_len mask keeps p itself correct.
    if sq_p != sq:
        q = _pad_seq(q, sq_p - sq)
        out = _pad_seq(out, sq_p - sq)
        dout = _pad_seq(dout, sq_p - sq)
        lse = _pad_seq(lse, sq_p - sq)
        if dlse is not None:
            dlse = _pad_seq(dlse, sq_p - sq)
    if sk_p != sk:
        k = _pad_seq(k, sk_p - sk)
        v = _pad_seq(v, sk_p - sk)
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, sq_p, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * hkv, sk_p, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * hkv, sk_p, d)

    def kv_head(bh):
        return (bh // h) * hkv + (bh % h) // g
    dot = dout.transpose(0, 2, 1, 3).reshape(b * h, sq_p, d)
    lse_t = lse.transpose(0, 2, 1).reshape(b * h, sq_p, 1)
    # D - dlse folded into one per-row vector: ds = P*(dP - D + dlse).
    d_row = jnp.sum(
        dout.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )
    if dlse is not None:
        d_row = d_row - dlse
    dm_t = d_row.transpose(0, 2, 1).reshape(b * h, sq_p, 1)
    qo = jnp.asarray(q_offset, jnp.int32).reshape(1, 1)
    ko = jnp.asarray(kv_offset, jnp.int32).reshape(1, 1)

    smem = pl.BlockSpec(
        (1, 1), lambda bh, i, j: (0, 0), memory_space=pltpu.SMEM
    )

    def vspec(blk, which):
        return pl.BlockSpec(
            (1, blk, d),
            (lambda bh, i, j: (bh, i, 0)) if which == "i"
            else (lambda bh, i, j: (bh, j, 0)),
            memory_space=pltpu.VMEM,
        )

    def kvspec(blk, which):
        return pl.BlockSpec(
            (1, blk, d),
            (lambda bh, i, j: (kv_head(bh), i, 0)) if which == "i"
            else (lambda bh, i, j: (kv_head(bh), j, 0)),
            memory_space=pltpu.VMEM,
        )

    def rspec(blk, which):
        return pl.BlockSpec(
            (1, blk, 1),
            (lambda bh, i, j: (bh, i, 0)) if which == "i"
            else (lambda bh, i, j: (bh, j, 0)),
            memory_space=pltpu.VMEM,
        )

    dq = pl.pallas_call(
        functools.partial(
            _flash_dq_kernel, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, kv_len=sk,
        ),
        grid=(b * h, sq_p // block_q, sk_p // block_k),
        in_specs=[
            smem, smem,
            vspec(block_q, "i"), kvspec(block_k, "j"), kvspec(block_k, "j"),
            vspec(block_q, "i"), rspec(block_q, "i"), rspec(block_q, "i"),
        ],
        out_specs=vspec(block_q, "i"),
        out_shape=jax.ShapeDtypeStruct((b * h, sq_p, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(qo, ko, qt, kt, vt, dot, lse_t, dm_t)

    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_dk_dv_kernel, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, kv_len=sk,
        ),
        grid=(b * h, sk_p // block_k, sq_p // block_q),
        in_specs=[
            smem, smem,
            vspec(block_q, "j"), kvspec(block_k, "i"), kvspec(block_k, "i"),
            vspec(block_q, "j"), rspec(block_q, "j"), rspec(block_q, "j"),
        ],
        out_specs=[vspec(block_k, "i"), vspec(block_k, "i")],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sk_p, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, sk_p, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qo, ko, qt, kt, vt, dot, lse_t, dm_t)

    unflat = lambda x, sp, s: (
        x.reshape(b, h, sp, d).transpose(0, 2, 1, 3)[:, :s]
    )  # noqa: E731
    dq = unflat(dq, sq_p, sq)
    dk = unflat(dk, sk_p, sk)
    dv = unflat(dv, sk_p, sk)
    if g > 1:
        # Per-query-head dk/dv -> shared-head gradients (the
        # sum-over-group that d(repeat_kv) would have produced).
        dk = dk.reshape(b, sk, hkv, g, d).sum(axis=3)
        dv = dv.reshape(b, sk, hkv, g, d).sum(axis=3)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Dispatcher
# ---------------------------------------------------------------------------

def blockwise_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    q_offset: jax.Array | int = 0,
    kv_offset: jax.Array | int = 0,
    sm_scale: Optional[float] = None,
    impl: str = "auto",
    block_q: int = 512,
    block_k: int = 512,
    block_q_bwd: Optional[int] = None,
    block_k_bwd: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Chunk attention with LSE; ``impl`` in {auto, xla, pallas,
    pallas_interpret}. ``auto`` picks the Pallas kernel on TPU and the
    XLA path elsewhere (CPU-simulated meshes in tests).
    ``block_q_bwd``/``block_k_bwd`` tile the backward kernels
    independently (None = same as forward)."""
    if q.shape[2] % k.shape[2]:
        # Checked here for BOTH impls: the Pallas index maps would
        # otherwise silently read cross-batch / clamped KV heads.
        raise ValueError(
            f"GQA needs Hq % Hkv == 0, got {q.shape[2]} % {k.shape[2]}"
        )
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "xla":
        return attention_reference(
            q, k, v, causal=causal,
            q_offset=q_offset, kv_offset=kv_offset, sm_scale=sm_scale,
        )
    if impl in ("pallas", "pallas_interpret"):
        return flash_attention(
            q, k, v,
            jnp.asarray(q_offset, jnp.int32),
            jnp.asarray(kv_offset, jnp.int32),
            causal, sm_scale, block_q, block_k,
            impl == "pallas_interpret",
            block_q_bwd, block_k_bwd,
        )
    raise ValueError(f"unknown attention impl: {impl!r}")
