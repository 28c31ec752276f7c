"""Flash-attention block-size autotuner: measure, don't guess.

The reference delegates attention kernel selection to cuDNN/SDPA
heuristics (fsdp_tp/llama2_model.py:206-228 calls
F.scaled_dot_product_attention and lets the runtime pick). On TPU the
Pallas kernel's VMEM tiling is ours to choose, and the best
(block_q, block_k) pair depends on sequence length, head count, and
which kernel is running -- the backward's dkv kernel works on
transposed [block_k, block_q] score tiles, so its optimum can differ
from the forward's. This module times candidate tilings on the local
chip and reports a ranked table, the same measure-first discipline as
the comm benchmark (comm/bench.py) applied one level down.

Timing protocol: each candidate compiles ONE jitted chain of ``iters``
dependent kernel applications (output feeds the next input, so XLA
cannot parallelize or elide them) that reduces to a scalar; the clock
stops when that scalar is on the host. The chain amortizes the
per-dispatch cost to <1% of a call
(checks/env_check.py:chip_microbench times the same way).
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import sys
import time
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from tpu_hpc.kernels.attention import blockwise_attention

DEFAULT_CANDIDATES: Tuple[Tuple[int, int], ...] = (
    (256, 256), (256, 512), (512, 256),
    (512, 512), (512, 1024), (1024, 512), (1024, 1024),
)


@dataclasses.dataclass
class TuneRecord:
    block_q: int
    block_k: int
    block_q_bwd: Optional[int]
    block_k_bwd: Optional[int]
    ms_per_call: float
    mode: str  # "fwd" | "grad"

    def blocks(self) -> str:
        s = f"{self.block_q}/{self.block_k}"
        if self.block_q_bwd or self.block_k_bwd:
            s += (
                f" bwd {self.block_q_bwd or self.block_q}"
                f"/{self.block_k_bwd or self.block_k}"
            )
        return s


def _time_candidate(
    q, k, v, *, causal: bool, impl: str, iters: int,
    block_q: int, block_k: int,
    block_q_bwd: Optional[int], block_k_bwd: Optional[int],
    mode: str,
) -> float:
    attn = functools.partial(
        blockwise_attention, causal=causal, impl=impl,
        block_q=block_q, block_k=block_k,
        block_q_bwd=block_q_bwd, block_k_bwd=block_k_bwd,
    )

    if mode == "fwd":
        def body(x, _):
            out, _lse = attn(x, k, v)
            return out.astype(x.dtype), ()
    elif mode == "grad":
        groups = q.shape[2] // k.shape[2]

        def body(x, _):
            # Differentiate wrt ALL of q, k, v: the backward is two
            # pallas_calls (dq and dkv) and a q-only grad would let
            # jit DCE the dkv kernel entirely -- the sweep would then
            # rank tilings by fwd+dq cost alone.
            gq, gk, gv = jax.grad(
                lambda xq, xk, xv: jnp.sum(
                    attn(xq, xk, xv)[0].astype(jnp.float32)
                ),
                argnums=(0, 1, 2),
            )(x, k, v)
            # Fold dk/dv into the carry (GQA-aware head repeat) so no
            # output is dead; renormalize so the chain neither explodes
            # nor collapses to denormals (timing-neutral: same ops
            # every step).
            g = gq + jnp.repeat(gk + gv, groups, axis=2)
            g = g / (jnp.max(jnp.abs(g)) + 1e-6)
            return g.astype(x.dtype), ()
    else:
        raise ValueError(f"unknown mode {mode!r} (fwd|grad)")

    @jax.jit
    def chain(x):
        x, _ = jax.lax.scan(body, x, None, length=iters)
        return jnp.sum(x.astype(jnp.float32))

    float(jax.device_get(chain(q)))  # compile + warm
    t0 = time.perf_counter()
    float(jax.device_get(chain(q)))
    return (time.perf_counter() - t0) / iters * 1e3


def autotune(
    seq_len: int = 2048,
    batch: int = 4,
    n_heads: int = 8,
    kv_heads: Optional[int] = None,
    head_dim: int = 128,
    causal: bool = True,
    mode: str = "grad",
    candidates: Sequence[Tuple[int, int]] = DEFAULT_CANDIDATES,
    sweep_bwd: bool = False,
    iters: int = 64,
    impl: str = "pallas",
    seed: int = 0,
) -> List[TuneRecord]:
    """Time every candidate tiling at the given attention shape and
    return records sorted fastest-first.

    ``mode="grad"`` times forward+backward through the custom_vjp
    (what a training step pays); ``mode="fwd"`` times inference.
    ``sweep_bwd=True`` additionally sweeps the backward-only tilings
    with the forward pinned to the best forward candidate found --
    the two kernels are tiled independently (blockwise_attention's
    block_q_bwd/block_k_bwd).
    """
    kv_heads = kv_heads or n_heads
    kq, kk, kv_ = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(
        kq, (batch, seq_len, n_heads, head_dim), jnp.bfloat16
    )
    k = jax.random.normal(
        kk, (batch, seq_len, kv_heads, head_dim), jnp.bfloat16
    )
    v = jax.random.normal(
        kv_, (batch, seq_len, kv_heads, head_dim), jnp.bfloat16
    )

    records: List[TuneRecord] = []
    usable = [
        (bq, bk) for bq, bk in candidates
        if bq <= seq_len and bk <= seq_len
    ]
    if not usable:
        raise ValueError(
            f"no candidate fits seq_len {seq_len}: blocks "
            f"{sorted(set(candidates))} all exceed it -- pass smaller "
            "candidates"
        )
    if sweep_bwd and mode != "grad":
        print(
            "autotune: --sweep-bwd only applies to mode='grad' "
            "(forward runs no backward kernel); ignoring it",
            file=sys.stderr,
        )
    for bq, bk in usable:
        ms = _time_candidate(
            q, k, v, causal=causal, impl=impl, iters=iters,
            block_q=bq, block_k=bk, block_q_bwd=None, block_k_bwd=None,
            mode=mode,
        )
        records.append(TuneRecord(bq, bk, None, None, ms, mode))
        print(
            f"  {bq}/{bk}: {ms:.3f} ms/call", file=sys.stderr
        )
    records.sort(key=lambda r: r.ms_per_call)

    if sweep_bwd and mode == "grad" and records:
        best = records[0]
        for bq, bk in usable:
            if (bq, bk) == (best.block_q, best.block_k):
                continue  # already measured as the shared-tiling row
            ms = _time_candidate(
                q, k, v, causal=causal, impl=impl, iters=iters,
                block_q=best.block_q, block_k=best.block_k,
                block_q_bwd=bq, block_k_bwd=bk, mode=mode,
            )
            records.append(
                TuneRecord(best.block_q, best.block_k, bq, bk, ms, mode)
            )
            print(
                f"  fwd {best.block_q}/{best.block_k} bwd {bq}/{bk}: "
                f"{ms:.3f} ms/call",
                file=sys.stderr,
            )
        records.sort(key=lambda r: r.ms_per_call)
    return records


def to_markdown(
    records: Sequence[TuneRecord], *, seq_len: int, batch: int,
    n_heads: int, kv_heads: int, head_dim: int, device_kind: str,
) -> str:
    lines = [
        f"# Flash-attention autotune -- {device_kind}, "
        f"B{batch} S{seq_len} H{n_heads}/{kv_heads} D{head_dim} "
        f"({records[0].mode})",
        "",
        "| blocks (q/k) | ms/call | vs best |",
        "|---|---|---|",
    ]
    best = records[0].ms_per_call
    for r in records:
        lines.append(
            f"| {r.blocks()} | {r.ms_per_call:.3f} | "
            f"{r.ms_per_call / best:.3f}x |"
        )
    lines += [
        "",
        f"Best: **{records[0].blocks()}** at "
        f"{records[0].ms_per_call:.3f} ms/call.",
        "",
    ]
    return "\n".join(lines)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--seq-len", type=int, default=2048)
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--kv-heads", type=int, default=None)
    p.add_argument("--head-dim", type=int, default=128)
    p.add_argument("--mode", choices=("fwd", "grad"), default="grad")
    p.add_argument("--sweep-bwd", action="store_true",
                   help="also sweep backward-only tilings with the "
                   "forward pinned to its best candidate")
    p.add_argument("--iters", type=int, default=64)
    p.add_argument("--out", type=str, default=None,
                   help="also write the markdown table to this path")
    args = p.parse_args(argv)

    records = autotune(
        seq_len=args.seq_len, batch=args.batch, n_heads=args.heads,
        kv_heads=args.kv_heads, head_dim=args.head_dim,
        mode=args.mode, sweep_bwd=args.sweep_bwd, iters=args.iters,
    )
    md = to_markdown(
        records, seq_len=args.seq_len, batch=args.batch,
        n_heads=args.heads, kv_heads=args.kv_heads or args.heads,
        head_dim=args.head_dim,
        device_kind=jax.local_devices()[0].device_kind,
    )
    print(md)
    if args.out:
        with open(args.out, "w") as f:
            f.write(md)
    return 0


if __name__ == "__main__":
    sys.exit(main())
