"""Speculative decoding + seeded sampling on the paged serving engine.

The decode loop is latency-bound, not FLOP-bound: one full target
forward per emitted token leaves the MXUs idle between tiny matmuls.
Speculative decoding (Leviathan et al., arXiv 2211.17192) recovers
that slack by *drafting* ``k`` cheap candidate tokens per slot and
*verifying* all ``k + 1`` positions in ONE batched target forward over
the paged cache -- accepted drafts commit, the first rejection is
corrected by a sample from the residual distribution, and the target
distribution is provably preserved (greedy streams are byte-exact,
which the tests/test_serve.py oracle pins). Two draft sources:

* **draft model** (``mode="draft"``) -- a small llama with its own
  mirrored paged KV pool drafts ``k`` tokens per slot in one compiled
  program (``k`` unrolled sampled decode steps);
* **prompt lookup** (``mode="ngram"``) -- self-speculation: the most
  recent earlier occurrence of the request's trailing n-gram in its
  OWN token history proposes the tokens that followed it (arXiv
  2304.04487's prompt-lookup idea). No draft checkpoint needed, so
  every deployment gets some win -- repetitive continuations (code,
  quoting, the cycles greedy decode falls into) accept at high rates.

Everything rides the repo's executable-table discipline: the verify
step's block tables, draft tokens, seeds and temperatures are all
*data*, so the zero-steady-state-recompile guarantee survives -- the
compile counter is pinned across accept/reject churn.

**Seeded sampling.** Temperature/top-p sampling uses per-request
seeds, and every random draw's key folds in ``(request seed, absolute
position, stream)`` -- never the slot index, the batch composition, or
a step counter -- so a request replays the same token stream no matter
what shares its batch or which slot it lands in after an eviction
(the determinism the loadgen virtual-clock harness stakes
byte-identical summaries on). Streams: 0 = the emitted-token draw
(prefill first token, verify bonus/residual), 1 = the draft model's
own draw, 2 = the acceptance uniform. Greedy (``temperature == 0``)
makes every draw a one-hot categorical -- deterministic, and exactly
``argmax``, which is why speculation can change *latency only*, never
the greedy token stream.

**Page accounting.** Admission already reserves
``ceil((prompt + max_new) / block_size)`` pages, and a verify step
writes at most positions ``pos .. pos + n_valid`` where
``n_valid <= remaining - 1`` -- every speculative write lands inside
the admission-time reservation, so accept/reject churn moves ZERO
pages through the allocator (rejected positions are masked by the
per-slot length rule and overwritten by the next verify before they
ever become readable). The draft pool mirrors the target's
admissions one-for-one; ``checks/fit.py --spec-draft`` budgets its
params + pages so an oversized draft fails the fit report instead of
OOMing at bring-up.
"""
from __future__ import annotations

import dataclasses
import time
import zlib
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpu_hpc.models import hybrid_ssm_moe, latent_moe, llama2, sparse_moe
from tpu_hpc.obs import get_bus, get_registry, span
from tpu_hpc.serve.decoder import (
    _embed,
    _logits_head,
    _rope_tables,
    decoder_layers,
)
from tpu_hpc.serve.paging import (
    PagedAttention,
    PagedEngine,
    make_chunk_logits_fn,
)

SPEC_MODES = ("draft", "ngram")

# Key streams: one per independent random decision at a position.
_STREAM_EMIT = 0    # the emitted-token draw (bonus/residual/prefill)
_STREAM_DRAFT = 1   # the draft model's own sampling draw
_STREAM_ACCEPT = 2  # the acceptance uniform


@dataclasses.dataclass(frozen=True)
class SpecConfig:
    """Static speculative-decoding shape.

    ``mode``: ``"draft"`` (draft-model path; needs draft params) or
    ``"ngram"`` (prompt-lookup self-speculation -- no extra model).
    ``k``: drafted tokens per verify step -- the verify program's
    fixed width (``k + 1`` query rows per slot). ``ngram``: longest
    trailing n-gram the prompt-lookup matcher tries (it falls back to
    shorter grams down to 1)."""

    mode: str = "ngram"
    k: int = 4
    ngram: int = 2

    def __post_init__(self):
        if self.mode not in SPEC_MODES:
            raise ValueError(
                f"unknown spec mode {self.mode!r} "
                f"(known: {', '.join(SPEC_MODES)})"
            )
        if self.k < 1:
            raise ValueError(f"spec k must be >= 1, got {self.k}")
        if self.ngram < 1:
            raise ValueError(
                f"ngram order must be >= 1, got {self.ngram}"
            )


def default_draft_config(
    cfg: llama2.LlamaConfig,
) -> llama2.LlamaConfig:
    """A development draft architecture for ``mode="draft"`` with no
    checkpoint: the target's config at half depth. Real deployments
    restore a trained draft (``--spec-draft-ckpt``) -- a random-init
    draft accepts ~1/vocab of its guesses and only proves wiring."""
    return dataclasses.replace(
        cfg, n_layers=max(1, cfg.n_layers // 2)
    )


def derive_request_seed(rid: str, seed: Optional[int] = None) -> int:
    """The per-request sampling seed: the explicit one when given,
    else a stable hash of the request id -- NEVER anything positional
    (slot, batch index, step), so replay determinism survives slot
    reassignment and batch-composition changes."""
    if seed is not None:
        return int(seed) & 0x7FFFFFFF
    return zlib.crc32(rid.encode()) & 0x7FFFFFFF


# ---------------------------------------------------------------------
# Host-side prompt lookup (the self-speculative draft source)
# ---------------------------------------------------------------------


def ngram_propose(
    history: Sequence[int], k: int, max_n: int = 2
) -> List[int]:
    """Prompt-lookup drafting: find the most recent EARLIER occurrence
    of the history's trailing ``n``-gram (longest first, down to 1)
    and propose the ``k`` tokens that followed it. Empty when nothing
    matches -- the verify step then degenerates to a plain (sampled)
    single-token decode, costing nothing extra."""
    h = list(history)
    if len(h) < 2:
        return []
    for n in range(min(max_n, len(h) - 1), 0, -1):
        tail = h[-n:]
        # Scan right-to-left for the most recent prior occurrence:
        # recent context predicts the continuation best.
        for start in range(len(h) - n - 1, -1, -1):
            if h[start:start + n] == tail:
                follow = h[start + n:start + n + k]
                if follow:
                    return [int(t) for t in follow]
    return []


class NgramIndex:
    """Incremental prompt-lookup state for ONE request, proposing
    byte-identically to ``ngram_propose`` over the same history.

    ``ngram_propose``'s rescan is O(history) per call, which on the
    decode hot path is O(T) per slot per tick -- O(T^2) host work per
    request over a generation, eroding exactly the ITL win
    speculation buys. The batcher keeps one index per decoding
    request instead: ``append`` is O(max_n) per committed token and
    ``propose`` is O(max_n + k), because the map remembers each
    gram's two most recent start positions -- the trailing gram's own
    occurrence is always the most recent, so the *prior* one (what
    the rescan finds) sits in the second slot."""

    def __init__(
        self, history: Sequence[int] = (), max_n: int = 2
    ) -> None:
        self.max_n = max_n
        self.history: List[int] = []
        self._starts: Dict[
            Tuple[int, ...], Tuple[int, Optional[int]]
        ] = {}
        for tok in history:
            self.append(tok)

    def append(self, tok: int) -> None:
        h = self.history
        h.append(int(tok))
        end = len(h)
        for n in range(1, min(self.max_n, end) + 1):
            g = tuple(h[end - n:end])
            prev = self._starts.get(g)
            self._starts[g] = (
                end - n, prev[0] if prev is not None else None
            )

    def propose(self, k: int) -> List[int]:
        h = self.history
        if len(h) < 2:
            return []
        for n in range(min(self.max_n, len(h) - 1), 0, -1):
            entry = self._starts.get(tuple(h[-n:]))
            # entry[0] is the trailing gram itself; the most recent
            # PRIOR occurrence is the second slot.
            start = None if entry is None else entry[1]
            if start is None:
                continue
            return h[start + n:start + n + k]
        return []


# ---------------------------------------------------------------------
# The shared sampling head: ONE token rule for draft and target
# ---------------------------------------------------------------------


def sampling_probs(
    logits: jax.Array, temp: jax.Array, top_p: jax.Array
) -> jax.Array:
    """``[slots, n, vocab]`` logits + per-slot scalar temperature /
    top-p -> the per-row token distributions BOTH the draft and the
    target sample from (rejection sampling is lossless only against a
    shared rule). ``temp == 0`` selects the greedy one-hot -- exact
    {0, 1} floats, so the downstream categorical is exactly argmax."""
    lf = logits.astype(jnp.float32)
    greedy = jax.nn.one_hot(
        jnp.argmax(lf, axis=-1), lf.shape[-1], dtype=jnp.float32
    )
    t = temp.astype(jnp.float32)[:, None, None]
    safe_t = jnp.where(t > 0, t, 1.0)
    probs = jax.nn.softmax(lf / safe_t, axis=-1)
    # Nucleus filter: keep the smallest prefix of the sorted
    # distribution whose mass reaches top_p (the crossing token
    # included; the top-1 token always survives).
    order = jnp.argsort(-probs, axis=-1)
    sorted_p = jnp.take_along_axis(probs, order, axis=-1)
    csum = jnp.cumsum(sorted_p, axis=-1)
    keep_sorted = (csum - sorted_p) < (
        top_p.astype(jnp.float32)[:, None, None]
    )
    keep = jnp.take_along_axis(
        keep_sorted, jnp.argsort(order, axis=-1), axis=-1
    )
    filtered = jnp.where(keep, probs, 0.0)
    filtered = filtered / jnp.sum(filtered, axis=-1, keepdims=True)
    return jnp.where(t > 0, filtered, greedy)


def _position_keys(
    seeds: jax.Array, positions: jax.Array, stream: int
) -> jax.Array:
    """Per-element PRNG keys from (request seed, absolute position,
    stream) -- the whole determinism contract in one fold chain."""
    base = jax.random.key(0)

    def one(s, p):
        k = jax.random.fold_in(base, s)
        k = jax.random.fold_in(k, p)
        return jax.random.fold_in(k, stream)

    return jax.vmap(one)(seeds.ravel(), positions.ravel())


def _categorical(keys: jax.Array, probs: jax.Array) -> jax.Array:
    """Per-row categorical draw; a one-hot row (greedy) draws its hot
    index deterministically (every other logit is -inf)."""
    return jax.vmap(
        lambda k, p: jax.random.categorical(k, jnp.log(p))
    )(keys, probs)


def sample_token(
    logits: jax.Array,
    seed: jax.Array,
    position: jax.Array,
    temp: jax.Array,
    top_p: jax.Array,
) -> jax.Array:
    """One token from one ``[vocab]`` logits row under the shared
    rule -- the seeded first-token head the spec prefill program uses
    (stream 0 at the producing row's absolute position)."""
    p = sampling_probs(
        logits[None, None, :], temp[None], top_p[None]
    )[0, 0]
    key = _position_keys(seed[None], position[None], _STREAM_EMIT)[0]
    return jax.random.categorical(key, jnp.log(p)).astype(jnp.int32)


# ---------------------------------------------------------------------
# Compiled programs
# ---------------------------------------------------------------------


def _rope_for(cfg, positions: jax.Array):
    """Per-row RoPE tables for a ``[slots, n]`` position matrix."""
    cos, sin = _rope_tables(cfg, 1, positions.reshape(-1))
    shape = (*positions.shape, cfg.head_dim // 2)
    return cos.reshape(shape), sin.reshape(shape)


def make_spec_draft_fn(
    cfg: llama2.LlamaConfig,
    k: int,
    block_size: int,
    max_blocks: int,
    table_width: int,
    scratch_block: int = 0,
):
    """The draft program: ``k`` sampled decode steps of the draft
    model, unrolled into ONE executable over every slot at once.

    ``(params, ks, vs, tokens [slots], pos [slots],
    tables [slots, table_width], active [slots], n_valid [slots],
    seeds [slots], temps [slots], top_ps [slots])`` ->
    ``(ks, vs, draft_tokens [slots, k], draft_probs [slots, k,
    vocab])``: step ``j`` embeds the previous token at position
    ``pos + j``, writes its K/V into the draft pool (scratch-
    redirected for inactive slots and beyond ``n_valid`` -- drafts
    past the emission cap are computed but never land), and SAMPLES
    the next candidate with the shared rule under the per-request
    seeded key (stream 1 at the producing row's position). The full
    per-step distributions ride out for the verify step's rejection
    test -- device-to-device, never fetched."""
    cache_cap = max_blocks * block_size
    attention = PagedAttention(cfg, block_size, max_blocks)

    def draft(params, ks, vs, tokens, pos, tables, active, n_valid,
              seeds, temps, top_ps):
        scope = jax.named_scope
        pool = attention.on(ks, vs)
        slots = tokens.shape[0]
        rows = jnp.arange(slots)
        col = jnp.arange(cache_cap)
        pool.view(tables)
        cur = tokens
        out_toks = []
        out_probs = []
        for j in range(k):
            pj = pos + j
            with scope("embed"):
                x = _embed(params, cur[:, None], cfg)
            cos, sin = _rope_for(cfg, pj[:, None])
            mask = (
                col[None, :] <= pj[:, None]
            )[:, None, None, None, :]
            write_ok = (active > 0) & (j < n_valid)
            pb = jnp.where(
                write_ok, tables[rows, pj // block_size],
                scratch_block,
            )
            pool.rows(pb, pj % block_size, mask)
            x, _ = decoder_layers(params, cfg, x, cos, sin, pool)
            with scope("head"):
                logits = _logits_head(x, params, cfg)
                p = sampling_probs(logits, temps, top_ps)[:, 0]
                keys = _position_keys(seeds, pj, _STREAM_DRAFT)
                tok = _categorical(keys, p).astype(jnp.int32)
            out_toks.append(tok)
            out_probs.append(p)
            cur = tok
        return (
            *pool.state()[:2],
            jnp.stack(out_toks, axis=1),
            jnp.stack(out_probs, axis=1),
        )

    return draft


def make_spec_verify_fn(
    cfg: llama2.LlamaConfig,
    k: int,
    block_size: int,
    max_blocks: int,
    table_width: int,
    onehot_q: bool,
    scratch_block: int = 0,
):
    """The verify program: ``k + 1`` query rows per slot through the
    target in ONE forward over the paged cache, plus the whole
    rejection-sampling decision on device.

    ``(params, ks, vs, tokens [slots, k+1], pos [slots], tables,
    active, n_valid, [draft_probs [slots, k, vocab],] seeds, temps,
    top_ps)`` -> ``(ks, vs, out_tokens [slots, k+1], n_accepted
    [slots])``. Row ``j`` carries token ``j`` of ``[last_committed,
    d_1 .. d_k]`` at absolute position ``pos + j``; its K/V is
    written into page ``tables[s, (pos+j)//bs]`` (scratch-redirected
    when inactive or ``j > n_valid``) BEFORE the gathered block-table
    attention, so each row attends to the cache AND to the candidate
    rows before it under the causal mask ``col <= pos + j``.

    Acceptance per Leviathan et al.: draft ``d_{j+1}`` (drawn from
    ``q_j``) accepts iff ``u_j * q_j(d) < p_j(d)`` with ``u_j`` from
    the (seed, position, stream-2) key; the emitting row is ALWAYS
    index ``n_accepted`` -- a rejection resamples the residual
    ``norm(max(p - q, 0))`` there, a clean sweep samples the bonus
    from ``p`` directly (``q`` zeroed makes the residual collapse to
    ``p`` -- one code path). With ``onehot_q=True`` (prompt-lookup
    drafts) ``q`` is the one-hot of the proposed token, built
    in-program -- no draft-probability operand to ship.

    Rejected rows' K/V writes land at positions the per-slot length
    rule keeps unreadable until the NEXT verify step overwrites them
    (emission advances ``pos`` by at most ``n_valid + 1``, and the
    next step's rows re-cover every not-yet-committed position before
    any mask can expose it) -- the rollback is positional, so the
    allocator sees zero traffic at accept/reject boundaries.
    """
    cache_cap = max_blocks * block_size
    n_rows = k + 1
    attention = PagedAttention(cfg, block_size, max_blocks)

    def verify(params, ks, vs, tokens, pos, tables, active, n_valid,
               *rest):
        scope = jax.named_scope
        pool = attention.on(ks, vs)
        if onehot_q:
            (seeds, temps, top_ps) = rest
            draft_probs = None
        else:
            (draft_probs, seeds, temps, top_ps) = rest
        slots = tokens.shape[0]
        qpos = pos[:, None] + jnp.arange(n_rows)[None, :]
        with scope("embed"):
            x = _embed(params, tokens, cfg)  # [slots, k+1, dim]
        cos, sin = _rope_for(cfg, qpos)
        col = jnp.arange(cache_cap)
        mask = (
            col[None, None, :] <= qpos[:, :, None]
        )[:, None, None, :, :]
        write_ok = (
            (active[:, None] > 0)
            & (jnp.arange(n_rows)[None, :] <= n_valid[:, None])
        )
        pb = jnp.where(
            write_ok,
            jnp.take_along_axis(tables, qpos // block_size, axis=1),
            scratch_block,
        )
        pool.rows(pb, qpos % block_size, mask)
        pool.view(tables)
        x, _ = decoder_layers(params, cfg, x, cos, sin, pool)
        with scope("head"):
            logits = _logits_head(x, params, cfg)  # [slots, k+1, vocab]
            p = sampling_probs(logits, temps, top_ps)
            drafts = tokens[:, 1:]  # [slots, k]: d_1 .. d_k
            if onehot_q:
                q_probs = jax.nn.one_hot(
                    drafts, cfg.vocab_size, dtype=jnp.float32
                )
            else:
                q_probs = draft_probs.astype(jnp.float32)
            p_d = jnp.take_along_axis(
                p[:, :k], drafts[..., None], axis=-1
            )[..., 0]
            q_d = jnp.take_along_axis(
                q_probs, drafts[..., None], axis=-1
            )[..., 0]
            u_keys = _position_keys(
                jnp.broadcast_to(seeds[:, None], (slots, k)),
                qpos[:, :k], _STREAM_ACCEPT,
            )
            u = jax.vmap(jax.random.uniform)(u_keys).reshape(slots, k)
            valid = jnp.arange(k)[None, :] < n_valid[:, None]
            accept = (u * q_d < p_d) & valid
            n_acc = jnp.sum(
                jnp.cumprod(accept.astype(jnp.int32), axis=1), axis=1
            )

            # The emitting row is n_acc in both outcomes: residual
            # resample on a rejection, bonus draw on a clean sweep (q
            # zeroed -> residual == p).
            p_row = jnp.take_along_axis(
                p, n_acc[:, None, None], axis=1
            )[:, 0]
            q_row = jnp.take_along_axis(
                jnp.concatenate(
                    [q_probs,
                     jnp.zeros((slots, 1, cfg.vocab_size), jnp.float32)],
                    axis=1,
                ),
                n_acc[:, None, None], axis=1,
            )[:, 0]
            q_row = jnp.where(
                (n_acc == n_valid)[:, None], 0.0, q_row
            )
            resid = jnp.maximum(p_row - q_row, 0.0)
            rsum = jnp.sum(resid, axis=-1, keepdims=True)
            resid = jnp.where(rsum > 0, resid / rsum, p_row)
            emit_keys = _position_keys(
                seeds, pos + n_acc, _STREAM_EMIT
            )
            emit = _categorical(emit_keys, resid).astype(jnp.int32)
            out = jnp.concatenate(
                [drafts, jnp.zeros((slots, 1), jnp.int32)], axis=1
            )
            out = jnp.where(
                jnp.arange(n_rows)[None, :] == n_acc[:, None],
                emit[:, None], out,
            )
        return *pool.state()[:2], out, n_acc.astype(jnp.int32)

    return verify


# ---------------------------------------------------------------------
# The runner
# ---------------------------------------------------------------------


class SpecRunner:
    """Owns the speculative-decode state attached to one PagedEngine:
    the draft engine (``mode="draft"``), the program builders the
    engines' executable tables dispatch to, the per-slot proposal
    bookkeeping, and the acceptance/draft-cost stats the summary and
    the ``obs`` registry read. Construct via
    :func:`attach_spec` -- it wires the engine hooks."""

    def __init__(
        self,
        engine,
        cfg: SpecConfig,
        draft_params: Any = None,
        draft_cfg: Optional[llama2.LlamaConfig] = None,
    ):
        if not getattr(engine, "is_paged", False) or not isinstance(
            engine, PagedEngine
        ):
            raise ValueError(
                "speculative decoding rides the paged engine "
                "(serve/paging.py); slab and disagg engines are not "
                "supported"
            )
        for model_cfg in (engine.cfg, draft_cfg):
            sparse_moe.refuse(
                model_cfg, "speculative decoding (serve/spec.py)",
                "its draft, verify and mirrored-pool programs carry "
                "keys and values only, and a page now has three arrays",
            )
            latent_moe.refuse(
                model_cfg, "speculative decoding (serve/spec.py)",
                "its draft, verify and mirrored-pool programs write and "
                "read per-head keys and values, not a latent row (and "
                "the model's multi-token-prediction module is no "
                "proposer here)",
            )
            hybrid_ssm_moe.refuse(
                model_cfg, "speculative decoding (serve/spec.py)",
                "a rejected draft would have to roll the recurrent "
                "state back, and its programs keep none",
            )
        if cfg.k > max(engine.serve_cfg.prefill_buckets):
            raise ValueError(
                f"spec k {cfg.k} exceeds the largest prefill bucket "
                f"{max(engine.serve_cfg.prefill_buckets)} (the verify "
                "write window must fit the table's scratch slack)"
            )
        if getattr(engine.paged, "kv_quant", "none") != "none":
            raise ValueError(
                "speculative decoding on a quantized KV pool is not "
                "supported: the verify window's multi-token rewrites "
                "would requantize shared pages per candidate (and the "
                "mirrored draft pool would need its own scale "
                "arrays); serve int8 pools with plain greedy decode"
            )
        if engine._execs:
            # Attaching to an already-warmed engine would leave the
            # spec programs to lazy-compile mid-traffic -- a latency
            # spike and a nonzero recompile count with no error.
            # Fail fast like every other misuse guard here.
            raise ValueError(
                "attach_spec must run BEFORE engine.warmup(): the "
                "executable table already holds compiled programs"
            )
        self.engine = engine
        self.cfg = cfg
        self.draft = None
        if cfg.mode == "draft":
            if draft_params is None or draft_cfg is None:
                raise ValueError(
                    "mode='draft' needs draft_params and draft_cfg "
                    "(restore a draft checkpoint, or use "
                    "default_draft_config for a dev-mode random init)"
                )
            if draft_cfg.vocab_size != engine.cfg.vocab_size:
                raise ValueError(
                    f"draft vocab {draft_cfg.vocab_size} != target "
                    f"vocab {engine.cfg.vocab_size} -- token ids must "
                    "mean the same thing to both models"
                )
            # The draft mirrors the target pool's shape: same pages,
            # same admissions, so reservation arithmetic is identical
            # on both sides (its pages are smaller in bytes -- fewer
            # layers/heads -- which checks/fit.py budgets).
            self.draft = PagedEngine(
                draft_params, draft_cfg, engine.serve_cfg,
                engine.mesh, engine.paged,
            )
            self.draft.gauge_suffix = "_draft"
            self.draft._spec_builders = {
                "spec_draft": self._build_draft_program,
            }
        engine.spec = self
        engine._spec_builders = {
            "spec_verify": self._build_verify_program,
            "spec_prefill": self._build_spec_prefill_program,
        }
        self.stats = {
            "verify_steps": 0, "drafted": 0, "accepted": 0,
            "rejected": 0, "emitted": 0,
        }
        self.draft_time_s = 0.0
        # HELP once at construction -- the per-verify-step stats path
        # must not re-describe under the registry lock at decode
        # cadence (the ServeMeter.__init__ discipline).
        reg = get_registry()
        reg.describe(
            "serve_spec_draft_s",
            "Draft-side forward (k-step burst or draft prefill), "
            "dispatch to handoff (s)",
        )
        reg.describe(
            "serve_spec_verify_s",
            "Batched (k+1)-position target verify forward (s)",
        )
        reg.describe("serve_spec_drafted_total",
                     "Speculative draft tokens proposed")
        reg.describe("serve_spec_accepted_total",
                     "Speculative draft tokens accepted by the "
                     "target verify forward")

    # -- program builders (dispatched from the engines' _build) --------
    def _abstracts(self, engine):
        cache = engine._cache_abstract()
        params_abs = engine._params_abstract()
        slots = engine.serve_cfg.slots
        rep = engine._rep

        def vec(shape, dtype=jnp.int32):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

        return cache, params_abs, slots, vec

    def _build_verify_program(self, key):
        del key
        engine = self.engine
        cache, params_abs, slots, vec = self._abstracts(engine)
        k = self.cfg.k
        onehot = self.cfg.mode == "ngram"
        fn = make_spec_verify_fn(
            engine.cfg, k, engine.paged.block_size,
            engine.max_blocks_per_seq, engine.table_width,
            onehot_q=onehot,
        )
        args = [
            params_abs, cache, cache,
            vec((slots, k + 1)),              # tokens
            vec((slots,)),                    # pos
            vec((slots, engine.table_width)),  # tables
            vec((slots,)),                    # active
            vec((slots,)),                    # n_valid
        ]
        if not onehot:
            args.append(
                vec((slots, k, engine.cfg.vocab_size), jnp.float32)
            )
        args += [
            vec((slots,)),                     # seeds
            vec((slots,), jnp.float32),        # temps
            vec((slots,), jnp.float32),        # top_ps
        ]
        jitted = jax.jit(
            fn,
            donate_argnums=(1, 2),
            out_shardings=(
                engine._cache_sharding, engine._cache_sharding,
                engine._rep, engine._rep,
            ),
        )
        return jitted.lower(*args).compile()

    def _build_draft_program(self, key):
        del key
        draft = self.draft
        cache, params_abs, slots, vec = self._abstracts(draft)
        k = self.cfg.k
        fn = make_spec_draft_fn(
            draft.cfg, k, draft.paged.block_size,
            draft.max_blocks_per_seq, draft.table_width,
        )
        args = [
            params_abs, cache, cache,
            vec((slots,)),                     # tokens
            vec((slots,)),                     # pos
            vec((slots, draft.table_width)),   # tables
            vec((slots,)),                     # active
            vec((slots,)),                     # n_valid
            vec((slots,)),                     # seeds
            vec((slots,), jnp.float32),        # temps
            vec((slots,), jnp.float32),        # top_ps
        ]
        jitted = jax.jit(
            fn,
            donate_argnums=(1, 2),
            out_shardings=(
                draft._cache_sharding, draft._cache_sharding,
                draft._rep, draft._rep,
            ),
        )
        return jitted.lower(*args).compile()

    def _build_spec_prefill_program(self, key):
        """The sampled chunk-prefill variant: the same layer loop as
        the greedy program (paging.make_chunk_logits_fn -- one body,
        two token rules) with the seeded temperature/top-p head on
        the final logits row. The key position is the producing row's
        absolute position ``start + true_len - 1``, matching the
        verify program's convention, so the first generated token of
        a sampled request is part of the same deterministic stream."""
        engine = self.engine
        bucket = key[1]
        cache, params_abs, slots, vec = self._abstracts(engine)
        inner = make_chunk_logits_fn(
            engine.cfg, bucket, engine.paged.block_size,
            engine.max_blocks_per_seq, engine.table_width,
            kernel=engine.paged.kernel, mesh=engine.mesh,
        )

        def spec_prefill(params, ks, vs, tokens, start, true_len,
                         table, seed, temp, top_p):
            ks, vs, logits = inner(
                params, ks, vs, tokens, start, true_len, table
            )
            tok = sample_token(
                logits, seed, start + true_len - 1, temp, top_p
            )
            return ks, vs, tok

        scalar = vec(())
        args = (
            params_abs, cache, cache,
            vec((1, bucket)), scalar, scalar,
            vec((engine.table_width,)),
            scalar, vec((), jnp.float32), vec((), jnp.float32),
        )
        jitted = jax.jit(
            spec_prefill,
            donate_argnums=(1, 2),
            out_shardings=(
                engine._cache_sharding, engine._cache_sharding,
                engine._rep,
            ),
        )
        return jitted.lower(*args).compile()

    # -- warmup / compile accounting -----------------------------------
    def warmup_draft(self) -> None:
        """Compile the draft side's steady-state programs: one greedy
        chunk prefill per bucket (its tokens are discarded -- only the
        K/V matter) + the k-step draft program."""
        if self.draft is None:
            return
        for b in self.draft.serve_cfg.prefill_buckets:
            self.draft._get_exec(("prefill", b))
        self.draft._get_exec(("spec_draft",))

    @property
    def draft_compile_count(self) -> int:
        return self.draft.compile_count if self.draft is not None else 0

    # -- engine lifecycle mirroring ------------------------------------
    def on_admit(self, slot: int, prompt, max_new: int) -> None:
        """Mirror a target admission into the draft pool. The pools
        are shaped identically and see identical operation sequences,
        so a draft-side budget error means real skew -- roll the
        TARGET admission back and re-raise so the request re-queues
        atomically."""
        if self.draft is None:
            return
        try:
            self.draft.admit(slot, prompt, max_new)
        except Exception:
            self.engine.release(slot)
            raise

    def on_prefill_done(self, slot: int) -> None:
        """The target finished a request's prompt -- run the draft's
        whole chunk plan now (the draft is small; its prefill cost is
        the price of drafting from real context). Wall time lands in
        ``draft_time_s`` -- the draft-cost metric."""
        if self.draft is None:
            return
        t0 = time.perf_counter()
        with span("spec_draft_prefill", hist="serve_spec_draft_s"):
            st = self.draft.slot_state(slot)
            while st.next_chunk < len(st.plan):
                self.draft.prefill_step(slot)
        self.draft_time_s += time.perf_counter() - t0

    def on_release(self, slot: int) -> None:
        if self.draft is not None:
            self.draft.release(slot)

    # -- the decode step -----------------------------------------------
    def decode(
        self,
        tokens: Sequence[int],
        positions: Sequence[int],
        active: Sequence[bool],
        n_valid: Sequence[int],
        seeds: Sequence[int],
        temps: Sequence[float],
        top_ps: Sequence[float],
        histories: Optional[Sequence[Sequence[int]]] = None,
        proposals: Optional[Sequence[Sequence[int]]] = None,
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """One speculative decode step for every slot: draft (model or
        prompt-lookup), then ONE batched target verify. Returns
        ``(out_tokens [slots, k+1], n_accepted [slots],
        n_drafted [slots])`` -- slot ``s`` emits
        ``out_tokens[s, :n_accepted[s] + 1]`` and actually staked
        ``n_drafted[s]`` draft tokens (prompt lookup can propose
        fewer than the cap). ``n_valid[s]`` caps the drafts that
        participate (the batcher sets ``min(k, remaining - 1)`` so
        emissions never exceed the request's budget -- which is also
        what keeps every speculative write inside the admission-time
        page reservation). ngram mode takes either per-slot
        ``proposals`` (from each request's incremental
        :class:`NgramIndex` -- the batcher's hot path) or raw
        ``histories`` to rescan with :func:`ngram_propose`; the two
        are byte-identical."""
        engine = self.engine
        k = self.cfg.k
        slots = engine.serve_cfg.slots
        pos = np.asarray(positions, np.int32)
        act = np.asarray(active, bool)
        nv = np.asarray(n_valid, np.int32)
        seeds_a = np.asarray(seeds, np.int32)
        temps_a = np.asarray(temps, np.float32)
        tops_a = np.asarray(top_ps, np.float32)

        # CoW guard over every page the verify writes touch -- and the
        # draft's mirrored window when a draft model runs (its pool
        # shares the same trie/refcount machinery, so a shared draft
        # page would corrupt its co-owner just as silently). By
        # construction the pages are exclusively ours, but the guard
        # rail stays load-bearing (the slab-era discipline).
        guarded = (engine,) if self.draft is None else (
            engine, self.draft,
        )
        for eng in guarded:
            bs = eng.paged.block_size
            for s in range(slots):
                if not act[s]:
                    continue
                for page_idx in range(
                    int(pos[s]) // bs,
                    (int(pos[s]) + int(nv[s])) // bs + 1,
                ):
                    eng._cow_write_target(s, page_idx * bs)

        token_rows = np.zeros((slots, k + 1), np.int32)
        token_rows[:, 0] = np.asarray(tokens, np.int32)
        draft_probs = None
        if self.cfg.mode == "draft":
            d = self.draft
            exec_ = d._get_exec(("spec_draft",))
            t0 = time.perf_counter()
            with span("spec_draft", hist="serve_spec_draft_s"):
                d.ks, d.vs, dtoks, draft_probs = exec_(
                    d.params, d.ks, d.vs,
                    d._rep_arr(token_rows[:, 0]),
                    d._rep_arr(pos),
                    d._tables_device(),
                    d._rep_arr(act.astype(np.int32)),
                    d._rep_arr(nv),
                    d._rep_arr(seeds_a),
                    d._rep_arr(temps_a, jnp.float32),
                    d._rep_arr(tops_a, jnp.float32),
                )
                dtoks_np = np.asarray(dtoks)
            self.draft_time_s += time.perf_counter() - t0
            token_rows[:, 1:] = dtoks_np
        else:
            # Prompt lookup over each request's OWN history; a short
            # (or empty) proposal shrinks that slot's n_valid -- the
            # verify degenerates gracefully to plain sampled decode.
            assert histories is not None or proposals is not None
            for s in range(slots):
                if not act[s]:
                    nv[s] = 0
                    continue
                if proposals is not None:
                    prop = list(proposals[s])
                else:
                    prop = ngram_propose(
                        histories[s], k, max_n=self.cfg.ngram
                    )
                nv[s] = min(int(nv[s]), len(prop))
                token_rows[s, 1:1 + len(prop)] = prop[:k]

        exec_ = engine._get_exec(("spec_verify",))
        args = [
            engine.params, engine.ks, engine.vs,
            engine._rep_arr(token_rows),
            engine._rep_arr(pos),
            engine._tables_device(),
            engine._rep_arr(act.astype(np.int32)),
            engine._rep_arr(nv),
        ]
        if draft_probs is not None:
            args.append(draft_probs)
        args += [
            engine._rep_arr(seeds_a),
            engine._rep_arr(temps_a, jnp.float32),
            engine._rep_arr(tops_a, jnp.float32),
        ]
        with span("spec_verify", hist="serve_spec_verify_s"):
            engine.ks, engine.vs, out, n_acc = exec_(*args)
            out_np = np.asarray(out)
            n_acc_np = np.asarray(n_acc)

        drafted = int(nv[act].sum()) if act.any() else 0
        accepted = int(n_acc_np[act].sum()) if act.any() else 0
        emitted = int(act.sum()) + accepted
        st = self.stats
        st["verify_steps"] += 1
        st["drafted"] += drafted
        st["accepted"] += accepted
        st["rejected"] += drafted - accepted
        st["emitted"] += emitted
        reg = get_registry()
        reg.inc("serve_spec_drafted_total", drafted)
        reg.inc("serve_spec_accepted_total", accepted)
        # Ring-only per-step evidence (the lg_token / kv_block
        # discipline): per-tick cadence is flight-recorder forensics.
        get_bus().emit(
            "spec_step", accepted=accepted, drafted=drafted,
        )
        return out_np, n_acc_np, nv

    # -- reporting ------------------------------------------------------
    def spec_summary(self) -> Dict[str, Any]:
        """The serve-summary block describing this runner: mode/k are
        identity, acceptance_rate and draft_ms are the two judged
        signals (regress: higher- / lower-is-better)."""
        st = self.stats
        return {
            "spec_mode": self.cfg.mode,
            "spec_k": self.cfg.k,
            "verify_steps": st["verify_steps"],
            "drafted": st["drafted"],
            "accepted": st["accepted"],
            "rejected": st["rejected"],
            "acceptance_rate": (
                st["accepted"] / st["drafted"] if st["drafted"]
                else 0.0
            ),
            "draft_ms": round(self.draft_time_s * 1e3, 3),
        }


def attach_spec(
    engine,
    cfg: SpecConfig,
    draft_params: Any = None,
    draft_cfg: Optional[llama2.LlamaConfig] = None,
) -> SpecRunner:
    """Attach speculative decoding to a PagedEngine (before
    ``warmup()``). Returns the runner; the engine's ``spec``
    attribute, warmup, prefill routing and admission mirroring all
    key off it."""
    return SpecRunner(
        engine, cfg, draft_params=draft_params, draft_cfg=draft_cfg
    )
