"""The serving engine: prefill + single-token decode over a KV cache.

Training in this repo is one jitted step over a sharded state; serving
gets the same treatment. The engine owns ONE preallocated, mesh-sharded
KV cache (``[layers, slots, seq, kv_heads, head_dim]`` for K and V:
batch slots shard over ``data``, KV heads over ``model`` -- the same
Megatron head split ``parallel/tp.py`` gives the training step), and
exactly two program shapes run in steady state:

  * **prefill** -- full causal attention over one request's prompt,
    padded up to a bucket length, writing the prompt's K/V into that
    request's slot rows and returning the first greedy token;
  * **decode** -- ONE token for EVERY slot: append each token's K/V at
    its slot's position counter, attend over the cache (length-masked
    per slot), return the next greedy token per slot.

TPU idiom: both are AOT-lowered and XLA-compiled at engine warmup for
a small, fixed set of padded shapes (one prefill program per bucket,
one decode program), so steady-state serving never recompiles -- the
fixed-shape discipline MPMD pipeline stages use (arXiv:2412.14374),
applied to inference. The engine dispatches ONLY from its executable
table; any miss is counted in ``compile_count``, which the recompile
guard in tests/test_serve.py pins to the warmup count.

The model math is serve/decoder.py's one layer loop; this module hands
it the attention state of a slab cache (where a prompt's and a token's
K/V rows go, what attention reads back).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_hpc.models import (
    conv_moe, hybrid_ssm_moe, latent_moe, llama2, sparse_moe,
)
from tpu_hpc.obs import get_registry, span
from tpu_hpc.serve.decoder import (
    _embed,
    _grouped_attention,
    _logits_head,
    _rope_tables,
    decoder_layers,
)


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Static serving shape: everything a compiled program depends on.

    ``slots``: fixed decode batch width (continuous batching admits and
    evicts requests at decode-step granularity into these slots; the
    decode program's shape never changes). ``max_seq_len``: KV-cache
    capacity per slot (prompt + generated tokens). ``prefill_buckets``:
    the padded prompt lengths prefill compiles for -- a prompt pads up
    to the smallest bucket that holds it, so N buckets = N prefill
    programs, ever. ``cache_dtype``: KV storage dtype (defaults to the
    model's compute dtype -- storing bf16 halves cache HBM vs fp32 and
    matches what the attention matmuls would cast to anyway).
    """

    slots: int = 8
    max_seq_len: int = 256
    prefill_buckets: Tuple[int, ...] = (64, 128)
    cache_dtype: Any = None

    def __post_init__(self):
        if self.slots < 1:
            raise ValueError(f"slots must be >= 1, got {self.slots}")
        if not self.prefill_buckets:
            raise ValueError("need at least one prefill bucket")
        bad = [b for b in self.prefill_buckets if b > self.max_seq_len]
        if bad:
            raise ValueError(
                f"prefill buckets {bad} exceed the cache capacity "
                f"max_seq_len={self.max_seq_len}"
            )
        object.__setattr__(
            self, "prefill_buckets", tuple(sorted(self.prefill_buckets))
        )

    def bucket_for(self, prompt_len: int) -> int:
        """Smallest compiled bucket holding ``prompt_len`` tokens."""
        for b in self.prefill_buckets:
            if prompt_len <= b:
                return b
        raise ValueError(
            f"prompt of {prompt_len} tokens exceeds the largest "
            f"prefill bucket {self.prefill_buckets[-1]}"
        )


def kv_cache_pspec(mesh: Mesh, slots: int, kv_heads: int) -> P:
    """Cache layout on the serving mesh: slots over ``data``, KV heads
    over ``model`` (mirrors the training layout -- batch on data,
    heads on the TP axis), each only when the axis exists, divides,
    and is wider than 1."""
    names = set(mesh.axis_names)

    def claim(axis: str, extent: int) -> Optional[str]:
        if axis in names and mesh.shape[axis] > 1 \
                and extent % mesh.shape[axis] == 0:
            return axis
        return None

    return P(None, claim("data", slots), None, claim("model", kv_heads),
             None)


def make_prefill_fn(cfg: llama2.LlamaConfig, bucket: int, slots: int):
    """Prefill program for one padded bucket length.

    ``(params, ks, vs, tokens [1, bucket], true_len, slot)`` ->
    ``(ks, vs, next_token)``: full causal attention over the padded
    prompt, the prompt's K/V written into slot ``slot`` rows
    ``[0:bucket)`` (the padded tail is garbage the per-slot length
    mask never reads), greedy token from the logits at row
    ``true_len - 1``. Its attention state reads nothing back: the
    prompt attends over the K/V it has just computed.
    """
    del slots  # shape comes from the cache operand
    scope = jax.named_scope

    def prefill(params, ks, vs, tokens, true_len, slot):
        with scope("embed"):
            x = _embed(params, tokens, cfg)
        cos, sin = _rope_tables(cfg, bucket)
        causal = jnp.tril(jnp.ones((bucket, bucket), dtype=bool))
        mask = causal[None, None, None, :, :]

        def attend(i, h, lp, q, k, v):
            nonlocal ks, vs
            with scope("kv_write"):
                ks = jax.lax.dynamic_update_slice(
                    ks, k.astype(ks.dtype)[None], (i, slot, 0, 0, 0)
                )
                vs = jax.lax.dynamic_update_slice(
                    vs, v.astype(vs.dtype)[None], (i, slot, 0, 0, 0)
                )
            with scope("attention"):
                return _grouped_attention(
                    q, k.astype(cfg.dtype), v.astype(cfg.dtype), mask,
                    cfg,
                )

        x, _ = decoder_layers(params, cfg, x, cos, sin, attend)
        with scope("head"):
            last = jax.lax.dynamic_slice(
                x, (0, true_len - 1, 0), (1, 1, cfg.dim)
            )
            logits = _logits_head(last, params, cfg)
            tok = jnp.argmax(logits[0, 0], axis=-1).astype(jnp.int32)
        return ks, vs, tok

    return prefill


def make_decode_fn(cfg: llama2.LlamaConfig, cache_len: int):
    """The single-token decode program over every slot at once.

    ``(params, ks, vs, tokens [slots], pos [slots])`` ->
    ``(ks, vs, next_tokens [slots])``: each slot's incoming token is
    embedded, rotated to its own position ``pos[slot]`` (the per-slot
    position counter feeding RoPE), its K/V appended at that position,
    and attention runs over cache columns ``<= pos[slot]`` -- columns
    beyond a slot's length (stale entries from an evicted request, the
    padded prefill tail) are masked out, which is what makes slot
    reuse safe.
    """
    scope = jax.named_scope

    def decode(params, ks, vs, tokens, pos):
        slots = tokens.shape[0]
        with scope("embed"):
            x = _embed(params, tokens[:, None], cfg)  # [slots, 1, dim]
        cos, sin = _rope_tables(cfg, 1, pos)
        cos, sin = cos[:, None, :], sin[:, None, :]  # [slots, 1, D/2]
        col = jnp.arange(cache_len)
        mask = (col[None, :] <= pos[:, None])[:, None, None, None, :]
        rows = jnp.arange(slots)

        def attend(i, h, lp, q, k, v):
            nonlocal ks, vs
            with scope("kv_write"):
                ks = ks.at[i, rows, pos].set(k[:, 0].astype(ks.dtype))
                vs = vs.at[i, rows, pos].set(v[:, 0].astype(vs.dtype))
            with scope("kv_read"):
                k_all = ks[i].astype(cfg.dtype)
                v_all = vs[i].astype(cfg.dtype)
            with scope("attention"):
                return _grouped_attention(q, k_all, v_all, mask, cfg)

        x, _ = decoder_layers(params, cfg, x, cos, sin, attend)
        with scope("head"):
            logits = _logits_head(x, params, cfg)  # [slots, 1, vocab]
            tok = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
        return ks, vs, tok

    return decode


class Engine:
    """AOT-compiled prefill/decode over one resident KV cache.

    Owns the sharded cache and the executable table; the scheduler
    (serve/scheduler.py) drives it one prefill or decode at a time.
    ``compile_count`` increments on every executable build -- after
    :meth:`warmup` it must stay put (the zero-recompile guard).
    """

    def __init__(
        self,
        params: Any,
        cfg: llama2.LlamaConfig,
        serve_cfg: ServeConfig,
        mesh: Mesh,
        param_pspecs: Any = None,
    ):
        from tpu_hpc.serve.weights import place_params, serving_pspecs

        conv_moe.refuse(
            cfg, "the serving engine (slab or paged)",
            "a short-convolution layer leaves a state of rows alone "
            "(the last two inputs of its taps): the slab cache holds "
            "keys and values, and paging.RecurrentState holds three "
            "rows AND a state-space matrix a layer and has no "
            "constructor for rows alone, nor the prefix trie a "
            "snapshot of them",
        )
        if not getattr(self, "is_paged", False):
            sparse_moe.refuse(
                cfg, "the slab Engine",
                "its cache holds keys and values only, no indexer key "
                "and no selection",
            )
            latent_moe.refuse(
                cfg, "the slab Engine",
                "its cache holds per-head keys and values, and its "
                "programs have no absorbed latent read",
            )
            hybrid_ssm_moe.refuse(
                cfg, "the slab Engine",
                "its cache holds keys and values only, no recurrent "
                "state a slot",
            )
        if cfg.n_heads % cfg.kv_heads:
            raise ValueError(
                f"n_heads {cfg.n_heads} must be a multiple of kv_heads "
                f"{cfg.kv_heads}"
            )
        if serve_cfg.max_seq_len > cfg.max_seq_len:
            raise ValueError(
                f"cache capacity {serve_cfg.max_seq_len} exceeds the "
                f"model's max_seq_len {cfg.max_seq_len}"
            )
        self.cfg = cfg
        self.serve_cfg = serve_cfg
        self.mesh = mesh
        if param_pspecs is None:
            param_pspecs = serving_pspecs(params, mesh)
        self.param_pspecs = param_pspecs
        self.params = place_params(params, mesh, param_pspecs)
        self._param_shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s), param_pspecs,
            is_leaf=lambda x: isinstance(x, P),
        )
        self._rep = NamedSharding(mesh, P())
        # HELP text for the span histograms this engine feeds (the
        # Prometheus exposition renders it ahead of each # TYPE).
        reg = get_registry()
        reg.describe(
            "serve_prefill_s",
            "Prompt prefill forward, dispatch to first-token fetch "
            "(s; one slab prompt or one paged chunk)",
        )
        reg.describe(
            "serve_decode_s",
            "One batched decode step across all slots, dispatch to "
            "token fetch (s)",
        )
        reg.describe(
            "serve_compiles_total",
            "Executable builds by this process's engines (flat after "
            "warm-up, or the server is recompiling)",
        )

        self._init_cache()

        self._execs: Dict[Any, Any] = {}
        self.compile_count = 0

    def _cache_shape(self) -> Tuple[int, ...]:
        """Resident K (and V) cache shape; the paged engine
        (serve/paging.py) overrides this with its block pool."""
        return (
            self.cfg.n_layers, self.serve_cfg.slots,
            self.serve_cfg.max_seq_len, self.cfg.kv_heads,
            self.cfg.head_dim,
        )

    def _cache_pspec(self) -> P:
        return kv_cache_pspec(
            self.mesh, self.serve_cfg.slots, self.cfg.kv_heads
        )

    def _init_cache(self) -> None:
        cache_dtype = self.serve_cfg.cache_dtype or self.cfg.dtype
        shape = self._cache_shape()
        self._cache_sharding = NamedSharding(
            self.mesh, self._cache_pspec()
        )
        alloc = jax.jit(
            lambda: (
                jnp.zeros(shape, cache_dtype),
                jnp.zeros(shape, cache_dtype),
            ),
            out_shardings=(self._cache_sharding, self._cache_sharding),
        )
        self.ks, self.vs = alloc()
        self.cache_bytes = 2 * math.prod(shape) * jnp.dtype(
            cache_dtype
        ).itemsize

    # -- executable table ---------------------------------------------
    def _cache_abstract(self):
        return jax.ShapeDtypeStruct(
            self.ks.shape, self.ks.dtype, sharding=self._cache_sharding
        )

    def _params_abstract(self):
        """The resident weights as shapes with their shardings: what
        every program of this engine is lowered against."""
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            self.params, self._param_shardings,
        )

    def _count_compile(self) -> None:
        """One executable build: on the engine, and in the registry
        (``serve_compiles_total``, every engine of the process) so an
        operator sees a recompile on a dashboard, not in a debugger."""
        self.compile_count += 1
        get_registry().inc("serve_compiles_total")

    def _build(self, key):
        """Lower-and-compile one program shape (counted)."""
        self._count_compile()
        cache = self._cache_abstract()
        params_abs = self._params_abstract()
        scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=self._rep)
        if key[0] == "prefill":
            bucket = key[1]
            fn = make_prefill_fn(self.cfg, bucket, self.serve_cfg.slots)
            tokens = jax.ShapeDtypeStruct(
                (1, bucket), jnp.int32, sharding=self._rep
            )
            args = (params_abs, cache, cache, tokens, scalar, scalar)
        else:
            fn = make_decode_fn(self.cfg, self.serve_cfg.max_seq_len)
            vec = jax.ShapeDtypeStruct(
                (self.serve_cfg.slots,), jnp.int32, sharding=self._rep
            )
            args = (params_abs, cache, cache, vec, vec)
        jitted = jax.jit(
            fn,
            donate_argnums=(1, 2),  # the cache is engine-resident
            out_shardings=(
                self._cache_sharding, self._cache_sharding, self._rep
            ),
        )
        return jitted.lower(*args).compile()

    def _get_exec(self, key):
        if key not in self._execs:
            self._execs[key] = self._build(key)
        return self._execs[key]

    def warmup(self) -> int:
        """Compile every steady-state program shape up front: one
        prefill per bucket + the decode step. Returns the executable
        count -- after this, ``compile_count`` must never move."""
        for b in self.serve_cfg.prefill_buckets:
            self._get_exec(("prefill", b))
        self._get_exec(("decode",))
        return self.compile_count

    @property
    def compile_count_total(self) -> int:
        """Executable builds across the whole serving unit. The slab
        engine IS the unit; the paged engine adds its attached
        speculative draft engine's builds (serve/spec.py) -- the one
        number every recompile guard should read."""
        return self.compile_count

    # -- live weight swap ----------------------------------------------
    def swap_params(self, params: Any) -> None:
        """Replace the resident weights IN PLACE (live hot-swap,
        serve/fleet.py). The executable table keys on abstract
        (shape, dtype, sharding) only, so a tree matching the
        resident layout swaps with ZERO recompiles -- the next
        prefill/decode dispatch simply reads the new tree. Anything
        structurally different is a hard error naming the first
        mismatch: a silently re-lowered program would blow the
        steady-state compile pin mid-serve.

        The caller owns the swap DISCIPLINE: cached K/V was computed
        under the old weights, so a paged engine must be drained and
        its pool reset (:meth:`PagedEngine.reset_pool`) before
        serving resumes -- stale cache rows under new weights would
        be silently wrong, not masked."""
        old_leaves = jax.tree_util.tree_leaves_with_path(self.params)
        new_leaves = jax.tree_util.tree_leaves_with_path(params)
        if len(old_leaves) != len(new_leaves):
            raise ValueError(
                f"swap_params: tree has {len(new_leaves)} leaves, "
                f"resident has {len(old_leaves)}"
            )
        for (op, ol), (np_, nl) in zip(old_leaves, new_leaves):
            if op != np_ or ol.shape != nl.shape \
                    or ol.dtype != nl.dtype:
                raise ValueError(
                    "swap_params: leaf mismatch at "
                    f"{jax.tree_util.keystr(np_)}: got "
                    f"{nl.shape}/{nl.dtype} for "
                    f"{jax.tree_util.keystr(op)} "
                    f"{ol.shape}/{ol.dtype}"
                )
            old_sh = getattr(ol, "sharding", None)
            new_sh = getattr(nl, "sharding", None)
            if old_sh is not None and new_sh is not None \
                    and old_sh != new_sh:
                raise ValueError(
                    "swap_params: sharding mismatch at "
                    f"{jax.tree_util.keystr(np_)} (place the tree "
                    "through serve/weights.place_params with this "
                    "engine's param_pspecs first)"
                )
        self.params = params

    # -- serving ops ----------------------------------------------------
    def _rep_arr(self, value, dtype=jnp.int32):
        return jax.device_put(jnp.asarray(value, dtype), self._rep)

    def prefill(self, slot: int, prompt: Sequence[int]) -> int:
        """Run one request's prompt through the bucketed prefill
        program, writing its K/V into ``slot``; returns the first
        greedy token. Bracketed as a ``prefill`` span (obs/spans.py):
        the JSONL/flight-ring phase record and the XProf
        TraceAnnotation share one bracket. ``int(tok)`` inside the
        span is the device fetch, so the span measures
        dispatch-to-result like the Trainer's chunk timer."""
        n = len(prompt)
        if n < 1:
            raise ValueError("empty prompt")
        if not 0 <= slot < self.serve_cfg.slots:
            raise ValueError(f"slot {slot} out of range")
        bucket = self.serve_cfg.bucket_for(n)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n] = np.asarray(prompt, np.int32)
        exec_ = self._get_exec(("prefill", bucket))
        with span("prefill", hist="serve_prefill_s", n=bucket):
            self.ks, self.vs, tok = exec_(
                self.params, self.ks, self.vs,
                self._rep_arr(padded), self._rep_arr(n),
                self._rep_arr(slot),
            )
            return int(tok)

    def decode(
        self, tokens: Sequence[int], positions: Sequence[int]
    ) -> np.ndarray:
        """One decode step for every slot: ``tokens[s]`` enters at
        position ``positions[s]``. Returns the next greedy token per
        slot (inactive slots produce garbage the scheduler ignores --
        their mask still bounds what they read). Span-bracketed like
        :meth:`prefill`; the ``np.asarray`` fetch rides inside."""
        exec_ = self._get_exec(("decode",))
        with span("decode", hist="serve_decode_s"):
            self.ks, self.vs, toks = exec_(
                self.params, self.ks, self.vs,
                self._rep_arr(np.asarray(tokens, np.int32)),
                self._rep_arr(np.asarray(positions, np.int32)),
            )
            return np.asarray(toks)
