"""LlamaSlotBackend — the jax half of the continuous-batching engine.

Owns the device-resident slot cache and the per-slot fill state
(``cur``/``pad_lens`` vectors), and drives the jitted slot primitives
in ``models.llama``:

- ``prefill_into_slot``: the *blocking* whole-prompt refill
  (``SPARKDL_SERVE_STALL_FREE=0`` fallback) — one compiled program per
  prompt-length *bucket* (``serving.engine.bucket_length``), slot index
  traced;
- ``prefill_chunk_into_slot``: the stall-free chunk primitive — ONE
  compiled program per (chunk size, num_slots, max_len); the engine
  interleaves these with decode steps so a long prompt never
  monopolizes the device (``begin_prefill`` / ``prefill_chunk`` /
  ``finish_prefill`` below);
- ``slot_decode_step``: ONE compiled program per (num_slots, max_len)
  for the engine's whole lifetime — the steady-state hot path.

All signatures are routed through ``GLOBAL_COMPILE_CACHE.note`` so
every (re)compilation is a visible flight-recorder ``recompile`` event:
the serving bench pins "no decode-step re-trace after warmup" on
exactly that evidence.

**Fill-state invariant (chunked mode).** ``_cur[slot]`` is always the
slot's *write frontier* — the next cache position a real write will
land on. ``slot_decode_step`` unconditionally writes every row's
(masked, discarded) token at its own ``_cur``, so a decode step running
between two prefill chunks garbage-writes exactly AT the frontier,
which the next chunk (or the request's own first decode step)
overwrites before any attention can read it. Parking a mid-prefill
slot anywhere *below* its frontier would clobber committed prompt K/V.

**Paged variant (ISSUE 11).** :class:`PagedLlamaSlotBackend` replaces
the per-slot ``max_len`` rows with block tables over ONE shared K/V
pool (``models.llama`` paged primitives): per-request HBM is the
blocks actually touched, shared prompt heads are pointer grafts
(:class:`serving.prefix.RadixPrefixCache` — zero-copy commits AND
hits), and allocation policy lives in the jax-free
:class:`serving.paging.PagedBlockManager`, the same object the
``StubBackend`` mirror rides, so the scheduler-visible behavior cannot
drift between the two.

**Shared-prefix KV reuse.** When ``SPARKDL_SERVE_PREFIX_CACHE_MB`` > 0
(default 64), every completed chunked prefill commits its prompt's
K/V rows (chunk-aligned row count, so the copy programs stay bounded)
into a :class:`serving.prefix.PrefixCache`; ``begin_prefill`` looks the
new prompt up and, on a hit, scatters the cached rows into the slot
device-side — the engine then chunk-prefills only the tail. The chunked
layout is **zero-aligned** (token ``i`` at cache position ``i``, no
left pad), which is what makes prefix rows position-independent of
prompt length and chunk count.

Sampling: greedy (``temperature<=0``) is deterministic and
token-identical to the static ``generate()`` path for the same prompt
(the equivalence tests and example Part 3 pin this). With temperature
sampling the rng is folded per decode step / per prefill — streams are
reproducible for a fixed engine schedule, but are NOT the same draws
``generate()`` makes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import functools
import logging
import os

from ..core.runtime import GLOBAL_COMPILE_CACHE
from ..models import llama as L
from ..runner import chaos as chaos_lib
from .paging import PagedBlockManager
from .prefix import (PrefixCache, prefix_cache_budget_bytes,
                     usable_reuse)

log = logging.getLogger("sparkdl_tpu.serving")


class SlotCacheLost(RuntimeError):
    """A jitted slot call failed after consuming the donated cache: the
    in-flight KV state is unrecoverable, so retrying the call cannot
    help (every retry would read a deleted buffer). ``serving_fatal``
    tells the (jax-free) engine to fail over cleanly instead of burning
    its retry budget and evicting innocent requests one by one."""

    serving_fatal = True


def _tree_sig(tree):
    """(shape, dtype) of every leaf — the part of the call signature
    jax actually traces. Keying the compile-cache note on THIS (not on
    config constants) makes the no-re-trace pin real: an operand dtype
    or shape drift becomes a visible new signature."""
    return tuple((tuple(getattr(x, "shape", ())), str(getattr(x, "dtype",
                                                              "")))
                 for x in jax.tree_util.tree_leaves(tree))


def _weight_quantize(self, weight_dtype):
    """Shared int8-weight hook (ISSUE 18): validate the mode, clone the
    model with ``weight_quant`` (QuantDense engages on the stored
    dtype) and convert ``self.params`` host-side. Runs INSIDE each
    backend ``__init__`` before any jitted call — on the tp backends
    that is after ``_tp_setup`` (the clone composes with the
    kernel-mesh clone) and before ``_tp_finish`` (so ``shard_params``
    places int8 codes + ``kernel_scale`` leaves directly; the
    column-parallel scale rules live in
    ``parallel.transformer_tp_rules``)."""
    self.weight_dtype = weight_dtype
    if weight_dtype is None:
        return
    self.model = self.model.clone(weight_quant=weight_dtype)
    self.params = L.quantize_params(self.params, weight_dtype)
    log.info("serving with %s-quantized projection weights "
             "(absmax per-channel scales, dequant folded after each "
             "matmul)", weight_dtype)


@functools.partial(jax.jit, static_argnames=("rows",))
def _gather_slot_rows(cache, slot, *, rows: int):
    """Copy ``[0, rows)`` of one slot's K/V rows out of the slot cache —
    the prefix-cache COMMIT copy. ``rows`` is static (one small copy
    program per distinct chunk-aligned length — bounded by
    max_len / chunk); ``slot`` traced. Scalar (``idx``) leaves become
    structure-preserving placeholders so the payload pytree zips back
    against the cache at scatter time."""
    def g(leaf):
        if getattr(leaf, "ndim", 0) == 4:
            return jax.lax.dynamic_slice(
                leaf, (slot, 0, 0, 0),
                (1, leaf.shape[1], rows, leaf.shape[3]))
        return jnp.zeros((), jnp.int32)

    return jax.tree_util.tree_map(g, cache)


@functools.partial(jax.jit, donate_argnames=("cache",))
def _scatter_prefix_rows(cache, payload, slot):
    """Write a cached prefix payload's rows into row ``slot`` at
    position 0 — the prefix-cache HIT copy (device-side, the cache is
    donated exactly like the slot primitives). Rows past the payload's
    real token count are stale entry state: the engine's tail chunks
    overwrite everything from the reuse point on before attention can
    reach it (the write-frontier invariant in the module doc)."""
    def s(big, sm):
        if getattr(sm, "ndim", 0) == 4:
            return jax.lax.dynamic_update_slice(
                big, sm.astype(big.dtype), (slot, 0, 0, 0))
        return big

    return jax.tree_util.tree_map(s, cache, payload)


class LlamaSlotBackend:
    """Slot backend over ``models.llama`` (see module doc).

    ``num_slots`` cache rows, each independently one in-flight request;
    ``max_len`` cache slots per row (a request needs
    ``bucket(prompt) + max_new_tokens <= max_len`` — the engine's
    admission check). The cache rides the jitted calls with buffer
    donation, so the HBM footprint stays one cache regardless of how
    many refills happen.
    """

    #: tensor-parallel degree — 1 for the single-device backends; the
    #: TensorParallel* subclasses set it to the tp mesh extent.
    tp_degree = 1

    def __init__(self, model, variables, num_slots: int, max_len: int, *,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0, seed: int = 0,
                 prefix_cache_bytes: int | None = None,
                 weight_dtype: str | None = None):
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        if max_len < 2:
            raise ValueError(f"max_len must be >= 2, got {max_len}")
        self.model = model
        self.params = variables["params"] if "params" in variables \
            else variables
        _weight_quantize(self, weight_dtype)
        self.num_slots = int(num_slots)
        self.max_len = int(max_len)
        self.vocab_size = int(model.cfg.vocab_size)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        from ..ops import flash_decode as fd
        reason = fd.support_reason(self.max_len)
        if reason is not None:
            log.warning("flash-decode kernel stands down for this "
                        "config (%s); decode steps use dense cache "
                        "attention", reason)
        self.cache = self._make_cache(self.model)
        self._tokens = np.zeros(self.num_slots, np.int32)
        # Idle slots park at fill index 0 — their write frontier: the
        # step's (masked, discarded) write lands exactly where the next
        # refill's first real write will overwrite it.
        self._cur = np.zeros(self.num_slots, np.int32)
        self._pads = np.zeros(self.num_slots, np.int32)
        self._rng = jax.random.PRNGKey(seed)
        self._step_i = 0
        self._prefill_i = 0
        budget = prefix_cache_budget_bytes() if prefix_cache_bytes is None \
            else max(0, int(prefix_cache_bytes))
        self.prefix_cache = PrefixCache(budget) if budget > 0 else None
        self._warned_commit = False

    def _make_cache(self, model):
        """Cache-allocation hook: the TP subclasses pass the
        head-sharded mesh placement so a big cache is born distributed
        instead of allocated on one device and reshuffled."""
        return L.init_cache(model, self.num_slots, self.max_len)

    def kv_pool_device_bytes(self) -> int:
        """PER-DEVICE K/V bytes of the slot cache / paged pool: the max
        over devices of summed K/V shard bytes — the whole cache on a
        single-device backend, ``total / tp`` under the head-sharded
        tensor-parallel layout. The engine exports it as the
        ``serving_kv_pool_device_bytes`` gauge; the tp bench leg pins
        the ``1/tp`` shrink on it."""
        per: dict = {}
        for leaf in jax.tree_util.tree_leaves(self.cache):
            # 4-D K/V leaves plus a quantized pool's 3-D kv_scale
            # planes — the scale overhead is part of the budget.
            if getattr(leaf, "ndim", 0) not in (3, 4):
                continue
            shards = getattr(leaf, "addressable_shards", None)
            if shards:
                for s in shards:
                    d = s.data
                    per[s.device.id] = per.get(s.device.id, 0) + \
                        d.size * d.dtype.itemsize
            else:
                per[-1] = per.get(-1, 0) + leaf.size * leaf.dtype.itemsize
        return max(per.values(), default=0)

    # -- engine protocol --------------------------------------------------
    def prefill(self, slot: int, prompt, bucket: int) -> int:
        """Prefill ``prompt`` (left-padded to ``bucket``) into ``slot``;
        returns the first sampled token."""
        if bucket > self.max_len:
            raise ValueError(f"bucket {bucket} > max_len {self.max_len}")
        ids, pad = L.left_pad_prompts([list(prompt)], pad_to=bucket)
        ids_arr, pad_arr = jnp.asarray(ids), jnp.asarray(pad)
        # One compiled prefill per bucket length (slot index is traced):
        # a NEW bucket is a visible recompile event, a seen one is not.
        # Keyed on the TRACED signature (operand + cache shapes/dtypes),
        # so a genuine re-trace regression shows up as new signatures.
        GLOBAL_COMPILE_CACHE.note(
            "serve_prefill",
            (_tree_sig((ids_arr, pad_arr)), _tree_sig(self.cache),
             self.temperature, self.top_k, self.top_p))
        key = self._rng if self.temperature <= 0.0 else \
            jax.random.fold_in(self._rng, (1 << 20) + self._prefill_i)
        self._prefill_i += 1
        tok, self.cache = self._guarded(
            L.prefill_into_slot, self.model, self.params, ids_arr,
            pad_arr, self.cache, jnp.int32(slot), key,
            temperature=self.temperature, top_k=self.top_k,
            top_p=self.top_p)
        tok = int(np.asarray(tok)[0])
        self._tokens[slot] = tok
        self._cur[slot] = bucket
        self._pads[slot] = int(pad[0])
        return tok

    # -- chunked (stall-free) prefill protocol ----------------------------
    def begin_prefill(self, slot: int, prompt, chunk: int) -> int:
        """Arm ``slot`` for a chunked (zero-aligned) prefill. Looks the
        prompt up in the prefix cache; on a hit the cached rows are
        copied into the slot device-side and the returned offset tells
        the engine where its tail chunks start (0 on miss; the cap/
        rounding policy is :func:`serving.prefix.usable_reuse`)."""
        self._pads[slot] = 0
        self._tokens[slot] = 0
        self._cur[slot] = 0  # frontier: nothing written yet
        if self.prefix_cache is None:
            return 0
        key, n_cached, payload = self.prefix_cache.lookup(prompt)
        reuse = usable_reuse(n_cached, len(prompt), chunk)
        if reuse <= 0 or payload is None:
            self.prefix_cache.note_miss()
            return 0
        GLOBAL_COMPILE_CACHE.note(
            "serve_prefix_put", (_tree_sig(payload), _tree_sig(self.cache)))
        self.cache = self._guarded(_scatter_prefix_rows, self.cache,
                                   payload, jnp.int32(slot))
        self.prefix_cache.use(key, reuse)
        self._cur[slot] = reuse  # frontier: tail chunks start here
        return reuse

    def prefill_chunk(self, slot: int, chunk, offset: int,
                      n_valid: int, window: int | None = None) -> int:
        """Consume one fixed-size chunk of a prompt into ``slot`` at
        ``[offset, offset + C)``; ``n_valid`` = real (non-pad) tokens in
        the chunk; ``window`` = the request's chunk-aligned total
        prompt length (the chunk touches/attends only that many rows —
        a short prompt's chunk never pays O(C·max_len) attention).
        Returns the token sampled at the chunk's last real position —
        the engine uses it only from the FINAL chunk."""
        ids = jnp.asarray(np.asarray(chunk, np.int32)[None, :])
        window = self.max_len if window is None \
            else min(int(window), self.max_len)
        # One compiled program per (chunk size, window) — window values
        # are chunk multiples, so the program count is bounded by
        # max_len/C; slot/offset/n_valid are traced. A NEW combination
        # is a visible recompile event.
        GLOBAL_COMPILE_CACHE.note(
            "serve_prefill_chunk",
            (_tree_sig((ids,)), _tree_sig(self.cache), window,
             self.temperature, self.top_k, self.top_p))
        key = self._rng if self.temperature <= 0.0 else \
            jax.random.fold_in(self._rng, (1 << 20) + self._prefill_i)
        self._prefill_i += 1
        tok, self.cache = self._guarded(
            L.prefill_chunk_into_slot, self.model, self.params, ids,
            self.cache, jnp.int32(slot), jnp.int32(offset),
            jnp.int32(n_valid), key, window=window,
            temperature=self.temperature,
            top_k=self.top_k, top_p=self.top_p)
        # frontier: the next write (chunk or first decode token) lands
        # past this chunk's rows
        self._cur[slot] = offset + len(chunk)
        return int(np.asarray(tok)[0])

    def finish_prefill(self, slot: int, prompt, last_tok: int,
                       aligned_len: int, commit: bool = True) -> int:
        """Complete a chunked prefill: pin the slot's decode state at
        the REAL prompt length and (when ``commit`` — the engine skips
        one-chunk prompts and warm hits whose only new rows are a
        distinct tail) copy the prompt's rows into the prefix cache
        (``aligned_len`` = chunk-aligned written length — the engine's
        chunk plan knows it; bounding the stored row count to chunk
        multiples bounds the copy-program count). Returns the request's
        first token."""
        n = len(prompt)
        self._tokens[slot] = int(last_tok)
        self._cur[slot] = n
        self._pads[slot] = 0
        if commit and self.prefix_cache is not None:
            try:
                chaos_lib.fire("serve_commit", batch=slot)
                self._commit_prefix(slot, prompt, aligned_len)
            except Exception as e:  # noqa: BLE001 — caching is an
                # optimization, never fatal — UNLESS the error says the
                # slot state itself is gone (injected cache_lost /
                # SlotCacheLost): then the engine must fail over.
                if getattr(e, "serving_fatal", False):
                    raise
                if not self._warned_commit:
                    self._warned_commit = True
                    log.warning("prefix-cache commit failed (%s: %s); "
                                "suppressing further warnings",
                                type(e).__name__, e)
        return int(last_tok)

    def _commit_prefix(self, slot: int, prompt, aligned_len: int):
        key = tuple(int(t) for t in prompt)
        cache_obj = self.prefix_cache
        if cache_obj is None or aligned_len < 1:
            return
        rows = min(int(aligned_len), self.max_len)
        GLOBAL_COMPILE_CACHE.note(
            "serve_prefix_gather", (rows, _tree_sig(self.cache)))
        payload = _gather_slot_rows(self.cache, jnp.int32(slot), rows=rows)
        nbytes = sum(x.size * x.dtype.itemsize
                     for x in jax.tree_util.tree_leaves(payload)
                     if getattr(x, "ndim", 0) == 4)
        cache_obj.put(key, payload, nbytes)

    def prefix_stats(self) -> dict | None:
        return None if self.prefix_cache is None else \
            self.prefix_cache.stats()

    def step(self, active_slots) -> list[int]:
        """Advance every slot one token at its own fill index; returns
        the per-slot token list (idle slots' entries are garbage — the
        engine only reads ``active_slots``)."""
        tok_arr = jnp.asarray(self._tokens)
        cur_arr = jnp.asarray(self._cur)
        pads_arr = jnp.asarray(self._pads)
        # Keyed on the traced signature (see prefill): after warmup this
        # must stay ONE signature for the engine's lifetime — the
        # acceptance observable for "refills never re-trace the step".
        GLOBAL_COMPILE_CACHE.note(
            "serve_decode_step",
            (_tree_sig((tok_arr, cur_arr, pads_arr)),
             _tree_sig(self.cache), self.temperature, self.top_k,
             self.top_p))
        # Greedy sampling never reads the key — skip the per-step fold_in
        # dispatch (one fewer device op on the hot loop).
        key = self._rng if self.temperature <= 0.0 else \
            jax.random.fold_in(self._rng, self._step_i)
        self._step_i += 1
        nxt, self.cache = self._guarded(
            L.slot_decode_step, self.model, self.params, self.cache,
            tok_arr, cur_arr, pads_arr, key,
            temperature=self.temperature, top_k=self.top_k,
            top_p=self.top_p)
        nxt = np.asarray(nxt).astype(np.int32)
        # Only busy slots advance their fill index (each just wrote at
        # cur, the next token lands at cur+1 — admission guarantees
        # bucket + max_new <= max_len so this never overruns); idle
        # slots stay parked and their write is masked garbage.
        active = np.asarray(sorted(active_slots), np.int32)
        self._cur[active] += 1
        self._tokens[active] = nxt[active]
        return nxt.tolist()

    # -- speculative verify protocol (ISSUE 12) ---------------------------
    def _verify_tokens(self, drafts, k: int):
        """The verify window's token matrix: column 0 is each slot's
        current token (what the decode step would consume), columns
        1..k its drafts (zero-padded — a padded column's write lands
        past the frontier / gets dropped, and its proposal is never
        committed)."""
        toks = np.zeros((self.num_slots, int(k) + 1), np.int32)
        toks[:, 0] = self._tokens
        for s, d in drafts.items():
            if d:
                toks[s, 1:1 + len(d)] = np.asarray(d, np.int32)
        return toks

    def verify(self, active_slots, drafts, k: int) -> list[list[int]]:
        """One batched speculative verify window
        (``models.llama.slot_verify_step`` — the fourth jitted
        donated-cache slot primitive): k+1 greedy proposals per slot
        in ONE program dispatch. Does NOT advance any fill state — the
        engine commits the accepted prefix via :meth:`commit_spec`
        (reject = no call at all). Greedy-only: the engine gates
        speculation on ``temperature <= 0``."""
        if self.temperature > 0.0:
            raise ValueError("speculative verify is greedy-only "
                             f"(temperature {self.temperature:g} > 0)")
        tok_arr = jnp.asarray(self._verify_tokens(drafts, k))
        cur_arr = jnp.asarray(self._cur)
        pads_arr = jnp.asarray(self._pads)
        # One compiled program per (num_slots, k+1, max_len) for the
        # engine's lifetime: the no-re-trace observable for "drafting /
        # accept / reject never re-trace the verify".
        GLOBAL_COMPILE_CACHE.note(
            "serve_verify_step",
            (_tree_sig((tok_arr, cur_arr, pads_arr)),
             _tree_sig(self.cache)))
        props, self.cache = self._guarded(
            L.slot_verify_step, self.model, self.params, self.cache,
            tok_arr, cur_arr, pads_arr)
        return np.asarray(props).astype(np.int32).tolist()

    def commit_spec(self, slot: int, n_tokens: int, last_tok: int):
        """Advance ``slot``'s write frontier past the ``n_tokens``
        positions the verify window committed and pin its current
        token. Rejected rows sit at/past the new frontier — garbage
        the next write overwrites before attention reads it, so
        rollback is exactly this non-advance (no device work)."""
        self._cur[slot] += int(n_tokens)
        self._tokens[slot] = int(last_tok)

    def _guarded(self, fn, *args, **kw):
        """Run one jitted slot call; if it raises AFTER consuming the
        donated cache (a mid-execution device error — the cache buffer
        is deleted by donation), convert to :class:`SlotCacheLost` so
        the engine fails over instead of retrying against a deleted
        array and evicting innocent requests one by one. Host-side
        failures (validation, chaos before dispatch) leave the cache
        alive and keep the per-request retry/quarantine path."""
        try:
            return fn(*args, **kw)
        except SlotCacheLost:
            raise
        except Exception as e:
            lost = any(getattr(x, "is_deleted", lambda: False)()
                       for x in jax.tree_util.tree_leaves(self.cache))
            if lost:
                raise SlotCacheLost(
                    f"slot cache consumed by failed "
                    f"{getattr(fn, '__name__', fn)}: "
                    f"{type(e).__name__}: {e}") from e
            raise

    def release(self, slot: int):
        """Retire hook: park the slot at fill index 0 (its stale cache
        rows are dead — a future refill overwrites [0, bucket) and masks
        everything past its own fill index)."""
        self._cur[slot] = 0
        self._pads[slot] = 0
        self._tokens[slot] = 0

    def rebuild(self):
        """Failover hook (ISSUE 19): the slot cache was consumed or
        wedged — allocate a fresh one (through the same ``_make_cache``
        hook the TP subclass shards), reset every slot's host-side
        frontier, and drop the prefix cache (its payloads were gathered
        from the dead cache's layout; ``PrefixCache.clear()``
        semantics). The engine re-admits live requests via the
        preemption-resume path, so nothing here needs their state."""
        self.cache = self._make_cache(self.model)
        self._tokens[:] = 0
        self._cur[:] = 0
        self._pads[:] = 0
        if self.prefix_cache is not None:
            self.prefix_cache.clear()
        self._warned_commit = False


def pool_bytes_per_block(model, block_size: int,
                         kv_dtype: str | None = None) -> int:
    """Bytes one physical block costs across every layer — the
    ``SPARKDL_SERVE_KV_POOL_MB`` → block-count conversion, derived from
    :func:`models.llama.paged_pool_spec` (the allocation's own source
    of truth; no parameter compute, no allocation). With ``kv_dtype``
    the count covers the quantized K/V codes PLUS each block's slice of
    the ``kv_scale`` planes (3-D leaves) — the scale overhead is billed
    against the same budget, so an int8 pool's ≥2× block gain is
    honest."""
    shapes = L.paged_pool_spec(model, 1, int(block_size), kv_dtype)
    return sum(int(np.prod(s.shape)) * s.dtype.itemsize
               for s in jax.tree_util.tree_leaves(shapes)
               if len(getattr(s, "shape", ())) in (3, 4))


class PagedLlamaSlotBackend(LlamaSlotBackend):
    """Block-table slot backend (ISSUE 11): one shared K/V pool of
    ``pool_blocks`` physical blocks, a ``[num_slots, max_blocks]``
    int32 block table, a jax-free :class:`serving.paging.BlockAllocator`
    (free list + refcounts + copy-on-write), and block-granular radix
    prefix sharing (:class:`serving.prefix.RadixPrefixCache`) whose
    hits are table pointer grafts — zero K/V bytes copied.

    ``self.cache`` *is* the pool (keeping the attribute name keeps the
    donated-cache loss guard ``_guarded`` working unchanged). Slot
    tables and the allocator live host-side; a slot's logical row
    ``[0, max_len)`` maps through its table, unallocated entries point
    at the reserved trash block 0 so masked garbage writes (idle /
    block-stalled slots) land where no request reads.

    Sizing: ``pool_blocks`` directly, or ``kv_pool_mb`` converted via
    :func:`pool_bytes_per_block`; the default matches the un-paged
    footprint (``num_slots × ceil(max_len / block_size)`` + trash) so
    paging is a strict generalization — over-subscription comes from
    raising ``num_slots`` against a FIXED pool, which is the point.
    """

    paged = True

    def __init__(self, model, variables, num_slots: int, max_len: int, *,
                 block_size: int = 16, pool_blocks: int | None = None,
                 kv_pool_mb: float | None = None,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0, seed: int = 0,
                 prefix_cache_bytes: int | None = None,
                 kv_dtype: str | None = None,
                 weight_dtype: str | None = None):
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        if max_len < 2:
            raise ValueError(f"max_len must be >= 2, got {max_len}")
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        if kv_dtype is not None:
            L.kv_quant_spec(kv_dtype)  # raises loudly on unknown/absent
        self.kv_dtype = kv_dtype
        self.model = model
        self.params = variables["params"] if "params" in variables \
            else variables
        _weight_quantize(self, weight_dtype)
        model = self.model
        self.num_slots = int(num_slots)
        self.block_size = int(block_size)
        self.max_blocks = -(-int(max_len) // self.block_size)
        self.max_len = self.max_blocks * self.block_size
        self.vocab_size = int(model.cfg.vocab_size)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        from ..ops import paged_flash_decode as pfd
        reason = pfd.support_reason(self.block_size, kv_dtype=kv_dtype)
        if reason is not None:
            log.warning("paged flash-decode kernel stands down for "
                        "this config (%s); decode steps use the dense "
                        "gather view", reason)
        if pool_blocks is None and kv_pool_mb is not None:
            # PER-DEVICE budget → block count: on the single-device
            # backend a block's device cost is its full K/V bytes; the
            # TP subclass overrides the hook with bytes/tp (each device
            # holds 1/tp of every block), so the same per-device
            # SPARKDL_SERVE_KV_POOL_MB buys tp× the blocks — more KV
            # at the same per-chip memory, the scale-out point.
            per = self._pool_block_device_bytes(model)
            pool_blocks = max(2, int(kv_pool_mb * 2 ** 20) // per)
        budget = prefix_cache_budget_bytes() if prefix_cache_bytes is None \
            else max(0, int(prefix_cache_bytes))
        self.tables = np.zeros((self.num_slots, self.max_blocks),
                               np.int32)  # 0 = trash block
        # Radix entries are pool blocks, not byte payloads: the MB knob
        # only gates sharing on/off here (the pool itself is the budget,
        # reclaimed LRU-first when allocation runs short).
        self.mgr = PagedBlockManager(
            self.num_slots, self.max_len, self.block_size, pool_blocks,
            radix=budget > 0,
            on_table=self._set_table, copy_block=self._copy_block)
        self.pool_blocks = self.mgr.pool_blocks
        # Observability (ISSUE 18): pool_stats() and the /serving
        # inspector carry the kv storage mode, the per-block byte cost
        # (scale plane included), the f32 cost it displaces and the
        # resulting effective block count — at equal kv_pool_mb an int8
        # pool's blocks_total is the ≥2× gain the acceptance pins.
        per_blk = pool_bytes_per_block(model, self.block_size, kv_dtype)
        shapes = L.paged_pool_spec(model, 1, self.block_size, kv_dtype)
        scale_per_blk = sum(
            int(np.prod(s.shape)) * s.dtype.itemsize
            for s in jax.tree_util.tree_leaves(shapes)
            if len(getattr(s, "shape", ())) == 3)
        self.mgr.info = {
            "kv_dtype": kv_dtype or "float",
            "kv_block_bytes": per_blk,
            "kv_block_bytes_f32": pool_bytes_per_block(
                model, self.block_size),
            "kv_scale_bytes_per_block": scale_per_blk,
            "effective_blocks": self.pool_blocks,
        }
        self.cache = self._make_pool(model)
        self.allocator = self.mgr.allocator
        self.radix = self.mgr.radix
        self._tokens = np.zeros(self.num_slots, np.int32)
        self._cur = np.zeros(self.num_slots, np.int32)
        self._pads = np.zeros(self.num_slots, np.int32)
        self._rng = jax.random.PRNGKey(seed)
        self._step_i = 0
        self._prefill_i = 0
        self.prefix_cache = None  # the byte-payload LRU does not apply
        self._warned_commit = False

    def _pool_block_device_bytes(self, model) -> int:
        """Per-DEVICE bytes one pool block costs (see ``__init__``) —
        quant-aware: int8/fp8 codes + the block's scale-plane slice,
        so the same ``kv_pool_mb`` budget honestly buys the extra
        blocks."""
        return pool_bytes_per_block(model, self.block_size,
                                    self.kv_dtype)

    def _make_pool(self, model):
        """Pool-allocation hook (see ``LlamaSlotBackend._make_cache``)."""
        return L.init_paged_pool(model, self.pool_blocks, self.block_size,
                                 kv_quant=self.kv_dtype)

    # -- allocation plumbing (policy lives in PagedBlockManager) ----------
    def _set_table(self, slot: int, idx: int, block: int) -> None:
        self.tables[slot, idx] = block

    def _copy_block(self, src: int, dst: int) -> None:
        GLOBAL_COMPILE_CACHE.note("serve_pool_cow", _tree_sig(self.cache))
        self.cache = self._guarded(L.copy_pool_block, self.cache,
                                   jnp.int32(src), jnp.int32(dst))

    def can_reserve(self, n: int) -> bool:
        return self.mgr.can_reserve(n)

    def ensure_block_for(self, slot: int, pos: int) -> bool:
        return self.mgr.ensure_block_for(slot, pos)

    def drain_alloc_samples(self) -> list[float]:
        return self.mgr.drain_alloc_samples()

    def pool_stats(self) -> dict:
        return self.mgr.pool_stats()

    def prefix_stats(self) -> dict | None:
        return self.mgr.prefix_stats()

    # -- engine protocol --------------------------------------------------
    def prefill(self, slot: int, prompt, bucket: int) -> int:
        """Blocking whole-prompt refill through the table. Left-padded
        layout is not zero-aligned, so the blocking path never radix-
        shares — it still pages (bucket + 1 decode block allocated, the
        rest grows on demand)."""
        if bucket > self.max_len:
            raise ValueError(f"bucket {bucket} > max_len {self.max_len}")
        self.mgr.reserve_bucket(slot, bucket)
        ids, pad = L.left_pad_prompts([list(prompt)], pad_to=bucket)
        ids_arr, pad_arr = jnp.asarray(ids), jnp.asarray(pad)
        row = jnp.asarray(self.tables[slot])
        GLOBAL_COMPILE_CACHE.note(
            "serve_prefill",
            (_tree_sig((ids_arr, pad_arr, row)), _tree_sig(self.cache),
             self.temperature, self.top_k, self.top_p))
        key = self._rng if self.temperature <= 0.0 else \
            jax.random.fold_in(self._rng, (1 << 20) + self._prefill_i)
        self._prefill_i += 1
        tok, self.cache = self._guarded(
            L.paged_prefill_into_slot, self.model, self.params, ids_arr,
            pad_arr, self.cache, row, key, temperature=self.temperature,
            top_k=self.top_k, top_p=self.top_p)
        tok = int(np.asarray(tok)[0])
        self._tokens[slot] = tok
        self._cur[slot] = bucket
        self._pads[slot] = int(pad[0])
        return tok

    def begin_prefill(self, slot: int, prompt, chunk: int) -> int:
        """Arm a chunked (zero-aligned) prefill: radix-graft the longest
        cached full-block head (table pointers + refcounts, no copy),
        then allocate private blocks covering the chunk-aligned
        remainder + one decode block. Raises
        :class:`serving.paging.BlockExhausted` when the pool cannot
        cover it (graft refs rolled back) — the engine requeues the
        request and waits."""
        self._pads[slot] = 0
        self._tokens[slot] = 0
        self._cur[slot] = 0
        reuse = self.mgr.reserve_prompt(slot, prompt, chunk)
        self._cur[slot] = reuse  # frontier: tail chunks start here
        return reuse

    def prefill_chunk(self, slot: int, chunk, offset: int,
                      n_valid: int, window: int | None = None) -> int:
        ids = jnp.asarray(np.asarray(chunk, np.int32)[None, :])
        # window is NOT clamped to max_len: a resume's chunk-aligned
        # plan can overhang the slot row, and the paged primitive pads
        # the attention view with scratch rows past the table instead
        # of letting dynamic_update_slice clamp the chunk's write back
        # over committed rows. Cap only against a runaway caller.
        window = self.max_len if window is None \
            else min(int(window), self.max_len + len(chunk))
        row = jnp.asarray(self.tables[slot])
        wb = -(-window // self.block_size)
        GLOBAL_COMPILE_CACHE.note(
            "serve_prefill_chunk",
            (_tree_sig((ids, row)), _tree_sig(self.cache), wb,
             self.temperature, self.top_k, self.top_p))
        key = self._rng if self.temperature <= 0.0 else \
            jax.random.fold_in(self._rng, (1 << 20) + self._prefill_i)
        self._prefill_i += 1
        tok, self.cache = self._guarded(
            L.paged_prefill_chunk_into_slot, self.model, self.params,
            ids, self.cache, row, jnp.int32(offset), jnp.int32(n_valid),
            key, window=wb * self.block_size,
            temperature=self.temperature, top_k=self.top_k,
            top_p=self.top_p)
        self._cur[slot] = offset + len(chunk)
        return int(np.asarray(tok)[0])

    def finish_prefill(self, slot: int, prompt, last_tok: int,
                       aligned_len: int, commit: bool = True) -> int:
        """Complete a chunked prefill. The radix commit is ZERO-COPY —
        the prompt's full blocks are already in the pool, the trie just
        takes a reference on each — so unlike the gather-copy LRU there
        is no copy economy to police: commit whenever sharing is on."""
        self._tokens[slot] = int(last_tok)
        self._cur[slot] = len(prompt)
        self._pads[slot] = 0
        if commit:
            try:
                chaos_lib.fire("serve_commit", batch=slot)
                self.mgr.commit(slot, prompt)
            except Exception as e:  # noqa: BLE001 — caching is an
                # optimization, never fatal — UNLESS serving-fatal
                # (injected cache_lost / SlotCacheLost): fail over.
                if getattr(e, "serving_fatal", False):
                    raise
                if not self._warned_commit:
                    self._warned_commit = True
                    log.warning("radix commit failed (%s: %s); "
                                "suppressing further warnings",
                                type(e).__name__, e)
        return int(last_tok)

    def step(self, active_slots) -> list[int]:
        tok_arr = jnp.asarray(self._tokens)
        cur_arr = jnp.asarray(self._cur)
        pads_arr = jnp.asarray(self._pads)
        tables_arr = jnp.asarray(self.tables)
        GLOBAL_COMPILE_CACHE.note(
            "serve_decode_step",
            (_tree_sig((tok_arr, cur_arr, pads_arr, tables_arr)),
             _tree_sig(self.cache), self.temperature, self.top_k,
             self.top_p))
        key = self._rng if self.temperature <= 0.0 else \
            jax.random.fold_in(self._rng, self._step_i)
        self._step_i += 1
        nxt, self.cache = self._guarded(
            L.paged_slot_decode_step, self.model, self.params,
            self.cache, tables_arr, tok_arr, cur_arr, pads_arr, key,
            temperature=self.temperature, top_k=self.top_k,
            top_p=self.top_p)
        nxt = np.asarray(nxt).astype(np.int32)
        active = np.asarray(sorted(active_slots), np.int32)
        self._cur[active] += 1
        self._tokens[active] = nxt[active]
        return nxt.tolist()

    def verify(self, active_slots, drafts, k: int) -> list[list[int]]:
        """Paged speculative verify window
        (``models.llama.paged_slot_verify_step``): the k+1 writes go
        through each slot's block table — the engine allocated the
        draft window's growth blocks up front (``ensure_block_for``
        per draft position), and positions past a slot's table route
        to the trash block, so a short window never clamps onto live
        blocks. Frontier state advances only via :meth:`commit_spec`
        (inherited) — reject is a pure ``cur`` non-advance, the
        misspeculated rows are garbage past the frontier."""
        if self.temperature > 0.0:
            raise ValueError("speculative verify is greedy-only "
                             f"(temperature {self.temperature:g} > 0)")
        tok_arr = jnp.asarray(self._verify_tokens(drafts, k))
        cur_arr = jnp.asarray(self._cur)
        pads_arr = jnp.asarray(self._pads)
        tables_arr = jnp.asarray(self.tables)
        GLOBAL_COMPILE_CACHE.note(
            "serve_verify_step",
            (_tree_sig((tok_arr, cur_arr, pads_arr, tables_arr)),
             _tree_sig(self.cache)))
        props, self.cache = self._guarded(
            L.paged_slot_verify_step, self.model, self.params,
            self.cache, tables_arr, tok_arr, cur_arr, pads_arr)
        return np.asarray(props).astype(np.int32).tolist()

    def release(self, slot: int):
        """Retire/evict/quarantine hook: drop every table reference
        (blocks return to the free list at refcount 0 — radix-cached
        ones stay resident on the trie's reference) and park the table
        on the trash block."""
        self.mgr.release(slot)
        self._cur[slot] = 0
        self._pads[slot] = 0
        self._tokens[slot] = 0

    def rebuild(self):
        """Failover hook (ISSUE 19): fresh pool (same ``_make_pool``
        hook the TP subclass shards), fresh block manager — allocator
        free list, radix trie and every table reference start from
        zero; the static pool facts (``mgr.info``) carry over."""
        info = self.mgr.info
        radix_on = self.mgr.radix is not None
        self.tables[:] = 0  # every row parks on the trash block
        self.mgr = PagedBlockManager(
            self.num_slots, self.max_len, self.block_size,
            self.pool_blocks, radix=radix_on,
            on_table=self._set_table, copy_block=self._copy_block)
        self.mgr.info = info
        self.allocator = self.mgr.allocator
        self.radix = self.mgr.radix
        self.cache = self._make_pool(self.model)
        self._tokens[:] = 0
        self._cur[:] = 0
        self._pads[:] = 0
        self._warned_commit = False


# ---------------------------------------------------------------------------
# Tensor-parallel slot backends (ISSUE 14): one engine spanning a mesh
# ---------------------------------------------------------------------------

# ONE definition of the placement knob (the launcher owns placement);
# scrub_serving_env and tp_mesh both ride it, so a rename cannot leave
# one surface reading (or scrubbing) a stale name.
from ..runner.launcher import TP_OFFSET_ENV  # noqa: E402


def tp_mesh(tp: int, devices=None):
    """``Mesh(('tp',))`` over ``tp`` devices starting at
    ``SPARKDL_TP_DEVICE_OFFSET`` (default 0) of the visible device list
    — the launcher's topology-aware placement sets the offset per rank
    so co-hosted engines claim disjoint device groups."""
    import jax as _jax
    devs = list(devices) if devices is not None else _jax.devices()
    raw = os.environ.get(TP_OFFSET_ENV, "0") or 0
    try:
        off = int(raw)
    except ValueError:
        # name the knob: a rank debugging a failed gang must see WHICH
        # env var was bad (the SPARKDL_SERVE_TP error convention)
        raise ValueError(
            f"{TP_OFFSET_ENV}={raw!r} is not an integer") from None
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    if off < 0 or off + tp > len(devs):
        raise ValueError(
            f"tp={tp} needs devices [{off}, {off + tp}) but only "
            f"{len(devs)} are visible (offset from {TP_OFFSET_ENV}; on "
            f"CPU force a bigger mesh with XLA_FLAGS="
            f"--xla_force_host_platform_device_count=N)")
    from jax.sharding import Mesh
    return Mesh(np.array(devs[off:off + tp]), ("tp",))


def _tp_setup(self, model, tp: int, mesh):
    """The whole tensor-parallel delta over the single-device backends
    (ISSUE 14 tentpole), half 1 — runs BEFORE ``super().__init__`` so
    the cache/pool allocation hooks see the mesh: validate the
    :func:`parallel.sharding.serving_tp_layout` SpecLayout against the
    model's head counts, build/adopt the ``Mesh(('tp',))``, derive the
    placement shardings, and pin dense in-model PREFILL attention (a
    pallas_call does not partition under GSPMD). The DECODE kernels are
    no longer lost to that constraint: ``kernel_mesh`` hands the mesh
    to the model, and the flash-decode / paged-flash-decode dispatch
    runs under ``shard_map`` over the head axis instead
    (``parallel.sharding.head_sharded_kernel`` — gated by
    ``SPARKDL_SERVE_TP_KERNEL``, auto = TPU only; the ISSUE 15 closure
    of ROADMAP item 3's kernel gap). The FOUR jitted donated-cache slot
    primitives
    (and their paged variants) then run UNCHANGED: GSPMD propagates
    the input shardings through every scatter/gather, keeps the cache
    head-sharded across donation, inserts the Megatron
    one-allreduce-per-block collectives, and hands back replicated
    logits/argmax — the jax-free scheduler (and ``PagedBlockManager``'s
    logical block ids) see exactly the single-device contract. No pjit
    wrapper, no re-implemented method; tp<=1 callers never construct
    these classes at all (``GenerationEngine.from_model`` routes tp<=1
    to the exact base classes — pinned by a signature-equality test)."""
    from jax.sharding import NamedSharding

    from ..parallel.sharding import serving_tp_layout
    layout = serving_tp_layout(tp, getattr(model, "cfg", None))
    self.tp_degree = int(tp)
    self.layout = layout
    self.mesh = mesh if mesh is not None else tp_mesh(tp)
    self._kv_sharding = NamedSharding(self.mesh, layout.kv_cache)
    self._replicated = NamedSharding(self.mesh, layout.replicated)
    # Pallas flash kernels do not partition under GSPMD: pin the dense
    # in-model attention for every sharded program (the "auto" default
    # would pick flash on TPU and fail to partition). Decode steps get
    # the kernels back via kernel_mesh — the model dispatches them
    # under shard_map over the head axis (ISSUE 15).
    return model.clone(attn_fn=None, kernel_mesh=self.mesh)


def _tp_finish(self):
    """The tensor-parallel delta, half 2 — runs AFTER
    ``super().__init__``: sharded weights loaded ONCE (device placement
    per the SpecLayout pattern rules, odd dims replicated via
    ``divisible_rules``), rng replicated."""
    from ..parallel.sharding import divisible_rules, shard_params
    self.params = shard_params(
        self.params, self.mesh,
        divisible_rules(self.layout.rules, self.mesh))
    self._rng = jax.device_put(self._rng, self._replicated)


class TensorParallelLlamaSlotBackend(LlamaSlotBackend):
    """Head-sharded :class:`LlamaSlotBackend` over a ``Mesh(('tp',))``
    (see the tensor-parallel section of the module doc): the slot cache
    leaves ``[slots, Hkv, max_len, hd]`` shard on ``Hkv``, q/k/v
    projections by head, MLP column-then-row, logits replicated — all
    four slot primitives run unchanged and per-device cache bytes are
    ``1/tp`` (:meth:`kv_pool_device_bytes`)."""

    def __init__(self, model, variables, num_slots: int, max_len: int, *,
                 tp: int, mesh=None, **kw):
        model = _tp_setup(self, model, tp, mesh)
        super().__init__(model, variables, num_slots, max_len, **kw)
        _tp_finish(self)

    def _make_cache(self, model):
        return L.init_cache(model, self.num_slots, self.max_len,
                            kv_sharding=self._kv_sharding,
                            scalar_sharding=self._replicated)


class TensorParallelPagedLlamaSlotBackend(PagedLlamaSlotBackend):
    """Head-sharded :class:`PagedLlamaSlotBackend`: every pool block
    ``[Hkv, block_size, hd]`` shards its ``Hkv`` axis over the tp mesh,
    so block ids stay LOGICAL (device-count-agnostic — the jax-free
    ``PagedBlockManager``, radix trie, CoW and preemption policy work
    verbatim) while each device holds ``1/tp`` of every block.
    ``kv_pool_mb`` is a PER-DEVICE budget: the block-count conversion
    divides a block's bytes by ``tp``, so a tp=4 engine holds 4× the KV
    of the single-device engine at the same per-chip memory."""

    def __init__(self, model, variables, num_slots: int, max_len: int, *,
                 tp: int, mesh=None, **kw):
        model = _tp_setup(self, model, tp, mesh)
        super().__init__(model, variables, num_slots, max_len, **kw)
        _tp_finish(self)

    def _pool_block_device_bytes(self, model) -> int:
        return max(1, pool_bytes_per_block(model, self.block_size,
                                           self.kv_dtype)
                   // self.tp_degree)

    def _make_pool(self, model):
        scale_sharding = None
        if self.kv_dtype is not None:
            # the kv_scale planes [pool, Hkv, 2] shard over the same
            # head axis as the codes they scale — each device holds its
            # heads' scales, and head_sharded_kernel feeds the kernel
            # matching shards.
            from jax.sharding import NamedSharding, PartitionSpec
            scale_sharding = NamedSharding(
                self.mesh, PartitionSpec(None, self.layout.axis, None))
        return L.init_paged_pool(model, self.pool_blocks, self.block_size,
                                 kv_sharding=self._kv_sharding,
                                 scalar_sharding=self._replicated,
                                 kv_quant=self.kv_dtype,
                                 scale_sharding=scale_sharding)
