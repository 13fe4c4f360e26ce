"""Continuously-batched inference engine built around fused decode megasteps.

A fixed number of decode SLOTS share one cache pytree (allocated once — the
cache, the weights, the per-slot decode state and the AOT-compiled
prefill/megastep executables together form the PCM *context*; see
repro.core.library). The execution model:

**Continuous admission.**  The engine never drains between waves: every
``step()`` first admits queued prefills into whatever slots are free —
slots freed by the *previous* megastep, including mid-megastep early exits
(the device loop breaks out as soon as a slot finishes while requests are
queued) — then runs one decode megastep for the now-larger active set.  A
request arriving against a busy engine therefore waits at most one
megastep (≤ K tokens) before its prefill launches, not for the current
batch to finish.  Greedy outputs are bit-identical regardless of what
shares the batch (see ``test_batching_invariance``), so continuous
admission changes *when* requests run, never *what* they generate, and it
reuses the same AOT executables — zero extra compiles.
``admission="drain"`` keeps the legacy drain-between-waves behaviour (all
active slots run to completion before the next wave admits); it exists as
the measured baseline for the front-door benchmark, not for serving.

**Admission order.**  ``submit`` maintains a priority queue: a request with
higher ``Request.priority`` (e.g. an interactive-SLO session turn from the
front door) is inserted ahead of lower-priority queued work — it preempts
*admission order only*, never a running decode; slots already decoding are
untouched.  FIFO within a priority class.

**Token streaming.**  A request's ``on_token`` callback fires once per
generated token, in order, from the engine's existing host sync points
(the per-wave first-token sync and the one-per-megastep block sync) — so
streaming costs zero extra device syncs.  Callbacks run on the engine's
thread: they must be cheap and never raise (exceptions are swallowed and
reported to stderr; the stream, not the engine, is what breaks).

**What is resident in a context.**  Everything the steady-state loop needs
lives on device for the lifetime of the engine: the weights, the slot
cache, the per-slot decode state (``lengths``, ``last_tokens``, ``temps``,
``active``, generated-token counts, per-slot stop-token tables, the RNG
key) and the compiled executables themselves.  Materializing the engine
inside a PCM context (``repro.core.context.materialize``) AOT-compiles the
megastep and every prefill-bucket executable up front, so a warm context
performs **zero** compiles — ``compile_seconds`` measures the real one-time
cost and ``stats.compiles`` counts cache misses (expected 0 after warm-up).

**The megastep.**  Instead of one jitted dispatch per token, ``step()``
launches a single fused ``lax.while_loop`` that generates up to
``megastep=K`` tokens per dispatch.  The loop carries (cache, lengths,
last_tokens, active, counts, rng) entirely on device; a per-slot *active
mask* keeps free/finished slots inert: their cache rows are provably
unchanged (see ``kvcache.select_slots``), they sample nothing, and —
because freed slots' device lengths are zeroed at megastep end —
length-masked attention reduces to a single masked position for them.
Stop-token / max-new-tokens / cache-overflow detection runs on device, so
a slot that finishes mid-megastep stops sampling and advancing immediately
(its residual attention work lasts only until that megastep returns); the
loop also exits early when every slot is done,
or when a slot frees up while requests are queued (so admission latency is
bounded by the work actually done, not by K).

**When the host syncs.**  Once per megastep: the device returns a
``(slots, K)`` token block plus per-slot produced counts and the active
mask, and the host unpacks K tokens per slot in one transfer — versus one
blocking ``np.asarray`` per token in the per-token loop.  Prefill waves
sync once per wave (first token + immediately-done flags); all other
state stays on device.

**How K trades latency for throughput.**  K=1 is bit-exact with the
classic per-token loop (greedy outputs are identical for every K — decode
math is unchanged, only dispatch granularity moves).  Larger K amortizes
Python/dispatch/host-sync overhead over K tokens, multiplying steady-state
decode throughput, at the cost of admitting queued requests at megastep
(≤ K token) granularity instead of every token.

Prefill waves are padded to the full slot count, and prefill + scatter
into the *donated* global cache run fused in a single dispatch (the
transient wave buffer lives only inside that executable — no separate
host-driven merge step), so there is exactly one prefill executable per
bucket length — all AOT-warmable.

**Paged KV storage (``paged=True``).**  For families whose cache leaves
keep the sequence axis right after the batch axis (dense/MoE full
attention, MLA latents), the slot cache can be replaced by a shared pool
of fixed-size pages behind a per-slot page table (``repro.serving.paged``).
A request reserves ``ceil(min(prompt+max_new, cache_len)/page_size)``
pages at admission — host-side free list, so decode never allocates on
device — grows into them as it decodes, and releases them when it
finishes: concurrent sessions are bounded by live tokens, not
slots x cache_len. Prefill waves still compile to one executable per
bucket (the wave prefills a transient ``ceil(bucket/P)``-page contiguous
cache, scattered into the pool through the freshly reserved tables in the
same dispatch); megasteps specialize on a power-of-two *page-count* bucket
(subsuming the contiguous path's prefix view) and route through
``model.decode_paged`` — the Pallas paged-decode kernels when
``cfg.use_kernels``, else a gather-to-contiguous view whose math is
bit-identical to the slot cache. Free/finished slots write only to the
pool's TRASH page, so live pages are provably untouched by non-owners and
the slot path's post-loop select/restore pass disappears. Families whose
state does not page (SSM/xLSTM, sliding-window ring buffers) silently keep
the slot cache; ``paged_fallback`` records why. Snapshots serialize only
live pages, so every tier/peer-transfer rung shrinks with actual context.

**Tier offload/restore (PCM snapshot hooks).**  The concurrent PCM runtime
demotes idle/preempted contexts off the accelerator:
``offload_device_state()`` pulls the whole device-resident tuple (weights,
slot cache, decode state, RNG) to host numpy in one ``jax.device_get`` and
drops the device references; ``restore_device_state()`` pushes it back in
one ``jax.device_put``. The AOT executable cache stays attached to the
engine object across the round trip, so a restored engine performs ZERO
builder calls and ZERO XLA compiles and decodes bit-identically — restore
cost is the transfer, which is the paper's entire point.
"""

from __future__ import annotations

import base64
import collections
import functools
import hashlib
import json
import os
import pickle
import sys
import threading
import time
import traceback
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import cdt
from repro.models.transformer import Model
from repro.serving import kvcache
from repro.serving import paged as paging
from repro.serving.request import EngineStats, Request, RequestState
from repro.serving.sampler import sample

NO_TOKEN = -1  # stop-table padding: never matches a real (>= 0) token id

# ---------------------------------------------------------------------------
# AOTRecipe executable cache — the ONE warm-start codepath.
#
# Executable objects never travel between engines by pointer anymore:
# every true compile publishes into this process-wide cache keyed by
# (engine AOT fingerprint, executable key), and engines built as transfer
# receivers (in-process clones AND wire-reconstructed shells — both carry
# ``_aot_shared=True``) resolve their executables here, falling back to an
# optional on-disk cache of ``jax.experimental.serialize_executable``
# payloads shared across OS processes. A hit counts under
# ``stats.aot_cache_hits``; only a genuine XLA lowering+compile counts
# under ``stats.compiles`` — which is what keeps the zero-recompile
# guarantee assertable over the wire.
#
# A loaded executable is bound to one device, so the fingerprint names the
# device (``<portable>@<device id>``). The portable half names everything
# else, the device kind included: a single-device program compiled for one
# chip is the same binary on every chip of its kind, so a payload — from
# disk, or serialized from an executable another device loaded — is
# loaded onto the receiver's own device with only its device assignment
# changed. A receiver on another chip thus hits instead of compiling.
# ---------------------------------------------------------------------------
_AOT_EXES: "collections.OrderedDict[Tuple[str, str], Callable]" = \
    collections.OrderedDict()
_AOT_EXES_MAX = 512
_AOT_LOCK = threading.Lock()
_AOT_CACHE_DIR: Optional[str] = None


def set_aot_cache_dir(path: Optional[str]) -> Optional[str]:
    """Point the cross-process executable cache at ``path`` (None disables
    it). Returns the previous setting. ``repro.launch.compile_cache`` sets
    it beside JAX's persistent cache; worker node processes are pointed at
    the same directory via ``--aot-cache`` so a receiver re-lowers into a
    cache hit instead of compiling."""
    global _AOT_CACHE_DIR
    prev = _AOT_CACHE_DIR
    _AOT_CACHE_DIR = path
    return prev


def _portable(fingerprint: str) -> str:
    return fingerprint.partition("@")[0]


def _aot_disk_file(fingerprint: str, key: str) -> Optional[str]:
    if _AOT_CACHE_DIR is None:
        return None
    name = hashlib.sha256(
        f"{_portable(fingerprint)}|{key}".encode()).hexdigest()[:40]
    return os.path.join(_AOT_CACHE_DIR, f"{name}.pcmexe")


def _load_on(payload, device):
    """Load a ``serialize_executable`` payload onto ``device``, whichever
    device of the same kind it was compiled for."""
    import io
    from jax._src import compiler
    from jax.experimental import serialize_executable as se

    class _OnDevice(se._JaxPjrtUnpickler):
        def persistent_load(self, pid):
            if pid[0] == "device":
                return device
            if pid[0] == "exec":
                opts = compiler.get_compile_options(
                    num_replicas=1, num_partitions=1,
                    device_assignment=np.array([[device.id]]))
                return self.backend.deserialize_executable(
                    pid[1], executable_devices=self.execution_devices,
                    compile_options=opts)
            return super().persistent_load(pid)

    serialized, in_tree, out_tree = payload
    unloaded, args_info, no_kwargs = _OnDevice(
        io.BytesIO(serialized), device.client, [device]).load()
    return jax.stages.Compiled(unloaded.load(), [],
                               in_tree.unflatten(args_info), out_tree,
                               no_kwargs=no_kwargs)


def _aot_remember(ck: Tuple[str, str], exe):
    with _AOT_LOCK:
        _AOT_EXES[ck] = exe
        while len(_AOT_EXES) > _AOT_EXES_MAX:
            _AOT_EXES.popitem(last=False)


def _aot_cache_lookup(fingerprint: str, key: str,
                      device) -> Optional[Callable]:
    """Process-dict hit for this device first; then a payload of the same
    portable program — the serialized on-disk file, or one serialized from
    an executable another device of this process loaded — loaded onto
    ``device``. A file that cannot be read back (torn write, foreign
    pickle) is a miss — the caller compiles for real and republishes — but
    a payload that fails to load raises: loading rests on JAX's own
    serialization internals, and a break there must not hide as extra
    compiles."""
    ck = (fingerprint, key)
    portable = _portable(fingerprint)
    with _AOT_LOCK:
        exe = _AOT_EXES.get(ck)
        if exe is not None:
            _AOT_EXES.move_to_end(ck)
            return exe
        sibling = next((e for (fp, k), e in _AOT_EXES.items()
                        if k == key and _portable(fp) == portable), None)
    path = _aot_disk_file(fingerprint, key)
    payload = None
    if path is not None and os.path.exists(path):
        try:
            with open(path, "rb") as f:
                payload = pickle.load(f)
        except Exception:
            payload = None
    if payload is None and sibling is not None:
        from jax.experimental import serialize_executable as se
        payload = se.serialize(sibling)
    if payload is None:
        return None
    exe = _load_on(payload, device)
    _aot_remember(ck, exe)
    return exe


def _aot_cache_publish(fingerprint: str, key: str, exe):
    """Record a freshly compiled executable: always into the process dict
    (in-process clones hit it), and — when a cache dir is configured —
    atomically onto disk so OTHER processes re-lower into a hit."""
    _aot_remember((fingerprint, key), exe)
    path = _aot_disk_file(fingerprint, key)
    if path is None or os.path.exists(path):
        return
    try:
        from jax.experimental import serialize_executable as se
        payload = se.serialize(exe)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "wb") as f:
            pickle.dump(payload, f)
        os.replace(tmp, path)
    except Exception:
        # disk publication is best-effort: a receiver that misses simply
        # pays one true compile (and is counted doing so)
        pass


def current_device():
    """The device JAX places new arrays on in this thread: the
    ``jax.default_device`` in force (a live PCM worker runs its whole
    thread under its own device), else the first device."""
    dev = jax.config.jax_default_device
    if dev is None or isinstance(dev, str):
        return jax.devices(dev)[0]
    return dev


def _bucket(n: int, buckets: Sequence[int]) -> int:
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"prompt length {n} exceeds the largest prefill bucket "
                     f"({buckets[-1]}) — prompts must never be silently "
                     f"truncated")


class InferenceEngine:
    # True on engines built as transfer receivers (clones, wire shells):
    # their executables resolve through the AOTRecipe cache. Fresh engines
    # stay False and always compile for real — keeps cold baselines cold.
    _aot_shared = False

    def __init__(self, model: Model, params, *, slots: int = 8,
                 cache_len: int = 512,
                 prefill_buckets: Sequence[int] = (32, 128, 512),
                 cache_dtype=None, rng_seed: int = 0,
                 extra: Optional[Dict] = None,
                 donate_cache: bool = True,
                 megastep: int = 1,
                 decode_buckets: Optional[Sequence[int]] = None,
                 max_stop_tokens: int = 4,
                 admission: str = "continuous",
                 paged: bool = False,
                 page_size: int = 64,
                 num_pages: Optional[int] = None,
                 prefix_sharing: bool = True):
        if admission not in ("continuous", "drain"):
            raise ValueError(f"admission must be 'continuous' or 'drain', "
                             f"got {admission!r}")
        self.admission = admission
        self._donate_cache = bool(donate_cache)
        self.model = model
        self.cfg = model.cfg
        # the device this engine's state and executables live on: where
        # its weights are, else where this thread places new arrays
        arrays = [leaf for leaf in jax.tree_util.tree_leaves(params)
                  if isinstance(leaf, jax.Array)]
        self.device = (next(iter(arrays[0].devices())) if arrays
                       else current_device())
        if cache_dtype is None:
            cache_dtype = jnp.dtype(self.cfg.kv_cache_dtype)
        self.params = params
        self.slots = slots
        self.cache_len = cache_len
        # auto-extend buckets to cache_len: every admissible prompt
        # (submit() enforces len <= cache_len) gets a bucket that holds it
        # whole — over-long prompts raise instead of silently truncating.
        self.prefill_buckets = tuple(sorted(
            set(min(b, cache_len) for b in prefill_buckets) | {cache_len}))
        self.extra = extra
        self.megastep = int(megastep)
        if self.megastep < 1:
            raise ValueError(f"megastep must be >= 1, got {megastep}")
        self.max_stop_tokens = max_stop_tokens

        # ---- paged-vs-contiguous storage resolution --------------------
        # paged=True is a REQUEST: families whose state does not page fall
        # back to the contiguous slot cache silently, recording why — so
        # callers can flip one flag fleet-wide and SSM/xLSTM/SWA engines
        # keep working unchanged.
        self.page_size = int(page_size)
        if paged and self.page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self._paged = False
        self.paged_fallback: Optional[str] = None
        if paged:
            if model.decode_paged is None:
                self.paged_fallback = (
                    "model has no paged decode path (SSM/xLSTM state and "
                    "sliding-window ring buffers keep the slot cache)")
            elif cache_len <= 8:
                self.paged_fallback = "cache_len too small to page"
            else:
                bax = kvcache.batch_axes(model.init_cache, cache_len,
                                         cache_dtype)
                sax = kvcache.seq_axes(model.init_cache, slots, cache_len,
                                       cache_dtype)
                if not paging.pageable(bax, sax):
                    self.paged_fallback = (
                        "cache leaves are not (batch, seq)-adjacent or do "
                        "not scale with cache_len")
                else:
                    self._paged = True

        if self._paged:
            # the physical pool is the model's own cache pytree built at
            # (num_pages + 1, page_size): page axis where the batch axis
            # was, +1 TRASH page absorbing every masked write. Default
            # num_pages matches the slot cache's capacity exactly — same
            # HBM, but admission is bounded by live tokens so far more
            # sessions fit when contexts are short.
            self.max_pages = -(-cache_len // self.page_size)
            self.num_pages = (int(num_pages) if num_pages is not None
                              else slots * self.max_pages)
            self.trash = self.num_pages
            self._alloc = paging.PageAllocator(self.num_pages,
                                               self.page_size)
            self.cache = model.init_cache(self.num_pages + 1,
                                          self.page_size, cache_dtype)
            self.page_table = jnp.full((slots, self.max_pages), self.trash,
                                       jnp.int32)
            bks, b = {self.max_pages}, 1
            while b < self.max_pages:
                bks.add(b)
                b *= 2
            self._page_buckets = tuple(sorted(bks))
        else:
            self.cache = model.init_cache(slots, cache_len, cache_dtype)
            self.page_table = None
        self._cache_dtype = jax.tree_util.tree_leaves(self.cache)[0].dtype
        self._axes = kvcache.batch_axes(model.init_cache, cache_len,
                                        cache_dtype)

        # ---- page-level prefix-sharing resolution ----------------------
        # prefix_sharing=True is likewise a REQUEST, resolved only on the
        # paged path: sharing is a page-table aliasing trick, so it needs
        # the table, a model whose tail-only prefill is exact
        # (non-MoE/MLA/SWA — see Model.prefill_shared), a cache dtype that
        # doesn't round the compute dtype (gathered prefix KV must be
        # bitwise what a full prefill would have produced), and a page
        # size dividing the 1024-token blockwise-attention chunk (shared
        # and full prefills then pad to identical chunk boundaries).
        self._prefix_cache: Optional[paging.PrefixCache] = None
        self.prefix_fallback: Optional[str] = None
        if paged and prefix_sharing:
            if not self._paged:
                self.prefix_fallback = "engine is not paged: " + (
                    self.paged_fallback or "")
            elif getattr(model, "prefill_shared", None) is None:
                self.prefix_fallback = (
                    "model has no shared-prefix prefill (MoE capacity "
                    "dropping and MLA recompression are "
                    "sequence-dependent; SWA does not page)")
            elif (np.dtype(self._cache_dtype)
                  != np.dtype(jax.dtypes.canonicalize_dtype(cdt(self.cfg)))):
                self.prefix_fallback = (
                    "cache dtype differs from the compute dtype — shared "
                    "prefix KV would round where a full prefill would not")
            elif 1024 % self.page_size:
                self.prefix_fallback = (
                    f"page_size {self.page_size} does not divide the "
                    f"1024-token attention chunk")
            else:
                self._prefix_cache = paging.PrefixCache(self.page_size)
        elif paged:
            self.prefix_fallback = "disabled (prefix_sharing=False)"
        # length-bounded decode: megasteps run on a bucketed cache PREFIX
        # sized from host-tracked lengths, so per-token work scales with
        # the live context, not allocated capacity. Only decoder-only
        # full-attention families qualify (ring buffers address the cache
        # modulo its physical size, so a sliced view changes semantics).
        # use_kernels is excluded: the Pallas decode kernel's block size
        # depends on the cache size it sees, so mixing prefix-view sizes
        # across K could change its summation order and break the cross-K
        # greedy bit-parity guarantee. The paged path
        # subsumes the prefix view entirely (page-count buckets).
        prefixable = (not self._paged
                      and getattr(self.cfg, "family", "") in ("dense", "moe")
                      and not getattr(self.cfg, "sliding_window", 0)
                      and not getattr(self.cfg, "use_kernels", False)
                      and cache_len > 16)
        if not prefixable:
            self.decode_buckets = (cache_len,)
        elif decode_buckets is not None:
            self.decode_buckets = tuple(sorted(
                set(min(b, cache_len) for b in decode_buckets)
                | {cache_len}))
        else:
            bks, b = {cache_len}, min(64, cache_len)
            while b < cache_len:
                bks.add(b)
                b *= 2
            self.decode_buckets = tuple(sorted(bks))
        self._seq_axes = (kvcache.seq_axes(model.init_cache, slots,
                                           cache_len, cache_dtype)
                          if len(self.decode_buckets) > 1 else None)
        self._host_lengths = np.zeros((slots,), np.int64)
        # per-slot decode state: device-resident, synced to host only at
        # megastep/wave boundaries
        self.lengths = jnp.zeros((slots,), jnp.int32)
        self.last_tokens = jnp.zeros((slots,), jnp.int32)
        self.temps = jnp.zeros((slots,), jnp.float32)
        self.active_mask = jnp.zeros((slots,), bool)
        self.gen_counts = jnp.zeros((slots,), jnp.int32)
        self.max_news = jnp.zeros((slots,), jnp.int32)
        self.stop_table = jnp.full((slots, max_stop_tokens), NO_TOKEN,
                                   jnp.int32)
        self._rng = jax.random.PRNGKey(rng_seed)

        self.queue: collections.deque = collections.deque()
        self.active: Dict[int, Request] = {}          # slot -> request
        self.free_slots: collections.deque = collections.deque(range(slots))
        self.stats = EngineStats(decode_path=(
            "paged" if self._paged
            else "prefix-bucket" if (len(self.decode_buckets) > 1
                                     and self.megastep >= 4)
            else "full"))
        self.compile_seconds = 0.0
        # seq-axes tree for the contiguous live-bytes estimate (lazy
        # prerequisite: seq_axes needs cache_len > 8)
        self._byte_axes = self._seq_axes
        if not self._paged and self._byte_axes is None and cache_len > 8:
            self._byte_axes = kvcache.seq_axes(model.init_cache, slots,
                                               cache_len, cache_dtype)

        self._megastep_jits: Dict[Tuple, Callable] = {}  # spec -> jitted
        if self._paged:
            # page_table rides at arg 1 and is NOT donated in the megastep
            # (reused across dispatches); prefill donates it (returned
            # updated with the wave's fresh rows)
            self._mega_donate = (2, 3, 4, 6, 7, 10) if donate_cache else ()
            pre_donate = tuple(range(9, 19)) if donate_cache else ()
            self._prefill_jit = jax.jit(self._paged_prefill_impl,
                                        donate_argnums=pre_donate)
            self._DEVICE_STATE_FIELDS = (
                InferenceEngine._DEVICE_STATE_FIELDS + ("page_table",))
            if self._prefix_cache is not None:
                self._shared_prefill_jit = jax.jit(
                    self._shared_prefill_impl,
                    donate_argnums=(tuple(range(12, 22)) if donate_cache
                                    else ()))
                self._cow_jit = jax.jit(
                    self._copy_pages_impl,
                    donate_argnums=(0, 1) if donate_cache else ())
        else:
            self._mega_donate = (1, 2, 3, 5, 6, 9) if donate_cache else ()
            pre_donate = (8, 9, 10, 11, 12, 13, 14, 15, 16) if donate_cache \
                else ()
            self._prefill_jit = jax.jit(self._prefill_impl,
                                        donate_argnums=pre_donate)
        self._exe: Dict[Tuple, Callable] = {}         # AOT executables
        if params is not None:
            for name in self._DEVICE_STATE_FIELDS:
                setattr(self, name, jax.device_put(getattr(self, name),
                                                   self.device))

    # ------------------------------------------------------------- jitted --
    def _prefill_impl(self, params, tokens, lens, slot_ids, valid,
                      wave_temps, wave_max_new, wave_stops,
                      cache, lengths, last_tokens, temps, active,
                      gen_counts, max_news, stop_table, rng):
        """Prefill a (slots, bucket) wave straight into the donated slot
        cache and per-slot state. ``slot_ids`` is a permutation of the slot
        indices; ``valid`` masks the rows that carry real requests (padding
        rows write their slots back unchanged)."""
        rng, k = jax.random.split(rng)
        wave_cache = self.model.init_cache(self.slots, self.cache_len,
                                           self._cache_dtype)
        logits, wave_cache = self.model.prefill(params, tokens, lens,
                                                wave_cache, extra=self.extra)
        toks = sample(logits, k, wave_temps, vocab_size=self.cfg.vocab_size,
                      active=valid)
        cache = kvcache.merge_slots(cache, wave_cache, slot_ids, self._axes,
                                    valid=valid)
        # on-device done detection for the first token (mirrors the
        # megastep): stop token, max_new_tokens==1, or a prompt that
        # already fills the cache
        stopped = jnp.any(toks[:, None] == wave_stops, axis=1)
        full = wave_max_new <= 1
        over = lens >= self.cache_len - 1
        row_active = valid & ~(stopped | full | over)

        def scat(dst, src):
            keep = valid.reshape((-1,) + (1,) * (src.ndim - 1))
            return dst.at[slot_ids].set(
                jnp.where(keep, src.astype(dst.dtype), dst[slot_ids]))

        lengths = scat(lengths, lens)
        last_tokens = scat(last_tokens, toks)
        temps = scat(temps, wave_temps)
        active = scat(active, row_active)
        gen_counts = scat(gen_counts, jnp.where(valid, 1, 0))
        max_news = scat(max_news, wave_max_new)
        stop_table = scat(stop_table, wave_stops)
        return (toks, row_active, cache, lengths, last_tokens, temps,
                active, gen_counts, max_news, stop_table, rng)

    def _megastep_impl(self, params, cache, lengths, last_tokens, temps,
                       active, gen_counts, max_news, stop_table, rng,
                       has_queue, *, prefix: int, restore: bool):
        """Generate up to ``megastep`` tokens in one dispatch.

        Decode runs on a ``prefix``-bounded cache view (the host guarantees
        no active slot can write past it during this megastep), so
        per-token work scales with the live context length. The while_loop
        exits early when no slot is active, or when a slot freed up while
        the host has queued requests (so waiting work is admitted
        promptly). Inactive slots are masked in the carried vectors each
        iteration and their cache rows restored in ONE select after the
        loop — zero per-token masking cost. Returns the new carried state
        plus a (slots, K) token block and per-slot produced counts — the
        host's single sync point."""
        K = self.megastep
        B = self.slots
        entry_active = active
        full_cache = cache
        view = (kvcache.slice_prefix(cache, prefix, self._seq_axes)
                if prefix < self.cache_len else cache)
        # the free-slot restore needs the entry rows kept alive across the
        # loop (an extra cache copy at full prefix) — only specialized in
        # when the host reports free slots
        entry_view = view if restore else None

        def cond(c):
            step, _, _, _, act, _, _, _, _ = c
            freed = jnp.any(entry_active & ~act)
            return (step < K) & jnp.any(act) & ~(has_queue & freed)

        def body(c):
            step, view, lengths, last, act, gen, rng, block, produced = c
            rng, k = jax.random.split(rng)
            logits, view = self.model.decode_step(
                params, last[:, None], lengths, view, extra=self.extra)
            toks = sample(logits, k, temps, vocab_size=self.cfg.vocab_size,
                          active=act, fallback=last)
            lengths = jnp.where(act, lengths + 1, lengths)
            gen = jnp.where(act, gen + 1, gen)
            block = jax.lax.dynamic_update_slice_in_dim(
                block, jnp.where(act, toks, 0)[:, None], step, axis=1)
            produced = produced + act.astype(jnp.int32)
            stopped = jnp.any(toks[:, None] == stop_table, axis=1)
            full = gen >= max_news
            over = lengths >= self.cache_len - 1
            act = act & ~(stopped | full | over)
            return (step + 1, view, lengths, toks, act, gen, rng, block,
                    produced)

        init = (jnp.int32(0), view, lengths, last_tokens, active,
                gen_counts, rng, jnp.zeros((B, K), jnp.int32),
                jnp.zeros((B,), jnp.int32))
        (_, view, lengths, last, active, gen, rng, block,
         produced) = jax.lax.while_loop(cond, body, init)
        # zero finished/free slots' lengths so subsequent megasteps attend
        # over a single masked position for them instead of their stale
        # full context (admission rewrites lengths; the host tracks real
        # lengths in its own shadow)
        lengths = jnp.where(active, lengths, 0)
        # one post-loop select: slots inactive at entry (free slots) keep
        # their entry cache rows bit-for-bit; slots that finished mid-loop
        # only ever wrote to dead positions at/past their final length.
        if restore:
            view = kvcache.select_slots(entry_view, view, entry_active,
                                        self._axes)
        cache = (kvcache.write_prefix(full_cache, view, self._seq_axes)
                 if prefix < self.cache_len else view)
        return cache, lengths, last, active, gen, rng, block, produced

    def _paged_prefill_impl(self, params, tokens, lens, slot_ids, valid,
                            wave_temps, wave_max_new, wave_stops, pt_rows,
                            page_table, cache, lengths, last_tokens, temps,
                            active, gen_counts, max_news, stop_table, rng):
        """Paged twin of ``_prefill_impl``: the wave prefills a transient
        contiguous cache of ``ceil(bucket/P)`` pages, which is scattered
        page-by-page into the donated pool through each row's freshly
        reserved table (``pt_rows``: full (slots, max_pages) rows,
        unreserved columns and padding rows aimed at TRASH), and the slot
        page table is updated — all in the same dispatch. Still exactly one
        executable per prefill bucket."""
        rng, k = jax.random.split(rng)
        P = self.page_size
        wn = -(-tokens.shape[1] // P)
        wave_cache = self.model.init_cache(self.slots, wn * P,
                                           self._cache_dtype)
        logits, wave_cache = self.model.prefill(params, tokens, lens,
                                                wave_cache, extra=self.extra)
        toks = sample(logits, k, wave_temps, vocab_size=self.cfg.vocab_size,
                      active=valid)
        cache = paging.scatter_view(
            cache, wave_cache, jax.lax.slice_in_dim(pt_rows, 0, wn, axis=1),
            self._axes, valid=valid, trash=self.trash)
        page_table = page_table.at[slot_ids].set(
            jnp.where(valid[:, None], pt_rows, page_table[slot_ids]))
        stopped = jnp.any(toks[:, None] == wave_stops, axis=1)
        full = wave_max_new <= 1
        over = lens >= self.cache_len - 1
        row_active = valid & ~(stopped | full | over)

        def scat(dst, src):
            keep = valid.reshape((-1,) + (1,) * (src.ndim - 1))
            return dst.at[slot_ids].set(
                jnp.where(keep, src.astype(dst.dtype), dst[slot_ids]))

        lengths = scat(lengths, lens)
        last_tokens = scat(last_tokens, toks)
        temps = scat(temps, wave_temps)
        active = scat(active, row_active)
        gen_counts = scat(gen_counts, jnp.where(valid, 1, 0))
        max_news = scat(max_news, wave_max_new)
        stop_table = scat(stop_table, wave_stops)
        return (toks, row_active, page_table, cache, lengths, last_tokens,
                temps, active, gen_counts, max_news, stop_table, rng)

    def _shared_prefill_impl(self, params, tokens, lens, starts, slot_ids,
                             valid, wave_temps, wave_max_new, wave_stops,
                             start_pages, pt_src, pt_dst, page_table, cache,
                             lengths, last_tokens, temps, active, gen_counts,
                             max_news, stop_table, rng):
        """Prefix-sharing twin of ``_paged_prefill_impl``: ``tokens`` holds
        only each row's unshared TAIL (prompt[starts:]), bucketed on tail
        length. The row's full page view is gathered through ``pt_src``
        (shared prefix pages resident, private columns don't matter yet),
        the model computes KV for the tail only and merges it into the
        view at each row's offset, and the merged view scatters back
        through ``pt_dst`` restricted to columns >= ``start_pages`` — so
        shared pages are READ, never written. When a hit ends mid-page the
        boundary column differs between the two tables (src = the shared
        original, dst = a fresh private page): the copy-on-write copy is
        the scatter itself, fused into this dispatch. Cold rows ride the
        same executable with starts == 0 and pt_src == pt_dst, computing
        exactly what ``_paged_prefill_impl`` would — one executable per
        TAIL bucket covers mixed hit/cold waves."""
        rng, k = jax.random.split(rng)
        view = paging.gather_view(cache, pt_src, self._axes)
        logits, merged = self.model.prefill_shared(params, tokens, lens,
                                                   starts, view,
                                                   extra=self.extra)
        toks = sample(logits, k, wave_temps, vocab_size=self.cfg.vocab_size,
                      active=valid)
        cols = jnp.arange(self.max_pages, dtype=jnp.int32)[None, :]
        dest = jnp.where(cols >= start_pages[:, None], pt_dst, self.trash)
        cache = paging.scatter_view(cache, merged, dest, self._axes,
                                    valid=valid, trash=self.trash)
        page_table = page_table.at[slot_ids].set(
            jnp.where(valid[:, None], pt_dst, page_table[slot_ids]))
        stopped = jnp.any(toks[:, None] == wave_stops, axis=1)
        full = wave_max_new <= 1
        over = lens >= self.cache_len - 1
        row_active = valid & ~(stopped | full | over)

        def scat(dst, src):
            keep = valid.reshape((-1,) + (1,) * (src.ndim - 1))
            return dst.at[slot_ids].set(
                jnp.where(keep, src.astype(dst.dtype), dst[slot_ids]))

        lengths = scat(lengths, lens)
        last_tokens = scat(last_tokens, toks)
        temps = scat(temps, wave_temps)
        active = scat(active, row_active)
        gen_counts = scat(gen_counts, jnp.where(valid, 1, 0))
        max_news = scat(max_news, wave_max_new)
        stop_table = scat(stop_table, wave_stops)
        return (toks, row_active, page_table, cache, lengths, last_tokens,
                temps, active, gen_counts, max_news, stop_table, rng)

    def _copy_pages_impl(self, page_table, cache, src, dst, rows, cols,
                         valid):
        """Device half of a decode-append copy-on-write: copy whole pages
        ``src[i] -> dst[i]`` in every cache leaf and repoint
        ``page_table[rows[i], cols[i]]`` at ``dst[i]`` — one dispatch for
        up to ``slots`` copies. Padding entries aim src and dst at TRASH
        (a value-preserving self-copy) and rewrite their table cell with
        its current value; the host guarantees (rows, cols) pairs are
        distinct so the scatter has no write races."""
        cache = paging.copy_pages(cache, src, dst, self._axes)
        cur = page_table[rows, cols]
        page_table = page_table.at[rows, cols].set(
            jnp.where(valid, dst, cur))
        return page_table, cache

    def _paged_megastep_impl(self, params, page_table, cache, lengths,
                             last_tokens, temps, active, gen_counts,
                             max_news, stop_table, rng, has_queue, *,
                             npages: int):
        """Paged twin of ``_megastep_impl``, addressed through a
        ``npages``-column slice of the table (the page-count bucket plays
        the contiguous path's prefix role — per-token work scales with live
        pages). Two routes share the loop:

        * ``cfg.use_kernels``: every token decodes through
          ``model.decode_paged`` — the Pallas kernels read K/V pages in
          place via scalar-prefetched page tables, no materialized view.
        * fallback: the pages are gathered into a contiguous view ONCE,
          the loop runs the same ``decode_step`` the slot cache uses, and
          the touched pages are scattered back ONCE — page traffic is
          amortized over the whole megastep instead of paid per token.

        No post-loop select/restore pass either way: rows inactive at
        entry scatter only to the TRASH page (fallback) or write through
        TRASH-aimed tables (kernel route), so live pages are untouched by
        construction."""
        K = self.megastep
        B = self.slots
        entry_active = active
        view_pt = (jax.lax.slice_in_dim(page_table, 0, npages, axis=1)
                   if npages < self.max_pages else page_table)
        gathered = not self.cfg.use_kernels
        carry = (paging.gather_view(cache, view_pt, self._axes)
                 if gathered else cache)

        def cond(c):
            step, _, _, _, act, _, _, _, _ = c
            freed = jnp.any(entry_active & ~act)
            return (step < K) & jnp.any(act) & ~(has_queue & freed)

        def body(c):
            step, pages, lengths, last, act, gen, rng, block, produced = c
            rng, k = jax.random.split(rng)
            if gathered:
                logits, pages = self.model.decode_step(
                    params, last[:, None], lengths, pages, extra=self.extra)
            else:
                logits, pages = self.model.decode_paged(
                    params, last[:, None], lengths, pages, view_pt, act,
                    extra=self.extra)
            toks = sample(logits, k, temps, vocab_size=self.cfg.vocab_size,
                          active=act, fallback=last)
            lengths = jnp.where(act, lengths + 1, lengths)
            gen = jnp.where(act, gen + 1, gen)
            block = jax.lax.dynamic_update_slice_in_dim(
                block, jnp.where(act, toks, 0)[:, None], step, axis=1)
            produced = produced + act.astype(jnp.int32)
            stopped = jnp.any(toks[:, None] == stop_table, axis=1)
            full = gen >= max_news
            over = lengths >= self.cache_len - 1
            act = act & ~(stopped | full | over)
            return (step + 1, pages, lengths, toks, act, gen, rng, block,
                    produced)

        init = (jnp.int32(0), carry, lengths, last_tokens, active,
                gen_counts, rng, jnp.zeros((B, K), jnp.int32),
                jnp.zeros((B,), jnp.int32))
        (_, carry, lengths, last, active, gen, rng, block,
         produced) = jax.lax.while_loop(cond, body, init)
        lengths = jnp.where(active, lengths, 0)
        if gathered:
            # rows inactive at entry (free slots, stale tables) land in
            # TRASH; active rows write back exactly their own pages
            cache = paging.scatter_view(cache, carry, view_pt, self._axes,
                                        valid=entry_active,
                                        trash=self.trash)
        else:
            cache = carry
        return cache, lengths, last, active, gen, rng, block, produced

    # ---------------------------------------------------- executables/AOT --
    def _get_exe(self, key: Tuple, jitfn, *args):
        """Layered AOT executable resolution. Own cache first; then — for
        ``_aot_shared`` engines only (clones and wire-reconstructed
        shells) — the AOTRecipe cache (process dict, then serialized disk
        payloads), counted under ``stats.aot_cache_hits``; else a true
        XLA lowering+compile, counted under ``stats.compiles`` and
        published back into the recipe cache. The split is what makes
        "zero true recompiles" assertable across process boundaries."""
        exe = self._exe.get(key)
        if exe is not None:
            return exe
        fp = self.aot_fingerprint
        if self._aot_shared:
            exe = _aot_cache_lookup(fp, repr(key), self.device)
            if exe is not None:
                self.stats.aot_cache_hits += 1
                self._exe[key] = exe
                return exe
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("engine.compile", key=repr(key)), \
                jax.default_device(self.device):
            exe = jitfn.lower(*args).compile()
        self.compile_seconds += time.monotonic() - t0
        self.stats.compiles += 1
        self._exe[key] = exe
        _aot_cache_publish(fp, repr(key), exe)
        return exe

    def _sds(self, x):
        return jax.ShapeDtypeStruct(jnp.shape(x), x.dtype)

    def _state_sds(self):
        return tuple(jax.tree_util.tree_map(self._sds, s) for s in (
            self.cache, self.lengths, self.last_tokens, self.temps,
            self.active_mask, self.gen_counts, self.max_news,
            self.stop_table, self._rng))

    def _megastep_jit(self, prefix: int, restore: bool):
        jkey = (prefix, restore)
        jit = self._megastep_jits.get(jkey)
        if jit is None:
            impl = functools.update_wrapper(
                functools.partial(self._megastep_impl, prefix=prefix,
                                  restore=restore), self._megastep_impl)
            jit = jax.jit(impl, donate_argnums=self._mega_donate)
            self._megastep_jits[jkey] = jit
        return jit

    def _megastep_exe(self, prefix: int, restore: bool):
        key = ("megastep", self.megastep, prefix, restore)
        exe = self._exe.get(key)
        if exe is not None:           # hot path: no SDS tree building
            return exe
        st = self._state_sds()
        params = jax.tree_util.tree_map(self._sds, self.params)
        return self._get_exe(
            key, self._megastep_jit(prefix, restore), params,
            st[0], st[1], st[2], st[3], st[4], st[5], st[6], st[7], st[8],
            jax.ShapeDtypeStruct((), jnp.bool_))

    def _paged_megastep_exe(self, npages: int):
        key = ("megastep", self.megastep, "paged", npages)
        exe = self._exe.get(key)
        if exe is not None:
            return exe
        jkey = ("paged", npages)
        jit = self._megastep_jits.get(jkey)
        if jit is None:
            impl = functools.update_wrapper(
                functools.partial(self._paged_megastep_impl, npages=npages),
                self._paged_megastep_impl)
            jit = jax.jit(impl, donate_argnums=self._mega_donate)
            self._megastep_jits[jkey] = jit
        st = self._state_sds()
        params = jax.tree_util.tree_map(self._sds, self.params)
        pt = jax.ShapeDtypeStruct((self.slots, self.max_pages), jnp.int32)
        return self._get_exe(
            key, jit, params, pt,
            st[0], st[1], st[2], st[3], st[4], st[5], st[6], st[7], st[8],
            jax.ShapeDtypeStruct((), jnp.bool_))

    def _decode_npages(self) -> int:
        """Smallest page-count bucket that bounds every active slot's reads
        and writes this megastep (host-tracked — no device sync). The paged
        analogue of ``_decode_prefix``: the table slice is cheap, so the
        bucket applies at every megastep size."""
        bound = 1 + max(
            self._host_lengths[s] + min(self.megastep,
                                        r.max_new_tokens - len(r.generated))
            for s, r in self.active.items())
        need = -(-int(bound) // self.page_size)
        for b in self._page_buckets:
            if need <= b:
                return b
        return self.max_pages

    def _decode_prefix(self) -> int:
        """Smallest decode bucket that bounds every ACTIVE slot's writes
        this megastep: length + however many tokens it can still produce
        (host-tracked, so choosing it costs no device sync).

        The prefix view costs a slice + write-back per dispatch, amortized
        over the megastep's K tokens — below K=4 it cannot pay for itself,
        so short megasteps decode on the full cache."""
        if self.megastep < 4 or len(self.decode_buckets) == 1:
            return self.cache_len
        bound = 1 + max(
            self._host_lengths[s] + min(self.megastep,
                                        r.max_new_tokens - len(r.generated))
            for s, r in self.active.items())
        for b in self.decode_buckets:
            if bound <= b:
                return b
        return self.cache_len

    def _prefill_exe(self, bucket: int):
        key = ("prefill", bucket)
        exe = self._exe.get(key)
        if exe is not None:
            return exe
        st = self._state_sds()
        params = jax.tree_util.tree_map(self._sds, self.params)
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
        head = (params,
                i32(self.slots, bucket), i32(self.slots), i32(self.slots),
                jax.ShapeDtypeStruct((self.slots,), jnp.bool_),
                jax.ShapeDtypeStruct((self.slots,), jnp.float32),
                i32(self.slots), i32(self.slots, self.max_stop_tokens))
        if self._paged:
            head = head + (i32(self.slots, self.max_pages),
                           i32(self.slots, self.max_pages))
        return self._get_exe(
            key, self._prefill_jit, *head,
            st[0], st[1], st[2], st[3], st[4], st[5], st[6], st[7], st[8])

    def _shared_prefill_exe(self, bucket: int):
        key = ("prefill_shared", bucket)
        exe = self._exe.get(key)
        if exe is not None:
            return exe
        st = self._state_sds()
        params = jax.tree_util.tree_map(self._sds, self.params)
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
        head = (params,
                i32(self.slots, bucket), i32(self.slots), i32(self.slots),
                i32(self.slots),
                jax.ShapeDtypeStruct((self.slots,), jnp.bool_),
                jax.ShapeDtypeStruct((self.slots,), jnp.float32),
                i32(self.slots), i32(self.slots, self.max_stop_tokens),
                i32(self.slots),
                i32(self.slots, self.max_pages),
                i32(self.slots, self.max_pages),
                i32(self.slots, self.max_pages))
        return self._get_exe(
            key, self._shared_prefill_jit, *head,
            st[0], st[1], st[2], st[3], st[4], st[5], st[6], st[7], st[8])

    def _cow_exe(self):
        key = ("cowcopy",)
        exe = self._exe.get(key)
        if exe is not None:
            return exe
        cache_sds = jax.tree_util.tree_map(self._sds, self.cache)
        i32v = jax.ShapeDtypeStruct((self.slots,), jnp.int32)
        pt = jax.ShapeDtypeStruct((self.slots, self.max_pages), jnp.int32)
        return self._get_exe(
            key, self._cow_jit, pt, cache_sds, i32v, i32v, i32v, i32v,
            jax.ShapeDtypeStruct((self.slots,), jnp.bool_))

    # -------------------------------------------- PCM tier offload/restore --
    _DEVICE_STATE_FIELDS = ("params", "cache", "lengths", "last_tokens",
                            "temps", "active_mask", "gen_counts", "max_news",
                            "stop_table", "_rng")

    @property
    def offloaded(self) -> bool:
        """True while the engine's device state lives in a ContextSnapshot
        (HOST_RAM or LOCAL_DISK tier) instead of on the accelerator."""
        return self.params is None

    def offload_device_state(self) -> Dict:
        """Demote: pull every device-resident array (weights, slot cache,
        per-slot decode state, RNG key) to host memory in one
        ``jax.device_get`` and DROP the device references so the HBM can be
        reclaimed. The AOT-compiled executables, host length shadow, queue
        and stats stay on this object — they are the snapshot's "AOT-warm
        metadata", and they are why a later ``restore_device_state`` needs
        zero builder calls and zero XLA compiles. Idempotence is the
        caller's job: offloading twice raises.

        Paged engines serialize ONLY the live pages (``_paged_live_ids``
        carries their pool indices): the snapshot's ``nbytes`` — and hence
        SnapshotPool occupancy, ContextStore admission and every
        TransferPlanner prediction — scales with actual context, not
        allocated capacity. The allocator, like the host length shadow and
        the queue, stays attached to this object."""
        if self.offloaded:
            raise RuntimeError("engine device state is already offloaded")
        state = {name: getattr(self, name)
                 for name in self._DEVICE_STATE_FIELDS}
        if self._paged:
            live = np.asarray(self._alloc.live_ids(), np.int32)
            state["cache"] = paging.gather_live(
                self.cache, jnp.asarray(live), self._axes)
        host = jax.device_get(state)
        if self._paged:
            host["_paged_live_ids"] = live
            # sharing structure rides along for integrity checking: the
            # refcount of each live page at offload time (allocator and
            # prefix cache stay attached to this object, so restore only
            # validates — it does not rebuild)
            host["_paged_refcounts"] = np.array(
                [self._alloc.refcount(int(p)) for p in live], np.int32)
            # per-leaf page axis of the gathered cache (pytree of ints
            # mirroring it): the spill path chunks each leaf along THIS
            # axis, so every on-disk chunk boundary is a page boundary
            host["_paged_page_axes"] = jax.tree_util.tree_map(
                lambda a: np.int32(a), self._axes)
        for name in self._DEVICE_STATE_FIELDS:
            setattr(self, name, None)
        return host

    def restore_device_state(self, host_state: Dict):
        """Promote: push a previously offloaded state dict onto the device
        this thread places new arrays on (a live worker's own device) in
        one ``jax.device_put``. Executables cached in
        ``_exe`` are reused as-is, so a restored engine decodes
        bit-identically to one that never left the device — at transfer
        cost, not build+compile cost. An engine restored onto another
        device resolves its executables again through the AOTRecipe cache
        (loaded for the new device, not compiled). Returns the restored
        device state, which may still be in flight."""
        if not self.offloaded:
            raise RuntimeError("engine device state is already resident")
        missing = [n for n in self._DEVICE_STATE_FIELDS
                   if n not in host_state]
        if missing:
            raise ValueError(f"snapshot is missing engine state: {missing}")
        device = current_device()
        if device != self.device:
            self._exe = {}
            self._aot_shared = True
            self.device = device
        with jax.default_device(device):
            return self._restore_on(host_state, device)

    def _restore_on(self, host_state: Dict, device):
        if self._paged:
            if "_paged_live_ids" not in host_state:
                raise ValueError("paged snapshot is missing the live-page "
                                 "index (_paged_live_ids)")
            live = np.asarray(host_state["_paged_live_ids"], np.int32)
            refs = host_state.get("_paged_refcounts")
            if refs is not None and len(refs) != live.size:
                raise ValueError(
                    f"paged snapshot refcount vector ({len(refs)}) does not "
                    f"match its live-page index ({live.size})")
            state = jax.device_put({n: host_state[n]
                                    for n in self._DEVICE_STATE_FIELDS
                                    if n != "cache"}, device)
            # rebuild the pool around the snapshotted live pages; released
            # pages and TRASH come back zeroed, which is invisible to every
            # read (non-owned columns are length-masked to exact-zero
            # softmax weight) — decode stays bit-identical
            pool = self.model.init_cache(self.num_pages + 1, self.page_size,
                                         self._cache_dtype)
            if live.size:
                pool = paging.scatter_live(
                    pool, jnp.asarray(live),
                    jax.device_put(host_state["cache"], device), self._axes)
            state["cache"] = jax.device_put(pool, device)
        else:
            state = jax.device_put(
                {n: host_state[n] for n in self._DEVICE_STATE_FIELDS},
                device)
        for name in self._DEVICE_STATE_FIELDS:
            setattr(self, name, state[name])
        return state

    def _require_resident(self):
        if self.offloaded:
            raise RuntimeError(
                "engine device state is offloaded (context demoted to "
                "HOST_RAM/LOCAL_DISK) — restore the context before use")

    # ------------------------------------------- P2P template transfer -----
    def export_template_device(self) -> Dict:
        """Device half of the template: the only fields that ship VERBATIM
        from this engine's HBM — the immutable weights and the
        point-in-time RNG key. Returned as DEVICE references (no
        ``device_get``): a chunk-streamed export slices these per chunk
        and pulls each chunk to host between serving turns, which is what
        lets a donor keep decoding mid-export. ``params`` never mutate
        after build, so interleaved chunk reads are coherent."""
        self._require_resident()
        return {"params": self.params, "_rng": self._rng}

    def export_template_host(self) -> Dict:
        """Host half of the template: every other field of a PRISTINE
        engine (all slots free, empty cache), synthesized from shapes
        alone with no whole-payload ``device_get``. A template ships an
        EMPTY engine, not the donor's live requests — so none of this
        needs to read the donor's actual decode state. A paged template
        carries ZERO cache pages (live set is empty) — the template's
        nbytes is essentially the weights."""
        self._require_resident()
        host: Dict = {}
        for name in ("lengths", "last_tokens", "temps", "gen_counts",
                     "max_news", "active_mask"):
            a = getattr(self, name)
            host[name] = np.zeros(a.shape, a.dtype)
        host["stop_table"] = np.full(self.stop_table.shape, NO_TOKEN,
                                     self.stop_table.dtype)
        if self._paged:
            host["cache"] = jax.device_get(paging.gather_live(
                self.cache, jnp.zeros((0,), jnp.int32), self._axes))
            host["_paged_live_ids"] = np.zeros((0,), np.int32)
            host["page_table"] = np.full((self.slots, self.max_pages),
                                         self.trash, np.int32)
        else:
            host["cache"] = jax.tree_util.tree_map(
                lambda l: np.zeros(l.shape, l.dtype), self.cache)
        return host

    def export_template(self) -> Dict:
        """Donor side of a peer-to-peer context bootstrap: a host copy of
        the weights plus a PRISTINE per-slot decode state (as a freshly
        built engine would have), WITHOUT detaching anything from this
        engine — the donor keeps serving. Pairs with ``clone_offloaded``:
        restore the template into the clone on the receiving worker and it
        decodes bit-identically to a cold-built engine, with zero builder
        calls and zero XLA compiles (the executables ride on the clone).
        The monolithic form of the device/host hook split above — one
        blocking ``device_get`` of the device half."""
        host = dict(self.export_template_host())
        host.update(jax.device_get(self.export_template_device()))
        return host

    def clone_offloaded(self) -> "InferenceEngine":
        """A structural twin of this engine for a P2P receiver: same
        model/config, with fresh empty queues/stats and NO device state
        (``offloaded`` until ``restore_device_state`` pushes an exported
        template in). Executables are NOT shared by pointer: the clone is
        marked ``_aot_shared`` and resolves them through the AOTRecipe
        cache (the donor's compiles published there), so an in-process
        receiver and a remote process bootstrap through ONE codepath —
        both compile-free, both counted as ``aot_cache_hits``."""
        import copy
        clone = copy.copy(self)
        clone._exe = {}
        clone._aot_shared = True
        clone._megastep_jits = {}
        clone.queue = collections.deque()
        clone.active = {}
        clone.free_slots = collections.deque(range(self.slots))
        clone._host_lengths = np.zeros_like(self._host_lengths)
        clone.stats = EngineStats(decode_path=self.stats.decode_path)
        clone.compile_seconds = 0.0
        if self._paged:
            clone._alloc = paging.PageAllocator(self.num_pages,
                                                self.page_size)
            if self._prefix_cache is not None:
                # the prefix trie indexes THIS engine's pool pages — a
                # receiver starts with an empty pool, so it starts with an
                # empty cache and re-earns its prefixes
                clone._prefix_cache = paging.PrefixCache(self.page_size)
        for name in self._DEVICE_STATE_FIELDS:
            setattr(clone, name, None)
        return clone

    @property
    def aot_fingerprint(self) -> str:
        """The AOTRecipe cache namespace for this engine's executables:
        ``<portable>@<device id>``. The portable digest covers everything
        that shapes a lowering — model config, slot/cache geometry, bucket
        sets, megastep K, paged/prefix resolution, donation — plus the
        jax/jaxlib versions, the backend platform and the device kind. Two
        engines with equal portable digests lower byte-compatible
        executables, so one's compile is the other's cache hit (in-process
        or across processes, loaded onto the hitting engine's device)."""
        fp = self.__dict__.get("_aot_fp")
        if fp is None:
            import jaxlib
            spec = {
                "config": self.cfg.key(),
                "slots": self.slots, "cache_len": self.cache_len,
                "prefill_buckets": list(self.prefill_buckets),
                "decode_buckets": list(self.decode_buckets),
                "cache_dtype": str(np.dtype(self._cache_dtype)),
                "megastep": self.megastep,
                "max_stop_tokens": self.max_stop_tokens,
                "donate": self._donate_cache,
                "paged": self._paged,
                "page_size": self.page_size if self._paged else None,
                "num_pages": self.num_pages if self._paged else None,
                "prefix": self._prefix_cache is not None,
                "extra": None if self.extra is None else hashlib.sha256(
                    pickle.dumps(self.extra)).hexdigest(),
                "jax": jax.__version__, "jaxlib": jaxlib.__version__,
                "backend": self.device.platform,
                "device_kind": self.device.device_kind,
            }
            fp = hashlib.sha256(
                json.dumps(spec, sort_keys=True).encode()).hexdigest()[:16]
            self.__dict__["_aot_fp"] = fp
        return f"{fp}@{self.device.id}"

    def wire_recipe(self) -> Dict:
        """The engine's wire-format identity: a JSON-serializable
        AOTRecipe (fingerprint + every constructor knob that shapes a
        lowering) plus the loader a receiving process imports to rebuild
        the SHELL — model re-built from config, no device state, no
        executable objects. ``repro.core.wire`` ships this instead of the
        engine object; the receiver's executables come from the AOTRecipe
        cache (compile-cache hit) or a counted true recompile."""
        import jaxlib
        import dataclasses
        rec = {
            "loader": "repro.serving.engine:engine_from_wire",
            "config": dataclasses.asdict(self.cfg),
            "slots": self.slots, "cache_len": self.cache_len,
            "prefill_buckets": list(self.prefill_buckets),
            "decode_buckets": list(self.decode_buckets),
            "cache_dtype": str(np.dtype(self._cache_dtype)),
            "megastep": self.megastep,
            "max_stop_tokens": self.max_stop_tokens,
            "admission": self.admission,
            "donate_cache": self._donate_cache,
            "paged": self._paged,
            "page_size": self.page_size,
            "num_pages": self.num_pages if self._paged else None,
            "prefix_sharing": self._prefix_cache is not None,
            "fingerprint": self.aot_fingerprint,
            "jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "backend": self.device.platform,
        }
        if self.extra is not None:
            rec["extra_b64"] = base64.b64encode(
                pickle.dumps(self.extra)).decode("ascii")
        return rec

    def warm_executables(self) -> float:
        """AOT-compile the megastep (every decode bucket) + every
        prefill-bucket executable.

        Called by PCM context materialization so the compile cost is paid
        once per context lifetime; returns the seconds spent compiling
        (idempotent — already-warm executables cost nothing)."""
        self._require_resident()
        before = self.compile_seconds
        if self._paged:
            for npb in self._page_buckets:
                self._paged_megastep_exe(npb)
            if self._prefix_cache is not None:
                for b in self.prefill_buckets:
                    self._shared_prefill_exe(b)
                self._cow_exe()
        else:
            reachable = (self.decode_buckets if self.megastep >= 4
                         else (self.cache_len,))
            for b in reachable:
                for restore in (False, True):
                    self._megastep_exe(b, restore)
        for b in self.prefill_buckets:
            self._prefill_exe(b)
        return self.compile_seconds - before

    # -------------------------------------------------------------- public --
    def submit(self, req: Request) -> Request:
        if len(req.prompt) > self.cache_len:
            raise ValueError(f"prompt ({len(req.prompt)}) exceeds cache "
                             f"({self.cache_len})")
        if len(req.stop_tokens) > self.max_stop_tokens:
            raise ValueError(f"request has {len(req.stop_tokens)} stop "
                             f"tokens; engine supports at most "
                             f"{self.max_stop_tokens}")
        if any(t < 0 for t in req.stop_tokens):
            raise ValueError("stop tokens must be non-negative ids")
        if self._paged:
            need = self._alloc.pages_needed(
                min(len(req.prompt) + req.max_new_tokens, self.cache_len))
            if need > self.num_pages:
                raise ValueError(
                    f"request needs {need} pages for its whole lifetime "
                    f"(prompt {len(req.prompt)} + max_new "
                    f"{req.max_new_tokens}); the pool holds "
                    f"{self.num_pages}")
        if req.priority > 0:
            # admission-order preemption: ahead of every queued request of
            # strictly lower priority, behind equal-or-higher (FIFO within
            # class) — running decodes are never disturbed
            idx = next((i for i, q in enumerate(self.queue)
                        if q.priority < req.priority), len(self.queue))
            self.queue.insert(idx, req)
        else:
            self.queue.append(req)
        return req

    def has_work(self) -> bool:
        return bool(self.queue or self.active)

    def step(self) -> List[Request]:
        """One scheduling step: admit queued prefills into free slots, then
        one decode megastep (up to K tokens) for all active slots. Returns
        finished requests. In ``drain`` mode admission additionally waits
        for the whole active set to finish."""
        self._require_resident()
        finished: List[Request] = []
        with jax.profiler.TraceAnnotation("engine.step",
                                          step=self.stats.steps):
            if self.queue and self.free_slots and (
                    self.admission == "continuous" or not self.active):
                with jax.profiler.TraceAnnotation("engine.admit"):
                    finished.extend(self._admit_wave())
            if self.active:
                with jax.profiler.TraceAnnotation("engine.decode"):
                    finished.extend(self._megastep_wave())
        self.stats.steps += 1
        return finished

    def run_to_completion(self) -> List[Request]:
        done = []
        while self.has_work():
            done.extend(self.step())
        return done

    def generate(self, prompts: Sequence[Sequence[int]],
                 max_new_tokens: int = 32, temperature: float = 0.0
                 ) -> List[List[int]]:
        reqs = [self.submit(Request(prompt=list(p),
                                    max_new_tokens=max_new_tokens,
                                    temperature=temperature))
                for p in prompts]
        self.run_to_completion()
        return [r.generated for r in reqs]

    def cancel(self, req: Request) -> bool:
        """Withdraw a request. Queued requests are removed outright;
        running ones are torn down — slot freed, page reservation released
        (shared prefix pages survive via their cache refcount), device row
        deactivated in one host roundtrip — without disturbing other
        slots. Returns False when the request is already finished or
        unknown to this engine. This is the shed/abandon path: a caller
        that admits a request and then drops it MUST cancel it, or its
        slot and page reservation leak until engine teardown."""
        if req.done:
            return False
        try:
            self.queue.remove(req)
            req.state = RequestState.CANCELLED
            req.finished_time = time.monotonic()
            return True
        except ValueError:
            pass
        s = req.slot
        if s is None or self.active.get(s) is not req:
            return False
        self._require_resident()
        del self.active[s]
        self.free_slots.append(s)
        if self._paged:
            self._alloc.release(s)
        self._host_lengths[s] = 0
        active = np.asarray(self.active_mask).copy()
        lengths = np.asarray(self.lengths).copy()
        active[s] = False
        lengths[s] = 0
        self.active_mask = jnp.asarray(active)
        self.lengths = jnp.asarray(lengths)
        req.state = RequestState.CANCELLED
        req.finished_time = time.monotonic()
        return True

    def drop_prefix_cache(self) -> int:
        """Evict every reclaimable prefix-cache page; return count freed.

        Live reservations are untouched: a page some active slot still
        maps (refcount > 1) is skipped and stays cached. On an idle
        engine this empties the cache entirely. Use under memory
        pressure or before measuring idle pool occupancy."""
        if self._prefix_cache is None:
            return 0
        return self._prefix_cache.evict(self._alloc.num_pages, self._alloc)

    # ------------------------------------------------------------ internal --
    def _ensure_free_pages(self, n: int) -> bool:
        """Free-list admission with prefix-cache pressure relief: when a
        reservation doesn't fit, evict LRU cache-only prefix pages
        (refcount 1 — never pages a live slot maps) until it does or
        nothing reclaimable remains. Live reservations always win over
        cached prefixes."""
        if self._alloc.can_reserve(n):
            return True
        if self._prefix_cache is not None:
            self._prefix_cache.evict(n - self._alloc.free_pages, self._alloc)
        return self._alloc.can_reserve(n)

    def _admit_wave(self) -> List[Request]:
        sharing = self._paged and self._prefix_cache is not None
        wave_starts: List[int] = []
        wave_pins: List[int] = []
        if self._paged:
            # admission-time reservation walk: claim head-of-queue requests
            # while a slot AND their whole-lifetime page reservation fit.
            # The walk stops at the first request that doesn't fit (no
            # queue-order bypass): it re-tries the moment a finish releases
            # pages, so head-of-line wait is bounded by running decodes.
            # A prefix-cache hit reserves only the UNSHARED pages — its
            # table row aliases the cached prefix pages (refcount++).
            wave, wave_slots = [], []
            while self.queue and self.free_slots:
                r = self.queue[0]
                n_total = self._alloc.pages_needed(
                    min(len(r.prompt) + r.max_new_tokens, self.cache_len))
                hit = (self._prefix_cache.match(r.prompt)
                       if sharing and len(r.prompt) > 1 else None)
                if hit is not None:
                    start, shared = hit
                    n_keep = start // self.page_size
                    if not self._ensure_free_pages(n_total - n_keep):
                        break
                    self.queue.popleft()
                    s = self.free_slots.popleft()
                    self._alloc.reserve_shared(s, shared[:n_keep],
                                               n_total - n_keep)
                    pin = -1
                    if start % self.page_size:
                        # partially shared boundary page: the COW copy is
                        # fused into the prefill dispatch (the gather reads
                        # the shared original through pt_src, the scatter
                        # fills the row's fresh private page through
                        # pt_dst). Pin the original so cache eviction for a
                        # later request in this same wave can't recycle it
                        # before the gather runs.
                        pin = shared[n_keep]
                        self._alloc.incref(pin)
                    r.prefix_tokens = start
                    wave_starts.append(start)
                    wave_pins.append(pin)
                else:
                    if not self._ensure_free_pages(n_total):
                        break
                    self.queue.popleft()
                    s = self.free_slots.popleft()
                    self._alloc.reserve(s, n_total)
                    wave_starts.append(0)
                    wave_pins.append(-1)
                wave.append(r)
                wave_slots.append(s)
            if not wave:
                return []
            n = len(wave)
        else:
            n = min(len(self.queue), len(self.free_slots))
            wave = [self.queue.popleft() for _ in range(n)]
            wave_slots = [self.free_slots.popleft() for _ in range(n)]
            wave_starts = [0] * n
            wave_pins = [-1] * n
        # pad the wave to the full slot count with the remaining slot ids
        # (a permutation): ONE executable per bucket, always AOT-warmable.
        taken = set(wave_slots)
        slot_ids = np.array(
            wave_slots + [s for s in range(self.slots) if s not in taken],
            np.int32)
        valid = np.zeros((self.slots,), bool)
        valid[:n] = True

        # a wave with any prefix hit routes through the shared executable,
        # bucketed on TAIL length (cold rows ride along with start 0 —
        # bit-identical to the classic path); pure-cold waves keep the
        # classic executable
        shared_wave = any(wave_starts)
        bucket = _bucket(max(len(r.prompt) - st
                             for r, st in zip(wave, wave_starts)),
                         self.prefill_buckets)
        toks = np.zeros((self.slots, bucket), np.int32)
        lens = np.zeros((self.slots,), np.int32)
        starts_np = np.zeros((self.slots,), np.int32)
        temps = np.zeros((self.slots,), np.float32)
        max_new = np.zeros((self.slots,), np.int32)
        stops = np.full((self.slots, self.max_stop_tokens), NO_TOKEN,
                        np.int32)
        for i, r in enumerate(wave):
            st = wave_starts[i]
            tail = r.prompt[st:]
            toks[i, :len(tail)] = tail
            lens[i] = len(r.prompt)
            starts_np[i] = st
            temps[i] = r.temperature
            max_new[i] = r.max_new_tokens
            stops[i, :len(r.stop_tokens)] = r.stop_tokens
            r.state = RequestState.PREFILLING
            r.slot = int(slot_ids[i])

        try:
            if self._paged:
                pt_dst = np.full((self.slots, self.max_pages), self.trash,
                                 np.int32)
                for i, s in enumerate(wave_slots):
                    ids = self._alloc.owned(s)
                    pt_dst[i, :len(ids)] = ids
                if shared_wave:
                    pt_src = pt_dst.copy()
                    start_pages = np.zeros((self.slots,), np.int32)
                    for i in range(n):
                        start_pages[i] = wave_starts[i] // self.page_size
                        if wave_pins[i] >= 0:
                            pt_src[i, start_pages[i]] = wave_pins[i]
                    exe = self._shared_prefill_exe(bucket)
                    with jax.profiler.TraceAnnotation(
                            "engine.prefill", bucket=bucket, n=n,
                            shared=shared_wave):
                        (first, row_active, self.page_table, self.cache,
                         self.lengths, self.last_tokens, self.temps,
                         self.active_mask, self.gen_counts, self.max_news,
                         self.stop_table, self._rng) = exe(
                            self.params, jnp.asarray(toks), jnp.asarray(lens),
                            jnp.asarray(starts_np), jnp.asarray(slot_ids),
                            jnp.asarray(valid), jnp.asarray(temps),
                            jnp.asarray(max_new), jnp.asarray(stops),
                            jnp.asarray(start_pages), jnp.asarray(pt_src),
                            jnp.asarray(pt_dst), self.page_table, self.cache,
                            self.lengths, self.last_tokens, self.temps,
                            self.active_mask, self.gen_counts, self.max_news,
                            self.stop_table, self._rng)
                else:
                    exe = self._prefill_exe(bucket)
                    with jax.profiler.TraceAnnotation(
                            "engine.prefill", bucket=bucket, n=n,
                            shared=shared_wave):
                        (first, row_active, self.page_table, self.cache,
                         self.lengths, self.last_tokens, self.temps,
                         self.active_mask, self.gen_counts, self.max_news,
                         self.stop_table, self._rng) = exe(
                            self.params, jnp.asarray(toks), jnp.asarray(lens),
                            jnp.asarray(slot_ids), jnp.asarray(valid),
                            jnp.asarray(temps), jnp.asarray(max_new),
                            jnp.asarray(stops), jnp.asarray(pt_dst),
                            self.page_table, self.cache, self.lengths,
                            self.last_tokens, self.temps, self.active_mask,
                            self.gen_counts, self.max_news, self.stop_table,
                            self._rng)
            else:
                exe = self._prefill_exe(bucket)
                with jax.profiler.TraceAnnotation(
                        "engine.prefill", bucket=bucket, n=n,
                        shared=shared_wave):
                    (first, row_active, self.cache, self.lengths,
                     self.last_tokens, self.temps, self.active_mask,
                     self.gen_counts, self.max_news, self.stop_table,
                     self._rng) = exe(
                        self.params, jnp.asarray(toks), jnp.asarray(lens),
                        jnp.asarray(slot_ids), jnp.asarray(valid),
                        jnp.asarray(temps), jnp.asarray(max_new),
                        jnp.asarray(stops), self.cache, self.lengths,
                        self.last_tokens, self.temps, self.active_mask,
                        self.gen_counts, self.max_news, self.stop_table,
                        self._rng)
        except BaseException:
            # reservation-leak fix: an admission that fails to dispatch
            # must hand back everything it claimed — pages (including
            # shared increfs and COW pins), slots, and queue positions —
            # or the pool leaks until restart
            for pin in wave_pins:
                if pin >= 0:
                    self._alloc.decref(pin)
            for r, s in zip(reversed(wave), reversed(wave_slots)):
                if self._paged:
                    self._alloc.release(s)
                self.free_slots.appendleft(s)
                r.state = RequestState.QUEUED
                r.slot = None
                r.prefix_tokens = 0
                self.queue.appendleft(r)
            raise

        if sharing:
            # the gather pin is only needed until the dispatch is ordered
            # against later cache writes (XLA sequences them through the
            # donated buffer)
            for pin in wave_pins:
                if pin >= 0:
                    self._alloc.decref(pin)
            # record the freshly prefilled prompts: full chunks + partial
            # tail chunk map to the slot's own pages (cache takes a
            # reference, so the prefix outlives the request)
            for r, s in zip(wave, wave_slots):
                self._prefix_cache.insert(r.prompt, self._alloc.owned(s),
                                          self._alloc)
            self.stats.prefix_hits += sum(1 for st in wave_starts if st)
            self.stats.prefix_tokens_reused += sum(wave_starts)
            self.stats.cow_copies += sum(1 for p in wave_pins if p >= 0)

        # one host sync per wave: the first token + immediately-done flags
        with jax.profiler.TraceAnnotation("engine.sync", of="prefill"):
            first_np, row_active_np = jax.device_get((first, row_active))
        now = time.monotonic()
        done: List[Request] = []
        for i, r in enumerate(wave):
            tok = int(first_np[i])
            r.generated.append(tok)
            r.first_token_time = now
            r.state = RequestState.DECODING
            self._host_lengths[r.slot] = len(r.prompt)
            if r.on_token is not None:
                self._emit(r, tok, 0)
            if row_active_np[i]:
                self.active[r.slot] = r
            else:
                done.append(self._finish(r))
        # tail tokens are what prefill actually computed — the prefix-hit
        # savings show up here (starts are all zero without sharing)
        self.stats.prefill_tokens += int(lens.sum()) - int(starts_np.sum())
        self.stats.prefill_batches += 1
        return done

    def _decode_cow(self):
        """Copy-on-write fence ahead of a decode megastep: any active slot
        whose next-K token appends would land in a page the prefix cache
        also holds (refcount > 1 — its prompt's partial tail page) first
        gets a private copy — page copy + table repoint fused into one
        dispatch for up to ``slots`` copies. When the pool has no page to
        copy into, the cache's claim on the page is revoked instead
        (un-share): correctness never depends on spare capacity. Shared
        FULL-prefix pages never reach this path — a prefix hit only maps
        them at columns below its first private page, and appends always
        land at or above it."""
        entries = []
        K = self.megastep
        for s in self.active:
            owned = self._alloc.owned(s)
            length = int(self._host_lengths[s])
            lo = length // self.page_size
            hi = min((length + K - 1) // self.page_size + 1, len(owned))
            for col in range(lo, hi):
                if self._alloc.refcount(owned[col]) <= 1:
                    continue
                if self._ensure_free_pages(1):
                    src, dst = self._alloc.cow(s, col)
                    entries.append((s, col, src, dst))
                else:
                    page = owned[col]
                    self._prefix_cache.forget_page(page, self._alloc)
                    if self._alloc.refcount(page) > 1:
                        raise RuntimeError(
                            f"page {page} is shared (refcount "
                            f"{self._alloc.refcount(page)}) in slot {s}'s "
                            f"append range but is not a cache partial — "
                            f"cannot un-share and no free page to copy into")
        if not entries:
            return
        exe = self._cow_exe()
        with jax.profiler.TraceAnnotation("engine.cow", copies=len(entries)):
            for i in range(0, len(entries), self.slots):
                chunk = entries[i:i + self.slots]
                # pads replicate the chunk's first entry: duplicate scatter
                # indices carry identical values, so the write stays
                # deterministic and the repeated page copy is a no-op
                chunk = chunk + [chunk[0]] * (self.slots - len(chunk))
                rows = np.array([e[0] for e in chunk], np.int32)
                cols = np.array([e[1] for e in chunk], np.int32)
                src = np.array([e[2] for e in chunk], np.int32)
                dst = np.array([e[3] for e in chunk], np.int32)
                self.page_table, self.cache = exe(
                    self.page_table, self.cache, jnp.asarray(src),
                    jnp.asarray(dst), jnp.asarray(rows), jnp.asarray(cols),
                    jnp.ones((self.slots,), bool))
        self.stats.cow_copies += len(entries)

    def _megastep_wave(self) -> List[Request]:
        t0 = time.monotonic()
        if self._prefix_cache is not None:
            self._decode_cow()
        # a drain engine never admits mid-batch, so freeing a slot early
        # cannot help anyone — the loop runs its full K
        has_queue = jnp.asarray(bool(self.queue)
                                and self.admission == "continuous")
        if self._paged:
            self.stats.live_pages = self._alloc.live_pages
            npages = self._decode_npages()
            exe = self._paged_megastep_exe(npages)
            with jax.profiler.TraceAnnotation("engine.megastep",
                                              npages=npages):
                (self.cache, self.lengths, self.last_tokens,
                 self.active_mask, self.gen_counts, self._rng, block,
                 produced) = exe(
                    self.params, self.page_table, self.cache, self.lengths,
                    self.last_tokens, self.temps, self.active_mask,
                    self.gen_counts, self.max_news, self.stop_table,
                    self._rng, has_queue)
        else:
            # the restore pass is only needed when free slots exist whose
            # cache rows must survive the megastep untouched
            prefix = self._decode_prefix()
            exe = self._megastep_exe(prefix, len(self.active) < self.slots)
            with jax.profiler.TraceAnnotation("engine.megastep",
                                              prefix=prefix):
                (self.cache, self.lengths, self.last_tokens,
                 self.active_mask, self.gen_counts, self._rng, block,
                 produced) = exe(
                    self.params, self.cache, self.lengths, self.last_tokens,
                    self.temps, self.active_mask, self.gen_counts,
                    self.max_news, self.stop_table, self._rng, has_queue)

        # the single host sync for up to K tokens across all slots
        with jax.profiler.TraceAnnotation("engine.sync", of="megastep"):
            block_np, produced_np, active_np = jax.device_get(
                (block, produced, self.active_mask))
        now = time.monotonic()
        done: List[Request] = []
        for s, r in list(self.active.items()):
            k = int(produced_np[s])
            if k:
                base = len(r.generated)
                toks = [int(t) for t in block_np[s, :k]]
                r.generated.extend(toks)
                if r.on_token is not None:
                    for j, t in enumerate(toks):
                        self._emit(r, t, base + j)
            if not active_np[s]:
                del self.active[s]
                done.append(self._finish(r, now))
        # token accounting derived from the device-side produced counts —
        # no per-token Python loop; host length shadow keeps prefix-bucket
        # selection sync-free
        self._host_lengths += produced_np
        self.stats.decode_tokens += int(produced_np.sum())
        self.stats.megasteps += 1
        self.stats.decode_seconds += time.monotonic() - t0
        return done

    def _emit(self, r: Request, token: int, index: int):
        """Fire a request's streaming callback. A raising callback must
        never wedge the engine (other slots' requests share the batch), so
        exceptions are reported and dropped — the stream breaks, not the
        engine."""
        try:
            r.on_token(r, token, index)
        except BaseException:
            print(f"on_token callback failed for request {r.request_id}:",
                  file=sys.stderr)
            traceback.print_exc(file=sys.stderr)

    def _finish(self, r: Request, now: Optional[float] = None) -> Request:
        r.state = RequestState.DONE
        r.finished_time = now if now is not None else time.monotonic()
        self.free_slots.append(r.slot)
        if self._paged:
            # pages go back to the pool immediately; the slot's stale device
            # table row is harmless (reads are length-masked, writes by
            # inactive slots go to TRASH) and is rewritten at re-admission
            self._alloc.release(r.slot)
        self.stats.completed += 1
        return r

    def snapshot(self) -> Dict:
        """Engine-state summary (used by PCM checkpointing & tests).

        ``capacity_bytes`` is the allocated cache (what HBM pays),
        ``live_bytes`` what a snapshot/peer transfer would actually ship:
        exact page accounting on the paged path, a sequence-leaf pro-rated
        estimate on the contiguous path. ``cache_bytes`` stays as a
        back-compat alias for capacity."""
        if self.offloaded:
            cap = live = 0
        elif self._paged:
            pb = paging.pool_bytes(self.cache, self.num_pages)
            cap = pb["capacity_bytes"]
            live = pb["per_page_bytes"] * self._alloc.live_pages
        else:
            cap = kvcache.capacity_bytes(self.cache)
            if self._byte_axes is None:
                live = cap
            else:
                live_tokens = sum(int(self._host_lengths[s])
                                  for s in self.active)
                live = kvcache.live_bytes(self.cache, self._byte_axes,
                                          live_tokens,
                                          self.slots * self.cache_len)
        return {
            "active": len(self.active), "queued": len(self.queue),
            "free_slots": len(self.free_slots),
            "admission": self.admission,
            "offloaded": self.offloaded,
            "cache_bytes": cap,
            "capacity_bytes": cap,
            "live_bytes": live,
            "decode_path": self.stats.decode_path,
            "live_pages": (self._alloc.live_pages if self._paged else 0),
            "free_pages": (self._alloc.free_pages if self._paged else 0),
            "paged_fallback": self.paged_fallback,
            "prefix_fallback": self.prefix_fallback,
            "prefix_cache": (self._prefix_cache.stats()
                            if self._prefix_cache is not None else None),
            "compile_seconds": self.compile_seconds,
            "stats": self.stats.as_dict(),
        }


def engine_from_wire(rec: Dict) -> "InferenceEngine":
    """Rebuild an engine SHELL from a :meth:`InferenceEngine.wire_recipe`
    in THIS process: the model is re-built from its config, the engine is
    constructed with the exact lowering-shaping knobs the donor recorded,
    then stripped of device state (``offloaded`` until a restore lands)
    and marked ``_aot_shared`` so its executables resolve through the
    AOTRecipe cache — a compile-cache hit when the donor's compiles were
    published here (same process or a shared ``set_aot_cache_dir``), a
    COUNTED true recompile otherwise. No executable object, model object,
    or parameter crosses the wire inside the recipe."""
    from repro.configs.base import (MLAConfig, ModelConfig, MoEConfig,
                                    SSMConfig)
    from repro.models.registry import build_model
    d = dict(rec["config"])
    d["moe"] = MoEConfig(**d["moe"])
    d["mla"] = MLAConfig(**d["mla"])
    d["ssm"] = SSMConfig(**d["ssm"])
    cfg = ModelConfig(**d)
    model = build_model(cfg)
    extra = None
    if rec.get("extra_b64"):
        extra = pickle.loads(base64.b64decode(rec["extra_b64"]))
    num_pages = rec.get("num_pages")
    eng = InferenceEngine(
        model, None,
        slots=int(rec["slots"]), cache_len=int(rec["cache_len"]),
        prefill_buckets=tuple(rec["prefill_buckets"]),
        cache_dtype=np.dtype(rec["cache_dtype"]),
        extra=extra,
        donate_cache=bool(rec.get("donate_cache", True)),
        megastep=int(rec["megastep"]),
        decode_buckets=tuple(rec["decode_buckets"]),
        max_stop_tokens=int(rec["max_stop_tokens"]),
        admission=rec.get("admission", "continuous"),
        paged=bool(rec.get("paged", False)),
        page_size=int(rec.get("page_size", 64)),
        num_pages=int(num_pages) if num_pages is not None else None,
        prefix_sharing=bool(rec.get("prefix_sharing", True)))
    for name in eng._DEVICE_STATE_FIELDS:
        setattr(eng, name, None)
    eng._aot_shared = True
    return eng
