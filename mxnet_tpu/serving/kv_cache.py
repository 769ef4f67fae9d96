"""Block-paged KV-cache pool: the serving-side replacement for the
per-executor contiguous cache.

The contiguous cached decoder (``_contrib_CachedMultiHeadAttention``) gives
every stream a private ``(max_len, heads, head_dim)`` cache per layer —
serving N streams costs N full-length caches whether a stream holds 4 tokens
or 4096. Here all streams share ONE device pool of fixed-size blocks
(``block_size`` token slots each); a per-request block table names which
pool blocks hold the request's tokens, in position order. Device memory
scales with tokens actually cached, admission is a free-list pop, and
release is O(blocks) with zero copying.

Who decides what a page is, arrows one way::

    ModelConfig.cache_specs()          serving/model.py: the ONE family
      |  names a PageSpec a cache      switch (rows, widths, order, parts)
      v
    KVBlockPool(spec, ...)             this module: holds ``k_pages`` /
      |  hands out pages of the spec   ``v_pages`` of ``spec.shape(...)``
      v
    step programs and kernels          serving/model.py, ops/attention.py:
                                       read the spec (and the pages' own
                                       shape); conclude nothing themselves

Layout (one pool per engine): ``(layers, parts x num_blocks) + block`` for
K and V, where a block is ``(block_size, G, W)`` — or ``(G, block_size,
W)`` where the spec is head-major — and a page row ``(G, W)`` holds a
token's K (or V) lane-dense (:class:`PageSpec`; 16 heads of 64 -> 8 rows
of 128). A 64-wide minor dimension fills half of every (8, 128) tile: the
device's default layout for such a pool is not row-major, the Pallas
kernels want row-major, and every program that touched the pool copied all
of it in and out (PERF.md, PR 25). With full lanes the default layout, the
scatter's and the kernel's are one, and the donated pool is updated where
it lies. ``(H, D) -> (G, W)`` is a row-major reshape, so a block's bytes
are what they always were. Block 0 is the reserved TRASH block — padded
table entries and padded batch rows point at it, so masked lanes of a
bucketed step scatter their garbage somewhere no reader ever trusts
(readers mask by context length; the pool hands block 0 to no request).

A model whose stack runs several times (``ModelConfig.loop_steps`` = R)
caches every pass apart. Its pool is R PARTS (``PageSpec.parts``) side by
side along the block axis, ``(layers, R x num_blocks, ...)``: block ``b``'s
rows of pass ``r`` are block ``r x num_blocks + b`` of the arrays, which
the step programs reach by adding ``r x num_blocks`` to the block table
(block 0 of each part is that pass's trash). A block id still names ONE
allocation unit, a token's rows in all ``R x layers`` cache layers: the
free list, refcounts and the prefix index know nothing of parts; ``cow``
copies, and ``nbytes`` / ``block_nbytes`` count, every part of a block.

Prefix sharing (docs/serving.md §prefix-sharing): every allocated block
carries a REFCOUNT. Full prefill blocks are content-hashed into a pool-
level prefix index — the digest chains token ids through the block's
position base, so only a same-tokens same-positions prefix can ever match
(position embeddings are baked into the cached K/V). A new request maps
the longest indexed block-aligned prefix into its table via
:meth:`prefix_match` (incref), and ``free``/preempt decrements — a block
returns to the free list only when its refcount reaches zero, at which
point its index entry is dropped. Shared blocks are COPY-ON-WRITE:
:meth:`cow` hands a writer a private bit-exact copy first. The trash
block is never refcounted, never indexed, never shared.

Fragmentation accounting: fixed-size blocks make external fragmentation
impossible by construction (any free block serves any request), so "defrag"
reduces to accounting for INTERNAL fragmentation — allocated-but-unused
slots in each request's tail block — exposed as the
``serving.kv_blocks_frag_slots`` gauge (the engine refreshes it each step).
"""
import dataclasses
import hashlib
import threading

import numpy as np

from .. import fault, telemetry
from ..analysis import witness
from ..base import MXNetError


class KVCacheOOM(MXNetError):
    """The block pool cannot satisfy an allocation (classified so the
    scheduler can preempt / the engine can fail the request instead of
    dying inside a step)."""


@dataclasses.dataclass(frozen=True)
class PageSpec:
    """What a page of ONE cache is: everything about ``k_pages`` /
    ``v_pages`` but how many blocks there are and how long. The model's
    configuration names it (``ModelConfig.cache_specs``), the pool builds
    its arrays from it, the step programs and the tests read it.

    layers          cache layers, axis 0 of the pages (a pool's layers are
                    not the model's: one a layer that WRITES the pool)
    k_rows, v_rows  ``(G, W)``: the rows and lanes one token's K, and V,
                    take in a page
    head_major      a block is ``(G, bs, W)`` — each row a ``(bs, W)``
                    slab — and not ``(bs, G, W)``
    parts           a looped stack's passes, side by side along the block
                    axis (module docstring)
    heads_per_row   heads side by side in one page row (1 = a row the
                    model named, or the plain ``(H, D)`` row)"""
    layers: int
    k_rows: tuple
    v_rows: tuple
    head_major: bool
    parts: int = 1
    heads_per_row: int = 1

    @classmethod
    def lane_dense(cls, layers, num_heads, head_dim, parts=1):
        """The one-block models' rows, token-major always (their step
        programs know no other order): ``r = 128 // head_dim`` consecutive
        heads share a row when that fills the 128 lanes exactly and
        divides the heads; any other shape (``head_dim`` >= 128, an odd
        head count) keeps ``(H, D)``, r = 1. One layout for the pool, the
        scatter and the kernels (PR 25): the model and the kernels read r
        off the pages they are handed."""
        r = 128 // head_dim if head_dim < 128 and 128 % head_dim == 0 else 1
        if num_heads % r:
            r = 1
        rows = (num_heads // r, r * head_dim)
        return cls(layers, rows, rows, False, parts, r)

    @classmethod
    def tiled(cls, layers, rows):
        """Rows the model names, K's and V's alike, in the order their
        tiles ask for: head-major when the lanes are full and the rows do
        not fill their sublane tiles (ten rows of 128: twenty K/V heads of
        64). Token-major, such a block is padded to sixteen rows in HBM
        and copied by every kernel call (PR 31); head-major it is whole
        tiles for any G. The formats that were, ``(8, 128)`` and
        ``(16, 128)``, stay token-major."""
        g, w = rows
        return cls(layers, rows, rows, w % 128 == 0 and g % 8 != 0)

    @property
    def block_axis(self):
        """The axis of the pages the block size stands at."""
        return 3 if self.head_major else 2

    @property
    def cache_layers(self):
        """Cache layers a block holds rows in: a layer and part."""
        return self.layers * self.parts

    def shape(self, num_blocks, block_size):
        """``(k_pages.shape, v_pages.shape)`` of a pool of ``num_blocks``
        blocks of ``block_size`` token slots."""
        return tuple(
            (self.layers, self.parts * num_blocks)
            + ((g, block_size, w) if self.head_major else (block_size, g, w))
            for g, w in (self.k_rows, self.v_rows))

    def block_nbytes(self, block_size, itemsize):
        """Device bytes ONE block pins across cache layers (K + V)."""
        (g, w), (gv, wv) = self.k_rows, self.v_rows
        return self.cache_layers * block_size * (g * w + gv * wv) * itemsize


class KVBlockPool:
    """Device KV block pool + thread-safe host-side free-list allocator
    with block refcounts and a content-hash prefix index."""

    def __init__(self, spec, num_blocks, block_size, dtype=np.float32,
                 device=None, prefix_cache=True, gauges=True):
        """``spec``: the :class:`PageSpec` of this cache. ``gauges``: a
        second pool of an engine (the window layers') leaves the
        process's ``serving.kv_blocks_*`` gauges to the first."""
        if num_blocks < 2:
            raise ValueError("KVBlockPool needs >= 2 blocks (block 0 is the "
                             "reserved trash block)")
        import jax.numpy as jnp

        #: what a page is: rows, widths, order, layers and parts are read
        #: HERE, by the engine's ``stats()`` as by everything else
        self.spec = spec
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.dtype = np.dtype(dtype)
        self.prefix_cache = bool(prefix_cache)
        self._gauges = bool(gauges)
        #: device bytes the engine holds beside this pool for the same
        #: streams (a window pool, state slots): :meth:`nbytes` counts them
        self.extra_nbytes = 0
        k, v = (jnp.zeros(shape, self.dtype)
                for shape in spec.shape(self.num_blocks, self.block_size))
        if device is not None:
            import jax

            k = jax.device_put(k, device)
            v = jax.device_put(v, device)
        #: the device pages; the engine REPLACES these after every jitted
        #: prefill/decode call (the arrays are donated into the step)
        self.k_pages = k
        self.v_pages = v
        self._lock = threading.Lock()
        self._lock = witness.declare(
            "mxnet_tpu.serving.kv_cache.KVBlockPool._lock", self._lock)
        # LIFO free list, block 0 excluded (trash)
        self._free = list(range(self.num_blocks - 1, 0, -1))
        # block id -> refcount, allocated blocks only (never block 0)
        self._ref = {}
        # content-hash prefix index: chained digest -> block id holding
        # that full block's K/V, plus the reverse map for O(1) removal
        # when the block's refcount hits zero
        self._prefix = {}
        self._block_digest = {}
        # per-pool tallies (the registry counters with the same names are
        # process-global; stats() must read only this pool's traffic)
        self.prefix_lookups = 0
        self.prefix_hits = 0
        self.prefix_hit_blocks = 0
        self.cow_copies = 0
        if self._gauges:
            telemetry.gauge("serving.kv_blocks_total").set(self.num_usable)
            telemetry.gauge("serving.kv_heads_per_row").set(
                spec.heads_per_row)
        # the pool may be constructed on a supervisor thread while handler
        # threads already poll the gauges of a predecessor — honor the
        # _locked suffix even on the init path
        with self._lock:
            self._refresh_gauges_locked()

    @property
    def num_usable(self):
        """Allocatable blocks (pool size minus the trash block)."""
        return self.num_blocks - 1

    def available(self):
        with self._lock:
            return len(self._free)

    def used(self):
        with self._lock:
            return self.num_usable - len(self._free)

    def nbytes(self):
        """Device bytes the pool pins (K + V), and what the engine holds
        beside it for the same streams (``extra_nbytes``)."""
        return self.block_nbytes() * self.num_blocks + self.extra_nbytes

    def block_nbytes(self):
        """Device bytes ONE block pins across cache layers (K + V) — the
        unit every shared reference saves."""
        return self.spec.block_nbytes(self.block_size, self.dtype.itemsize)

    def copy_block(self, pages, src, dst):
        """``pages`` with block ``src``'s rows copied bit-exactly over
        block ``dst``'s, in every layer and in every part ``pages`` has
        (the pool's own, or a draft model's over the same block ids)."""
        for at in range(0, pages.shape[1], self.num_blocks):
            pages = pages.at[:, at + dst].set(pages[:, at + src])
        return pages

    def blocks_for(self, num_tokens):
        """Blocks needed to hold ``num_tokens`` cache slots."""
        return -(-int(num_tokens) // self.block_size)

    # ---- alloc / free ---------------------------------------------------
    def alloc(self, n):
        """Pop ``n`` blocks off the free list (each born with refcount 1);
        raises :class:`KVCacheOOM` (allocating nothing) when fewer than
        ``n`` are free."""
        n = int(n)
        # chaos: forced allocator exhaustion, checked OUTSIDE the pool
        # lock (the injection must not perturb lock ordering) — exercises
        # every KVCacheOOM consumer: preemption, admission failure, the
        # classified alloc-failure counters (docs/fault_tolerance.md)
        if fault.hit("kv_oom") is not None:
            telemetry.counter("serving.kv_blocks_alloc_failures").inc()
            raise KVCacheOOM(
                "KV block pool exhausted (fault-injected kv_oom): want %d "
                "blocks" % n)
        with self._lock:
            if n > len(self._free):
                telemetry.counter("serving.kv_blocks_alloc_failures").inc()
                raise KVCacheOOM(
                    "KV block pool exhausted: want %d blocks, %d free of %d "
                    "usable (%d-token slots each)"
                    % (n, len(self._free), self.num_usable, self.block_size))
            got = [self._free.pop() for _ in range(n)]
            for b in got:
                self._ref[b] = 1
            telemetry.counter("serving.kv_blocks_allocs").inc(n)
            self._refresh_gauges_locked()
            self._check_invariants_locked()
            return got

    def free(self, blocks):
        """Drop one reference per listed block. A block returns to the
        free list (and its prefix-index entry is dropped) only when its
        refcount reaches ZERO — freeing a shared block reclaims nothing.
        Double-free (a block with no references) and trash-free are hard
        errors: the accounting gauges must never drift."""
        blocks = [int(b) for b in blocks]
        with self._lock:
            released = 0
            for b in blocks:
                if b <= 0 or b >= self.num_blocks:
                    raise ValueError("free of invalid block id %d" % b)
                rc = self._ref.get(b, 0)
                if rc <= 0:
                    raise ValueError("double free of block %d" % b)
                if rc == 1:
                    del self._ref[b]
                    self._drop_index_locked(b)
                    self._free.append(b)
                    released += 1
                else:
                    self._ref[b] = rc - 1
            if released:
                telemetry.counter("serving.kv_blocks_frees").inc(released)
            self._refresh_gauges_locked()
            self._check_invariants_locked()
            return released

    # ---- refcounts ------------------------------------------------------
    def refcount(self, b):
        """Current reference count of ``b`` (0 when free/never allocated)."""
        with self._lock:
            return self._ref.get(int(b), 0)

    def incref(self, blocks):
        """Add one reference per listed block (each must be allocated)."""
        with self._lock:
            for b in blocks:
                b = int(b)
                rc = self._ref.get(b, 0)
                if b <= 0 or rc <= 0:
                    raise ValueError(
                        "incref of unallocated block %d (trash and free "
                        "blocks cannot be shared)" % b)
                self._ref[b] = rc + 1
            self._refresh_gauges_locked()
            self._check_invariants_locked()

    def reclaimable(self, blocks):
        """How many of ``blocks`` would actually return to the free list
        if freed now — only those whose refcount is exactly 1. The
        scheduler's eviction-victim picker computes its reclaim gain from
        this, never from ``len(blocks)``."""
        with self._lock:
            return sum(1 for b in blocks if self._ref.get(int(b), 0) == 1)

    def cow(self, b):
        """Copy-on-write: hand the caller a PRIVATE copy of block ``b``
        before a write. Sole owner (refcount 1) -> ``b`` itself, no copy.
        Shared -> allocate a fresh block, device-copy the K/V pages
        bit-exactly, drop one reference from ``b``, return the new id.
        Raises :class:`KVCacheOOM` when the free list is dry."""
        b = int(b)
        with self._lock:
            rc = self._ref.get(b, 0)
            if b <= 0 or rc <= 0:
                raise ValueError("cow of unallocated block %d" % b)
            if rc == 1:
                return b
            if not self._free:
                telemetry.counter("serving.kv_blocks_alloc_failures").inc()
                raise KVCacheOOM(
                    "KV block pool exhausted: copy-on-write of shared "
                    "block %d needs a free block, 0 free of %d usable"
                    % (b, self.num_usable))
            nb = self._free.pop()
            self._ref[nb] = 1
            self._ref[b] = rc - 1
            # eager device-side page copy — bit-exact K/V into the private
            # block; the writer's table swaps b -> nb after this returns
            self.k_pages = self.copy_block(self.k_pages, b, nb)
            self.v_pages = self.copy_block(self.v_pages, b, nb)
            self.cow_copies += 1
            telemetry.counter("serving.prefix_cow_copies").inc()
            telemetry.counter("serving.kv_blocks_allocs").inc()
            self._refresh_gauges_locked()
            self._check_invariants_locked()
            return nb

    # ---- prefix index ---------------------------------------------------
    def _digests(self, tokens):
        """Chained content digest per FULL block of ``tokens``: digest i
        covers tokens[0 : (i+1)*block_size] plus the position base i, so
        equal digests imply equal token prefix at equal absolute positions
        — the only condition under which cached K/V (position embeddings
        baked in, attention over the whole prefix) is reusable."""
        bs = self.block_size
        out = []
        h = hashlib.sha1()
        for i in range(len(tokens) // bs):
            h.update(b"%d|" % i)
            h.update(np.asarray(  # fwlint: disable=device-escape — host token list -> bytes for hashing; no device value involved
                tokens[i * bs:(i + 1) * bs], np.int64).tobytes())
            out.append(h.digest())
        return out

    def prefix_match(self, tokens):
        """Longest indexed block-aligned prefix of ``tokens``: returns the
        matched block ids IN POSITION ORDER with one reference taken on
        each (the caller owns them exactly like ``alloc`` output — ``free``
        releases). Empty list when the index is cold or disabled."""
        if not self.prefix_cache:
            return []
        digests = self._digests(tokens)
        with self._lock:
            self.prefix_lookups += 1
            telemetry.counter("serving.prefix_lookups").inc()
            got = []
            for d in digests:
                b = self._prefix.get(d)
                if b is None:
                    break
                rc = self._ref.get(b, 0)
                assert rc > 0, (
                    "prefix index invariant violated: indexed block %d has "
                    "no references (index entries must be dropped when the "
                    "refcount hits zero)" % b)
                self._ref[b] = rc + 1
                got.append(b)
            if got:
                self.prefix_hits += 1
                self.prefix_hit_blocks += len(got)
                telemetry.counter("serving.prefix_hits").inc()
                telemetry.counter("serving.prefix_hit_blocks").inc(len(got))
            self._refresh_gauges_locked()
            self._check_invariants_locked()
            return got

    def prefix_insert(self, tokens, blocks):
        """Register a freshly prefilled request's FULL blocks under their
        chain digests. ``blocks[i]`` must hold tokens[i*bs:(i+1)*bs]'s K/V
        at position base i. First writer wins: a digest already indexed
        (e.g. the shared prefix this request itself mapped) is skipped, as
        is any block already indexed under another digest."""
        if not self.prefix_cache:
            return 0
        digests = self._digests(tokens)
        added = 0
        with self._lock:
            for d, b in zip(digests, blocks):
                b = int(b)
                if d in self._prefix or b in self._block_digest:
                    continue
                assert b > 0 and self._ref.get(b, 0) > 0, (
                    "prefix_insert of unallocated block %d" % b)
                self._prefix[d] = b
                self._block_digest[b] = d
                added += 1
            self._refresh_gauges_locked()
            self._check_invariants_locked()
        return added

    def _drop_index_locked(self, b):
        d = self._block_digest.pop(b, None)
        if d is not None:
            self._prefix.pop(d, None)

    def prefix_stats(self):
        """This pool's prefix-sharing snapshot: ``ServingEngine.stats()``
        ["prefix"], which ``tools/serve.py`` serves at ``/stats``."""
        with self._lock:
            shared = [rc for rc in self._ref.values() if rc > 1]
            saved_blocks = sum(rc - 1 for rc in shared)
            return {
                "enabled": self.prefix_cache,
                "lookups": self.prefix_lookups,
                "hits": self.prefix_hits,
                "hit_rate": (self.prefix_hits / self.prefix_lookups
                             if self.prefix_lookups else None),
                "hit_blocks": self.prefix_hit_blocks,
                "shared_blocks": len(shared),
                "kv_bytes_saved": saved_blocks * self.block_nbytes(),
                "cow_copies": self.cow_copies,
                "index_size": len(self._prefix),
            }

    # ---- accounting -----------------------------------------------------
    def _refresh_gauges_locked(self):
        if not self._gauges:
            return
        telemetry.gauge("serving.kv_blocks_used").set(
            self.num_usable - len(self._free))
        telemetry.gauge("serving.kv_blocks_free").set(len(self._free))
        shared = [rc for rc in self._ref.values() if rc > 1]
        telemetry.gauge("serving.prefix_shared_blocks").set(len(shared))
        telemetry.gauge("serving.prefix_kv_bytes_saved").set(
            sum(rc - 1 for rc in shared) * self.block_nbytes())

    def _check_invariants_locked(self):
        # every usable block is exactly one of: free, or referenced;
        # the trash block is neither, and never indexed or shared
        assert len(self._free) + len(self._ref) == self.num_usable, (
            "KV pool accounting drift: %d free + %d referenced != %d usable"
            % (len(self._free), len(self._ref), self.num_usable))
        assert 0 not in self._ref and 0 not in self._block_digest, \
            "trash block must never be refcounted or indexed"
        assert len(self._prefix) == len(self._block_digest), \
            "prefix index maps out of sync"


class StateSlots:
    """Per-stream recurrent state of the "mamba" layers, or of the "kda"
    layers: for each of ``Ls`` layers a conv tail ``((K - 1) Dn,)`` in the
    activations' type and a state in float32, ``num_slots`` of each
    (``ModelConfig.slot_shapes`` names the two). A stream holds ONE slot for
    all its layers, fixed in size whatever its length; the step programs
    update the slots in place (donated, aliased). Slot 0 is the trash slot:
    padded batch rows and scratch programs point at it.

    The conv tails are ``(Ls, NS, (K - 1) Dn)``: a slot is a row, so that
    the three-row tail is not padded to a sixteen-row tile. A "mamba"
    layer's states are ``(Ls, NS, N, Dn)``: states along the sublanes,
    channels along the lanes (``ops/ssm.py``); a "kda" layer's ``(Ls, NS, H,
    dk, dv)``: a head's matrix is whole tiles, the values along the lanes
    (``ops/kda.py``), and its tail holds the q, k and v projections' rows
    side by side."""

    def __init__(self, num_layers, num_slots, conv_width, state_shape,
                 conv_dtype=np.float32, device=None):
        if num_slots < 2:
            raise ValueError("StateSlots needs >= 2 slots (slot 0 is the "
                             "reserved trash slot)")
        import jax
        import jax.numpy as jnp

        self.num_layers = int(num_layers)
        self.num_slots = int(num_slots)
        conv = jnp.zeros((self.num_layers, self.num_slots, int(conv_width)),
                         conv_dtype)
        ssm = jnp.zeros((self.num_layers, self.num_slots)
                        + tuple(state_shape), jnp.float32)
        if device is not None:
            conv, ssm = (jax.device_put(a, device) for a in (conv, ssm))
        #: the device arrays; the engine REPLACES them after every step
        self.conv, self.ssm = conv, ssm
        self._free = list(range(self.num_slots - 1, 0, -1))
        telemetry.gauge("serving.state_slots_used").set(0)

    @property
    def num_usable(self):
        return self.num_slots - 1

    def available(self):
        return len(self._free)

    def used(self):
        return self.num_usable - len(self._free)

    def slot_nbytes(self):
        """Device bytes one stream's slot pins, over all layers."""
        return self.nbytes() // self.num_slots

    def nbytes(self):
        return int(self.conv.size * self.conv.dtype.itemsize
                   + self.ssm.size * 4)

    def alloc(self):
        if not self._free:
            raise KVCacheOOM("no free state slot (%d in use)"
                             % self.num_usable)
        slot = self._free.pop()
        telemetry.gauge("serving.state_slots_used").set(self.used())
        return slot

    def free(self, slot):
        slot = int(slot)
        if not 0 < slot < self.num_slots or slot in self._free:
            raise ValueError("free of invalid or free state slot %d" % slot)
        self._free.append(slot)
        telemetry.gauge("serving.state_slots_used").set(self.used())


class StreamState:
    """What a stream of a model with window and state layers holds BESIDE
    its full-pool blocks, booked by one manager so that admission is
    atomic over the three kinds:

    * a state slot (:class:`StateSlots`), if the model has "mamba" or "kda"
      layers;
    * window-pool blocks ``req.wblocks``, by position like ``req.blocks``
      but only for the last ``window`` keys: entry ``j`` is the block of
      positions ``j * bs ..``, or 0 once it lies wholly behind the window
      (freed during decode; a long prompt's prefill never gets them). A
      stream holds at most ``window + block_size`` tokens of them, and
      the slots a decode chunk writes beyond its first.

    Called from the stepping thread only (the scheduler's and the
    engine's, under the engine's lock)."""

    def __init__(self, window_pool, slots, window):
        self.pool = window_pool     # None: no window layers
        self.slots = slots          # None: no state layers
        self.window = int(window)
        self.blocks_freed = 0
        self.max_blocks_held = 0

    def _span(self, ctx):
        """(first, last) window-pool table entries a step with context
        ``ctx`` (its own position included) touches."""
        bs = self.pool.block_size
        return max(ctx - self.window, 0) // bs, (ctx - 1) // bs

    def blocks_needed(self, n_tokens):
        """Window blocks a stream of ``n_tokens`` cached tokens is admitted
        with: those its FIRST decode step reads and writes."""
        if self.pool is None:
            return 0
        first, last = self._span(n_tokens + 1)
        return last - first + 1

    def can_admit(self, n_tokens):
        return ((self.pool is None
                 or self.blocks_needed(n_tokens) <= self.pool.available())
                and (self.slots is None or self.slots.available() > 0))

    def admit(self, req, n_tokens):
        """Book a slot and the window blocks, or nothing
        (:class:`KVCacheOOM`)."""
        blocks = []
        if self.pool is not None:
            first, _last = self._span(n_tokens + 1)
            blocks = [0] * first + self.pool.alloc(
                self.blocks_needed(n_tokens))
        if self.slots is not None:
            try:
                req.slot = self.slots.alloc()
            except KVCacheOOM:
                if self.pool is not None:
                    self.pool.free([b for b in blocks if b])
                raise
        req.wblocks = blocks
        self._note_held(req)

    def ensure(self, req, pos, last_pos=None):
        """Before decode steps that write positions ``pos .. last_pos``
        (one dispatch; ``last_pos`` defaults to ``pos``): return the
        blocks wholly behind the FIRST step's window to the pool — the
        last step's window has slid past blocks the first still reads —,
        then back the write slots up to the last step's
        (:class:`KVCacheOOM` if the pool is dry; what was freed stays
        freed)."""
        if self.pool is None:
            return
        first, last = self._span(pos + 1)
        if last_pos is not None:
            last = self._span(last_pos + 1)[1]
        behind = [b for b in req.wblocks[:first] if b]
        if behind:
            self.pool.free(behind)
            req.wblocks[:first] = [0] * min(first, len(req.wblocks))
            self.blocks_freed += len(behind)
            telemetry.counter("serving.window.blocks_freed").inc(len(behind))
        while last >= len(req.wblocks):
            req.wblocks.extend(self.pool.alloc(1))
        self._note_held(req)

    def release(self, req):
        if self.pool is not None:
            held = [b for b in req.wblocks if b]
            if held:
                self.pool.free(held)
        req.wblocks = []
        if req.slot is not None:
            self.slots.free(req.slot)
            req.slot = None

    def _note_held(self, req):
        self.max_blocks_held = max(self.max_blocks_held,
                                   sum(1 for b in req.wblocks if b))

    def nbytes(self):
        return ((self.pool.nbytes() if self.pool is not None else 0)
                + (self.slots.nbytes() if self.slots is not None else 0))
