"""Paged KV-cache manager: block pool + block tables + COW prefix cache.

vLLM-style paging mapped onto this framework's state machinery
(*Ragged Paged Attention*, PAPERS.md): instead of one contiguous,
growing [B, S, H, D] cache per sequence (the dense `use_cache` path in
models/generation.py — every length compiles its own executable and a
long sequence pins worst-case memory), K/V live in a pool of fixed-size
blocks

    k_pool[layer]: [num_blocks, block_size, num_heads * head_dim]

and each sequence owns an ordered list of block ids (its *block table*).
Token `i` of a sequence lives at flat slot ``table[i // bs] * bs +
i % bs``.  Appending a token never moves data; freeing a sequence
returns whole blocks to the pool; admission control is a free-list
length check.

**One layout.**  A token's KV heads lie side by side in one row and a
block's tokens in consecutive rows, so flat slot ``s`` is row ``s`` of
the pool seen as ``[num_blocks * block_size, num_heads * head_dim]``.
The step's scatter writes rows of that view in place
(`attention._kv_scatter_impl`), the ragged kernel copies ``[block_size,
lanes]`` lane windows out of the same array (`ops/pallas_ragged.py`),
and `_ragged_ref` gathers from it: nothing between them relays a pool.
The windowed groups' pools, the int8 pools (their scale tables
``[num_blocks, block_size, lanes]`` were always token-major) and a
block on its way to the host or to another pool (``[block_size,
num_heads * head_dim]``: `kv_blocks_gather` / `kv_blocks_scatter`, the
host ring of tiering.py, `HandoffPayload`, transport.py's frames) all
keep this one format.

Block 0 is reserved as the *pad block*: padded batch rows scatter their
garbage K/V there and padded block-table entries point at it — it is
never attributed to a real sequence, and paged attention masks it out
via context_lens.

**Copy-on-write prefix caching** (``PADDLE_TPU_PREFIX_CACHE``, default
on): every FULL block of a prompt gets a chain hash

    h_i = hash((h_{i-1}, tuple(block_tokens)))

so a block's identity covers its whole prefix.  ``allocate(...,
tokens=)`` walks the chain against the hash index and reuses every hit
block (refcount += 1) instead of recomputing it — a fleet of requests
sharing a system prompt pays ONE prefill.  Hits are capped at
``num_tokens - 1`` so at least one token is computed for logits.
Freed blocks whose content is still indexed park in an LRU
(refcount 0, children evicted before parents); eviction only happens
when the free list runs dry, so prefix credit survives preemption:
``free(..., tokens=)`` hashes the dying sequence's full blocks first
and ``requeue`` re-enters through ``allocate`` which finds them again.
Writes into a shared block trigger a COW split (device-side block
copy + table swap); writes into a privately-held but still-indexed
block just de-index it.  ``truncate`` never touches block contents —
it releases whole blocks refcount-aware, so preemption rollback cannot
corrupt a prefix another sequence still reads.

**Per-layer kinds and state slots** (``layer_specs=``, a model's
``cache_spec()``): ``paged_kv`` layers share the block pool and the
tables above (one geometry: KV heads and head dim, which need not be the
query heads'); a ``recurrent`` layer keeps no K/V but its named
fixed-size ``states`` per live request (``{name: {"shape", "dtype"}}``:
a decayed or delta-rule state a head, a convolution's last inputs beside
it), a ``latent`` layer (latent attention, MLA) keeps ONE row of
``width`` lanes a token for all its heads in the same blocks and tables
(one pool a layer, ``[num_blocks, block_size, lanes]`` with ``width``
rounded up to whole 128-lane tiles, no V pool: the values are the row's
first lanes), and a paged layer with ``sparse_sizes`` also
keeps one mean-pooled key per ``stride`` tokens per request (the
block-sparse selector's cache).  Both live in **slot pools**
``[state_slots + 1, ...]``: slot 0 is the pad slot, ``allocate`` hands a
request one slot for all such layers (``can_allocate`` counts them:
slots are a resource the scheduler admits by), its first prefill chunk
zeroes the state, ``free`` returns the slot, and a requeued request
recomputes its state as it does its blocks.  A block hash cannot
restore either, so the prefix cache stands aside for such models
(``prefix_bypassed`` / ``prefix_cache.bypassed_recurrent``), and a
sequence with state cannot be exported to another pool.

**Groups of paged layers** (a spec's ``window``): the paged layers that
see the whole sequence (no ``window``) form the *full group*, which is
everything above.  Layers that see only the last ``window`` tokens form
a `WindowGroup` a window value: a pool, a free list and block tables of
its own over the same block geometry, sized from the window, the most
tokens a step writes to a row (``window_span``) and the rows
(``state_slots``), so that a row's blocks are always there.  A windowed
group gives a row's blocks back as its position passes them
(`release_passed`), between the chunks of a long prompt too, and its
table holds only what is still held, from a *context base*.  A block
hash cannot restore blocks that were given back, so the prefix cache
stands aside for such models (``prefix_cache.bypassed_window``), and a
sequence cannot be exported.  ``max_model_len`` bounds the full group
only.

The pool tensors are ordinary framework Tensors.  The engine's
``to_static`` step functions read them (discovered as state) and write
them via ``_inplace_update`` (mutated state → donated to XLA), so the
compiled step updates the cache in place at 1x memory.

HBM accounting: the pool registers itself with the memory guard
(``register_resident``) as a named **"kv cache blocks"** line item —
the charge is the PHYSICAL pool size, fixed at construction, so shared
prefix blocks are never double-charged no matter how many logical
copies exist (``stats()`` reports ``logical_blocks`` vs
``physical_blocks`` to make the sharing visible in ``HbmBudgetError``
triage).

Sizing: ``num_blocks`` explicit, or derived from the HBM budget
(``PADDLE_TPU_HBM_BUDGET`` / device bytes_limit) via ``hbm_fraction``.
``PADDLE_TPU_KV_BLOCK_SIZE`` (default 16) sets the block size.

Utilization rides the observability registry: gauges
``serving.kv_blocks_total`` / ``serving.kv_blocks_in_use`` /
``serving.kv_utilization`` / ``serving.kv_blocks_shared`` /
``serving.prefix_hit_rate`` plus a host-side high-water mark.

**Host tiering** (tiering.py, ``PADDLE_TPU_KV_TIERING`` /
``PADDLE_TPU_KV_HOST_BUDGET``): when an LRU eviction would delete a
still-indexed refcount-0 block, its bytes (and int8 scale rows) are
demoted to a bounded host-RAM ring instead and the chain-hash entry
follows them.  The chain walk then resolves each link against BOTH
tiers — an HBM hit is shared in place, a host hit is promoted back
(fresh block + ``device_put``) and counts as cached tokens exactly
like an HBM hit, so the effective prefix cache is host-RAM sized.
A hash lives in exactly one tier at a time: indexing a block in HBM
drops any host twin, and spilling only happens at the moment the HBM
copy is evicted.  ``truncate`` bumps a *commit generation* and
``commit_prefix`` re-verifies stored hashes against the actual tokens,
so a truncated-then-regrown sequence can never re-index — or promote —
a stale entry.  ``export_sequence`` / ``import_sequence`` reuse the
same host representation to move a whole sequence between pools
(the disaggregated prefill→decode handoff, serving/disagg.py).
"""
from __future__ import annotations

import os
import time
from collections import OrderedDict

import numpy as np

from ... import observability as obs
from .tiering import (HandoffPayload, HostKVPool, _dma_span, _observe_dma,
                      kv_host_budget, kv_tiering_enabled)

__all__ = ["ENV_KV_BLOCK_SIZE", "ENV_PREFIX_CACHE", "kv_block_size",
           "prefix_cache_enabled", "PagedKVCache", "RESIDENT_NAME"]

ENV_KV_BLOCK_SIZE = "PADDLE_TPU_KV_BLOCK_SIZE"
ENV_PREFIX_CACHE = "PADDLE_TPU_PREFIX_CACHE"
_DEFAULT_BLOCK_SIZE = 16


def _kv_dma_policy():
    """Retry schedule for host-tier DMA: one fast retry, then the
    caller degrades the transfer to a cache miss (never a crash)."""
    from ...distributed.fault_tolerance.retry import RetryPolicy
    return RetryPolicy(retries=1, base=0.001, factor=2.0, max_delay=0.01)
RESIDENT_NAME = "kv cache blocks"

# when no budget is visible (CPU tests without PADDLE_TPU_HBM_BUDGET)
_DEFAULT_NUM_BLOCKS = 256
_MIN_NUM_BLOCKS = 8
_MAX_NUM_BLOCKS = 65536


def kv_block_size():
    """Tokens per KV block (PADDLE_TPU_KV_BLOCK_SIZE, default 16)."""
    try:
        v = int(os.environ.get(ENV_KV_BLOCK_SIZE, _DEFAULT_BLOCK_SIZE))
    except ValueError:
        return _DEFAULT_BLOCK_SIZE
    return max(1, v)


def prefix_cache_enabled():
    """Whether COW prefix caching is on (PADDLE_TPU_PREFIX_CACHE,
    default "1"; "0"/"false"/"off" disable)."""
    return os.environ.get(ENV_PREFIX_CACHE, "1").lower() not in (
        "0", "false", "off")


class WindowGroup:
    """The paged layers of one ``window``: a pool of blocks, a free list
    and per-sequence tables of their own.  A sequence holds only the
    blocks its next token can still see and the ones this step writes;
    block 0 is the pad block, as in the full group."""

    def __init__(self, window, layers, block_size, rows, span):
        self.window, self.layers = int(window), tuple(layers)
        self.block_size = int(block_size)
        self.rows = int(rows)
        #: a row's most blocks: the window behind a step's first token,
        #: the ``span`` tokens the step writes, and the two partial
        #: blocks at the ends
        self.table_width = -(-(self.window + int(span))
                             // self.block_size) + 2
        self.num_blocks = self.rows * self.table_width + 1
        self.bytes_per_block = 0       # set by the pool (its geometry)
        self._free = list(range(self.num_blocks - 1, 0, -1))
        self._first = {}       # seq_id -> logical index of its table[0]
        self._tables = {}      # seq_id -> [block ids] from that index on
        self.high_water = 0
        self.released = 0      # blocks given back as positions passed

    @property
    def blocks_in_use(self):
        return self.num_blocks - 1 - len(self._free)

    @property
    def pool_bytes(self):
        return self.num_blocks * self.bytes_per_block

    def admits(self):
        """Whether one more sequence has a row's worth of blocks."""
        return len(self._tables) < self.rows

    def open(self, seq_id):
        self._first[seq_id], self._tables[seq_id] = 0, []

    def extend(self, seq_id, length):
        """Hold blocks for every position below ``length``."""
        table = self._tables[seq_id]
        need = -(-int(length) // self.block_size) \
            - self._first[seq_id] - len(table)
        if need > len(self._free):
            raise RuntimeError(
                f"the window-{self.window} group has {len(self._free)} "
                f"free blocks and sequence {seq_id!r} needs {need}: it "
                "is sized so that every row's blocks are there")
        for _ in range(need):
            table.append(self._free.pop())
        self.high_water = max(self.high_water, self.blocks_in_use)

    def release_passed(self, seq_id, position):
        """Give back the blocks that lie wholly behind the window of the
        token at ``position`` (the lowest one this step computes).
        Returns how many went."""
        keep_from = max(0, int(position) - self.window + 1) \
            // self.block_size
        table = self._tables[seq_id]
        gone = min(len(table), max(0, keep_from - self._first[seq_id]))
        if gone:
            self._free.extend(table[:gone])
            del table[:gone]
            self._first[seq_id] += gone
            self.released += gone
        return gone

    def truncate(self, seq_id, length):
        """Drop the blocks past ``length`` tokens (never one that was
        given back: a roll-back ends at or after the step's first
        token)."""
        table = self._tables[seq_id]
        keep = max(0, -(-int(length) // self.block_size)
                   - self._first[seq_id])
        while len(table) > keep:
            self._free.append(table.pop())

    def free(self, seq_id):
        self._free.extend(self._tables.pop(seq_id, ()))
        self._first.pop(seq_id, None)

    def context_base(self, seq_id):
        """Tokens before the first block the sequence still holds."""
        return self._first[seq_id] * self.block_size

    def slot_mapping(self, seq_id, start, count):
        pos = np.arange(int(start), int(start) + int(count))
        blocks = np.asarray(self._tables[seq_id], np.int32)[
            pos // self.block_size - self._first[seq_id]]
        return (blocks * self.block_size
                + pos % self.block_size).astype(np.int32)

    def block_table(self, seq_id):
        out = np.zeros(self.table_width, np.int32)
        table = self._tables[seq_id]
        out[:len(table)] = table
        return out

    def stats(self):
        return {"window": self.window, "layers": len(self.layers),
                "num_blocks": self.num_blocks - 1,
                "blocks_in_use": self.blocks_in_use,
                "high_water": self.high_water,
                "pool_bytes": self.pool_bytes,
                "blocks_released": self.released}


class PagedKVCache:
    """Block pool + allocator + per-sequence block tables + COW prefix
    cache.

    Host-side bookkeeping only lives here (free list, tables, lengths,
    refcounts, the prefix hash index); the device-side gather/scatter
    is in serving/attention.py, driven by the arrays this class builds
    (slot mappings, padded block tables, context lengths).  The only
    device work initiated here is the COW block copy.
    """

    def __init__(self, num_layers=None, num_heads=None, head_dim=None,
                 dtype="float32", block_size=None, num_blocks=None,
                 max_model_len=None, hbm_fraction=0.3, register=True,
                 prefix_cache=None, resident_name=None, tiering=None,
                 host_budget=None, layer_specs=None, state_slots=None,
                 window_span=None):
        import jax.numpy as jnp
        from ...core.dtypes import to_jax_dtype
        from ...core.tensor import Tensor

        from ...ops.pallas_ragged import KV_SCALE_LANES

        # per-layer kinds (a model's ``cache_spec()``): "paged_kv"
        # layers share the block pool and the tables (one geometry),
        # "recurrent" layers keep a fixed-size state per live request
        # in a slot pool, and a paged layer with "sparse_sizes" (a
        # block-sparse selector's sizes) also keeps one pooled key per
        # ``stride`` tokens per request
        if layer_specs is None:
            layer_specs = [{"kind": "paged_kv", "num_kv_heads": num_heads,
                            "head_dim": head_dim}] * int(num_layers)
        self.layer_specs = [dict(s) for s in layer_specs]
        paged = [(i, s) for i, s in enumerate(self.layer_specs)
                 if s["kind"] in ("paged_kv", "latent")]
        if len({s["kind"] for _, s in paged}) > 1:
            raise ValueError("a model's paged layers are all paged K/V or "
                             "all latent rows, not both")
        #: latent layers: one pool a layer, a row of ``width`` lanes a
        #: token for every head (it is the keys, and its first lanes the
        #: values)
        self.latent = any(s["kind"] == "latent" for _, s in paged)
        # a latent row in whole 128-lane tiles: HBM lays a row out so
        # anyway, and the kernel's copy of a block cannot name fewer
        # lanes than the row holds there (the tail lanes stay zero)
        geometry = {(1, -(-int(s["width"]) // 128) * 128)
                    if s["kind"] == "latent"
                    else (int(s["num_kv_heads"]), int(s["head_dim"]))
                    for _, s in paged}
        if len(geometry) != 1:
            by_group = sorted({(s.get("window"), int(s["num_kv_heads"]),
                                int(s["head_dim"])) for _, s in paged},
                              key=str)
            raise ValueError(
                "the paged layers of a model, whatever group they are "
                "in, share one block geometry (KV heads, head dim); got "
                f"(window, heads, dim) {by_group or 'no paged layer'}")
        # groups of paged layers by window: the layers with none are
        # the full group (this object's own tables, free list and
        # prefix cache); each window value is a WindowGroup
        full = [(i, s) for i, s in paged if not s.get("window")]
        if not full:
            raise ValueError(
                "a model's paged layers need at least one without a "
                "window: the full group's tables carry every sequence's "
                "length")
        windows = sorted({int(s["window"]) for _, s in paged
                          if s.get("window")})
        #: model layer -> index into the full group's pools
        self._kv_index = {i: n for n, (i, _) in enumerate(full)}
        self.num_layers = len(full)
        (self.num_heads, self.head_dim), = geometry
        forced = {int(s["block_size"]) for _, s in paged
                  if s.get("block_size")}
        if len(forced) > 1 or (forced and block_size
                               and int(block_size) not in forced):
            raise ValueError(
                f"the model's layers fix the KV block size to "
                f"{sorted(forced)}; the pool was asked for {block_size}")
        self.block_size = int(block_size or (forced and forced.pop())
                              or kv_block_size())
        self._jdtype = jnp.dtype(to_jax_dtype(dtype))
        #: int8 pools carry per-slot f32 dequant scale tables
        #: ``[num_blocks, block_size, KV_SCALE_LANES]`` per layer per
        #: side; every token is quantized independently at scatter time
        #: (amax over its (H, D) slice), so a block filling up across
        #: decode steps never re-scales already-written slots.
        self.quantized = self._jdtype == jnp.dtype(jnp.int8)
        if self.latent and (self.quantized or windows):
            raise NotImplementedError(
                "a latent pool is kept in the model's type and seen whole: "
                "no int8 scale tables, no window")
        self.scale_lanes = KV_SCALE_LANES if self.quantized else 0
        #: pools a layer: K and V, or the latent row alone
        self.sides = 1 if self.latent else 2
        # byte charge follows the ELEMENT dtype (int8 = 1 byte) plus the
        # scale-table overhead, so a fixed HBM budget admits ~2x blocks
        self.bytes_per_block = (self.sides * self.num_layers * self.num_heads
                                * self.block_size * self.head_dim
                                * self._jdtype.itemsize
                                + 2 * self.num_layers * self.block_size
                                * self.scale_lanes * 4)
        if num_blocks is None:
            num_blocks = self._blocks_from_budget(hbm_fraction)
        # +1: block 0 is the reserved pad block, never allocated
        self.num_blocks = max(_MIN_NUM_BLOCKS, int(num_blocks)) + 1
        self.max_model_len = int(max_model_len) if max_model_len else None
        # fixed block-table width: enough blocks for the longest
        # sequence the model can hold (bounds the decode program shape)
        cap = self.max_model_len or (self.num_blocks - 1) * self.block_size
        self.table_width = max(
            1, -(-cap // self.block_size))  # ceil div
        self.prefix_cache = (prefix_cache_enabled()
                             if prefix_cache is None else bool(prefix_cache))

        shape = (self.num_blocks, self.block_size,
                 self.num_heads * self.head_dim)
        self._pools = []  # [(k_tensor, v_tensor)] per layer; latent: (row,)
        self._scales = []  # [(k_scale, v_scale)] per layer (int8 only)
        for i in range(self.num_layers):
            k = Tensor(jnp.zeros(shape, self._jdtype), _internal=True,
                       stop_gradient=True)
            if self.latent:
                k.name = f"kv_cache.latent.layer{i}"
                self._pools.append((k,))
                continue
            k.name = f"kv_cache.k.layer{i}"
            v = Tensor(jnp.zeros(shape, self._jdtype), _internal=True,
                       stop_gradient=True)
            v.name = f"kv_cache.v.layer{i}"
            self._pools.append((k, v))
            if self.quantized:
                sshape = (self.num_blocks, self.block_size,
                          self.scale_lanes)
                ks = Tensor(jnp.zeros(sshape, jnp.float32),
                            _internal=True, stop_gradient=True)
                ks.name = f"kv_cache.k_scale.layer{i}"
                vs = Tensor(jnp.zeros(sshape, jnp.float32),
                            _internal=True, stop_gradient=True)
                vs.name = f"kv_cache.v_scale.layer{i}"
                self._scales.append((ks, vs))

        # -- windowed groups -----------------------------------------------
        self.window_groups = []
        self._layer_pool = {i: self._pools[n]
                            for i, n in self._kv_index.items()}
        self._group_of = {i: None for i in self._kv_index}
        if windows and self.quantized:
            raise NotImplementedError(
                "an int8 pool keeps scale tables per block; the windowed "
                "groups do not carry them yet")
        if windows and not state_slots:
            raise ValueError("windowed layers need state_slots (the rows "
                             "their group is sized for)")
        for window in windows:
            layers = [i for i, s in paged if s.get("window") == window]
            group = WindowGroup(window, layers, self.block_size,
                                state_slots,
                                window_span or self.block_size)
            group.bytes_per_block = (2 * len(layers) * self.num_heads
                                     * self.block_size * self.head_dim
                                     * self._jdtype.itemsize)
            gshape = (group.num_blocks,) + shape[1:]
            for i in layers:
                pair = []
                for side in "kv":
                    t = Tensor(jnp.zeros(gshape, self._jdtype),
                               _internal=True, stop_gradient=True)
                    t.name = f"kv_cache.{side}.window{window}.layer{i}"
                    pair.append(t)
                self._layer_pool[i] = tuple(pair)
                self._group_of[i] = group
            self.window_groups.append(group)
        self.prefix_bypassed_window = 0   # admissions it stood aside for

        # -- per-request state (slot pools) --------------------------------
        # slot 0 is the pad slot (idle rows point at it, as padded
        # tokens point at block 0); a request holds one slot from
        # admission to its end, across every layer that keeps state
        stateful = [(i, s) for i, s in enumerate(self.layer_specs)
                    if s["kind"] == "recurrent"
                    or s.get("sparse_sizes")]
        self.state_slots = int(state_slots or 0) if stateful else 0
        if stateful and not self.state_slots:
            raise ValueError("layers that keep per-request state need "
                             "state_slots (one per live request)")
        #: model layer -> {name: [slots + 1, *shape]}
        self._state = {}
        self._compressed = {}   # model layer -> [slots + 1, Hkv, J, D]
        for i, spec in stateful:
            if spec["kind"] == "recurrent":
                self._state[i] = {}
                for name, state in spec["states"].items():
                    t = Tensor(jnp.zeros(
                        (self.state_slots + 1,) + tuple(state["shape"]),
                        jnp.dtype(to_jax_dtype(state["dtype"]))),
                        _internal=True, stop_gradient=True)
                    t.name = f"kv_cache.{name}.layer{i}"
                    self._state[i][name] = t
            else:
                # one pooled key per `stride` tokens, to the longest
                # context, in whole 128-lane tiles
                keys = spec["sparse_sizes"].num_keys(cap)
                t = Tensor(jnp.zeros(
                    (self.state_slots + 1, self.num_heads,
                     -(-keys // 128) * 128, self.head_dim), self._jdtype),
                    _internal=True, stop_gradient=True)
                t.name = f"kv_cache.compressed.layer{i}"
                self._compressed[i] = t
        self._free_slots = list(range(self.state_slots, 0, -1))
        self._slot_of = {}     # seq_id -> state slot
        if self.state_slots or self.window_groups:
            # a block hash cannot restore a recurrent state, the
            # selector's pooled keys or blocks a window gave back: the
            # prefix cache stands aside
            self.prefix_cache = False
        self.prefix_bypassed = 0   # admissions it stood aside for

        self._free = list(range(self.num_blocks - 1, 0, -1))  # pop() → 1
        self._tables = {}      # seq_id -> [block ids]
        self._lengths = {}     # seq_id -> tokens stored
        self._seq_adapter = {} # seq_id -> LoRA adapter id (None = base)
        # prefix-cache state
        self._ref = {}         # block -> refcount (blocks in any table)
        self._hash_of = {}     # block -> chain hash (full prefix blocks)
        self._by_hash = {}     # chain hash -> canonical block
        self._cached_free = OrderedDict()  # refcount-0 indexed blocks LRU
        self._cached_len = {}  # seq_id -> tokens served from the cache
        self._hit_tokens = 0   # prefix tokens reused, cumulative
        self._lookup_tokens = 0  # prompt tokens that consulted the index
        self.cow_splits = 0    # COW block copies performed, cumulative
        self.high_water = 0    # max blocks in use, ever
        # -- host tier (tiering.py) --------------------------------------
        # evicted-but-indexed blocks spill into a bounded host ring; a
        # chain hash lives in EXACTLY one tier (_by_hash xor _host_of)
        if self.window_groups or self.latent:
            # a windowed model indexes nothing, so nothing spills; the
            # host ring holds K and V blocks, not latent rows
            tiering = False
        if tiering is None:
            tiering = kv_tiering_enabled() and kv_host_budget() is not None
        if host_budget is None:
            host_budget = kv_host_budget()
        if tiering and host_budget is None:
            # explicit tiering=True with no budget: mirror the HBM pool
            host_budget = self.full_pool_bytes
        host_slots = (int(host_budget) // self.bytes_per_block
                      if tiering and host_budget else 0)
        self.host = None
        if host_slots >= 1:
            # _jdtype is a numpy dtype (ml_dtypes covers bf16), so the
            # host ring stores the exact on-device representation
            self.host = HostKVPool(
                self.num_layers, self.num_heads, self.block_size,
                self.head_dim, self._jdtype, self.scale_lanes,
                host_slots)
        self._host_of = {}     # chain hash -> host ring slot
        self._host_hash = {}   # host ring slot -> chain hash
        self._host_lru = OrderedDict()  # slot -> None, eviction order
        self._host_pin = set()  # slots an in-progress allocate holds
        self._host_gen = {}    # slot -> commit generation at spill time
        #: bumped by truncate(): the stale-guard epoch — a host entry
        #: spilled before a truncate is verified, never blindly trusted
        self._commit_gen = 0
        self.host_spills = 0
        self.host_promotes = 0
        self.host_evictions = 0
        self.stale_hash_drops = 0
        self._host_hit_tokens = 0
        # a second pool in the same process (the speculative draft
        # cache) charges its own line item so HBM triage separates them
        self.resident_name = resident_name or RESIDENT_NAME
        self._registered = False
        self._host_registered = False
        if register:
            self._register_resident()
        self._update_gauges()

    # -- sizing ----------------------------------------------------------
    def _blocks_from_budget(self, fraction):
        from ...memory.estimator import device_hbm_budget
        budget = device_hbm_budget()
        if not budget:
            return _DEFAULT_NUM_BLOCKS
        n = int(budget * float(fraction)) // self.bytes_per_block
        return max(_MIN_NUM_BLOCKS, min(_MAX_NUM_BLOCKS, n))

    @property
    def pool_bytes(self):
        """Bytes of every group's pool."""
        return self.full_pool_bytes + sum(g.pool_bytes
                                          for g in self.window_groups)

    @property
    def full_pool_bytes(self):
        return self.num_blocks * self.bytes_per_block

    @property
    def state_pool_bytes(self):
        """Bytes of the recurrent layers' state slots."""
        return sum(int(t._value.nbytes) for t in self._state_tensors())

    def _state_tensors(self):
        return [t for named in self._state.values()
                for t in named.values()]

    @property
    def compressed_pool_bytes(self):
        """Bytes of the sparse layers' pooled-key slots."""
        return sum(int(t._value.nbytes)
                   for t in self._compressed.values())

    def _register_resident(self):
        from ...memory.guard import register_resident
        register_resident(
            self.resident_name,
            self.pool_bytes + self.state_pool_bytes
            + self.compressed_pool_bytes,
            buffer_ids=lambda: {id(t._value)
                                for t in self.pool_tensors()})
        self._registered = True
        if self.host is not None:
            # host=True: a named line item for triage, NOT charged
            # against the device HBM preflight
            register_resident(self.host_resident_name,
                              self.host.nbytes, host=True)
            self._host_registered = True

    @property
    def host_resident_name(self):
        return f"{self.resident_name} host tier"

    def close(self):
        """Drop the memory-guard charge (the pool itself dies with the
        last reference)."""
        if self._registered:
            from ...memory.guard import unregister_resident
            unregister_resident(self.resident_name)
            self._registered = False
        if self._host_registered:
            from ...memory.guard import unregister_resident
            unregister_resident(self.host_resident_name, host=True)
            self._host_registered = False

    # -- pool tensors ----------------------------------------------------
    def layer_pools(self, layer):
        """(k_pool, v_pool) Tensors for one (model) layer, whichever
        group it is in."""
        return self._layer_pool[layer]

    def layer_group(self, layer):
        """The `WindowGroup` of a paged layer (None: the full group)."""
        return self._group_of[layer]

    def layer_scales(self, layer):
        """(k_scale, v_scale) per-slot dequant tables for one layer
        (int8 pools only; None otherwise)."""
        if not self.quantized:
            return None
        return self._scales[self._kv_index[layer]]

    def layer_state(self, layer, name):
        """One of a recurrent layer's state pools, ``[slots + 1,
        *shape]``, by its name in the layer's spec."""
        return self._state[layer][name]

    def layer_compressed(self, layer):
        """A sparse layer's pooled-key pool ``[slots + 1, Hkv, J, D]``."""
        return self._compressed[layer]

    def pool_tensors(self):
        windowed = [self._layer_pool[i] for g in self.window_groups
                    for i in g.layers]
        return ([t for kv in (self._pools + self._scales + windowed)
                 for t in kv]
                + self._state_tensors()
                + list(self._compressed.values()))

    # -- state slots -----------------------------------------------------
    def slot(self, seq_id):
        """The state slot a live sequence holds (0: none)."""
        return self._slot_of.get(seq_id, 0)

    @property
    def free_state_slots(self):
        return len(self._free_slots)

    # -- allocator -------------------------------------------------------
    @property
    def free_blocks(self):
        """Blocks available for allocation: virgin free blocks plus the
        evictable refcount-0 prefix-cache LRU."""
        return len(self._free) + len(self._cached_free)

    @property
    def blocks_in_use(self):
        """PHYSICAL blocks held by live sequences (shared counted
        once; parked cache blocks are not in use)."""
        return (self.num_blocks - 1) - self.free_blocks

    @property
    def logical_blocks(self):
        """Sum of table lengths: what the sequences would occupy
        WITHOUT sharing."""
        return sum(len(t) for t in self._tables.values())

    @property
    def shared_blocks(self):
        """Physical blocks referenced by more than one sequence."""
        return sum(1 for c in self._ref.values() if c > 1)

    def blocks_needed(self, num_tokens):
        return -(-int(num_tokens) // self.block_size)

    def can_allocate(self, num_tokens, tokens=None, headroom=0,
                     adapter=None):
        """Admission check; with ``tokens`` prefix-cache hits count as
        already available (a hit parked in the LRU is reactivated, not
        consumed from the free capacity).  ``headroom`` blocks are held
        back for the decode growth of already-running sequences — an
        admission that consumed them could be preempted right back out
        by the very decode appends it displaced, and the retry would
        livelock."""
        if self.state_slots and not self._free_slots:
            return False          # every state slot is held
        if not all(g.admits() for g in self.window_groups):
            return False          # every row of a windowed group is held
        chain = self._walk_chain(tokens, num_tokens, adapter=adapter)
        hbm_hits = [ref for _, kind, ref in chain if kind == "hbm"]
        # a HOST hit still consumes a physical block (the promotion
        # DMAs into a fresh one) — only HBM hits reduce the need
        need = self.blocks_needed(num_tokens) - len(hbm_hits)
        # same capacity formula as allocate(): a parked hit block is
        # reactivated, not consumed — but it must not ALSO be counted
        # as evictable free capacity
        hits_parked = sum(1 for b in hbm_hits if b in self._cached_free)
        capacity = (len(self._free)
                    + len(self._cached_free) - hits_parked)
        return need + int(headroom) <= capacity

    def _chain_hash(self, prev, block_tokens, adapter=None):
        # the chain root is seeded with the pool dtype so a bf16 block
        # and an int8 block holding the same tokens can never alias
        # (their stored bytes differ) — matters when tables/hashes
        # migrate across pools, e.g. a failover replay onto a replica
        # configured with a different PADDLE_TPU_KV_DTYPE.  The LoRA
        # adapter id seeds the root the same way: an adapter changes
        # the K/V bytes every layer writes, so two tenants prefilling
        # the same prompt must never alias cache entries
        if prev is None:
            prev = (str(self._jdtype),
                    None if adapter is None else str(adapter))
        return hash((prev, tuple(int(t) for t in block_tokens)))

    def _walk_chain(self, tokens, num_tokens, adapter=None):
        """``[(hash, tier, ref)]`` for the longest cached block-aligned
        prefix of ``tokens``, resolved against BOTH tiers: ``("hbm",
        block_id)`` entries are sharable in place, ``("host", slot)``
        entries need promotion.  Capped so at least one of
        ``num_tokens`` is still computed (the model must produce
        logits).  Read-only — safe from ``can_allocate`` and the
        affinity router."""
        chain = []
        if not self.prefix_cache or tokens is None:
            return chain
        bs = self.block_size
        h = None
        max_reuse = int(num_tokens) - 1   # leave >= 1 token to compute
        for b in range(min(len(tokens), int(num_tokens)) // bs):
            if (b + 1) * bs > max_reuse:
                break
            h = self._chain_hash(h, tokens[b * bs:(b + 1) * bs],
                                 adapter=adapter)
            blk = self._by_hash.get(h)
            if blk is not None:
                chain.append((h, "hbm", blk))
                continue
            slot = self._host_of.get(h)
            if slot is not None:
                chain.append((h, "host", slot))
                continue
            break
        return chain

    def _prefix_hits(self, tokens, num_tokens, adapter=None):
        """HBM-resident blocks covering the longest cached prefix that
        needs NO promotion DMA (legacy view of ``_walk_chain``)."""
        hits = []
        for _, kind, ref in self._walk_chain(tokens, num_tokens,
                                             adapter=adapter):
            if kind != "hbm":
                break
            hits.append(ref)
        return hits

    def _take_block(self):
        """One writable block: prefer virgin free blocks, else evict
        the least-recently-used refcount-0 cached block (de-indexing
        its hash).  With tiering the evicted block's bytes are demoted
        to the host ring first — the prefix survives, one DMA away."""
        if self._free:
            return self._free.pop()
        blk, _ = self._cached_free.popitem(last=False)
        h = self._hash_of.pop(blk, None)
        if h is not None and self._by_hash.get(h) == blk:
            del self._by_hash[h]
            if self.host is not None:
                self._spill(blk, h)
        return blk

    # -- host tier -------------------------------------------------------
    def _host_take_slot(self):
        """A writable host ring slot, evicting the host-LRU entry if
        the ring is full (pinned slots — promotions in flight for the
        current allocate — are never victims).  None when every slot is
        pinned."""
        slot = self.host.take()
        if slot is not None:
            return slot
        for victim in self._host_lru:
            if victim not in self._host_pin:
                self._drop_host(self._host_hash[victim])
                self.host_evictions += 1
                obs.get_registry().counter(
                    "serving.host_evictions").inc()
                return self.host.take()
        return None

    def _drop_host(self, h):
        """Remove a chain hash's host entry (if any) and return its
        ring slot to the free list.  Called whenever the hash becomes
        canonical in HBM again — a hash lives in exactly one tier — and
        when a stale entry is invalidated."""
        slot = self._host_of.pop(h, None)
        if slot is None:
            return
        self._host_hash.pop(slot, None)
        self._host_lru.pop(slot, None)
        self._host_gen.pop(slot, None)
        self.host.give(slot)

    def _spill(self, blk, h):
        """Demote an evicted, still-indexed block's bytes to the host
        ring.  The device gathers are dispatched first and admitted
        into the in-flight pipeline window (bounding outstanding DMA
        like any compute step), then landed host-side."""
        if h in self._host_of:            # content already host-resident
            self._host_lru.move_to_end(self._host_of[h])
            return
        slot = self._host_take_slot()
        if slot is None:                  # ring exhausted by pins
            return
        from ...core.pipeline import get_window
        from ...distributed.fault_tolerance.plan import fault_point
        from ...distributed.fault_tolerance.retry import RetryExhausted

        def _dma():
            # "kv.dma_fail" fires before any host-side mutation, so a
            # retried (or abandoned) transfer leaks nothing; a full
            # rewrite of the slot makes the retry idempotent
            fault_point("kv.dma_fail")
            ks = [k._value[blk] for k, _ in self._pools]
            vs = [v._value[blk] for _, v in self._pools]
            kss = vss = None
            if self.quantized:
                kss = [s._value[blk] for s, _ in self._scales]
                vss = [s._value[blk] for _, s in self._scales]
            get_window().admit(ks + vs, label="kv:dma:spill")
            self.host.write(
                slot, [np.asarray(x) for x in ks],
                [np.asarray(x) for x in vs],
                kss and [np.asarray(x) for x in kss],
                vss and [np.asarray(x) for x in vss])

        t0 = time.perf_counter()
        try:
            with _dma_span("spill", self.bytes_per_block, block=blk):
                _kv_dma_policy().call(
                    _dma, exceptions=(ConnectionError, OSError),
                    what="kv:spill")
        except RetryExhausted:
            # degrade: the evicted block simply is not host-cached — a
            # future request recomputes it (a miss, never a crash)
            self.host.give(slot)
            obs.get_registry().counter("serving.kv_dma_fail").inc()
            if obs.enabled():
                obs.instant("kv.dma_fail", cat="fault", dir="spill",
                            block=blk)
            return
        _observe_dma("spill", self.bytes_per_block,
                     time.perf_counter() - t0)
        self._host_of[h] = slot
        self._host_hash[slot] = h
        self._host_gen[slot] = self._commit_gen
        self._host_lru[slot] = None
        self.host_spills += 1
        obs.get_registry().counter("serving.host_spills").inc()

    def _promote(self, slot, blk, h):
        """Bring a host-resident prefix block back: ``device_put`` the
        ring slot's bytes (+ scale rows) into a freshly taken block and
        make the hash canonical in HBM again (dropping the host entry —
        one tier per hash).  Returns False when the transfer failed
        after retries — ``blk`` is then unindexed scratch the caller
        recycles, and the entry degrades to a recompute."""
        import jax.numpy as jnp
        from ...core.pipeline import get_window
        from ...distributed.fault_tolerance.plan import fault_point
        from ...distributed.fault_tolerance.retry import RetryExhausted

        def _dma():
            # fires before the hash is re-indexed; a retry rewrites the
            # whole block, so partial state from a failed attempt is
            # overwritten (or discarded with the scratch block)
            fault_point("kv.dma_fail")
            k_parts, v_parts, ks_parts, vs_parts = self.host.read(slot)
            puts = []
            for i, (k, v) in enumerate(self._pools):
                k._inplace_update(
                    k._value.at[blk].set(jnp.asarray(k_parts[i])))
                v._inplace_update(
                    v._value.at[blk].set(jnp.asarray(v_parts[i])))
                puts.extend((k._value, v._value))
            for i, (ks, vs) in enumerate(self._scales):
                ks._inplace_update(
                    ks._value.at[blk].set(jnp.asarray(ks_parts[i])))
                vs._inplace_update(
                    vs._value.at[blk].set(jnp.asarray(vs_parts[i])))
            get_window().admit(puts, label="kv:dma:promote")

        t0 = time.perf_counter()
        try:
            with _dma_span("promote", self.bytes_per_block, block=blk):
                _kv_dma_policy().call(
                    _dma, exceptions=(ConnectionError, OSError),
                    what="kv:promote")
        except RetryExhausted:
            obs.get_registry().counter("serving.kv_dma_fail").inc()
            if obs.enabled():
                obs.instant("kv.dma_fail", cat="fault", dir="promote",
                            block=blk)
            return False
        _observe_dma("promote", self.bytes_per_block,
                     time.perf_counter() - t0)
        self._hash_of[blk] = h
        self._by_hash[h] = blk
        self._drop_host(h)
        self.host_promotes += 1
        obs.get_registry().counter("serving.host_promotes").inc()
        return True

    def _activate(self, blk):
        """Bring a hit block into a table (refcount += 1; un-park it
        from the LRU if it was refcount-0)."""
        if blk in self._cached_free:
            del self._cached_free[blk]
            self._ref[blk] = 1
        else:
            self._ref[blk] = self._ref.get(blk, 0) + 1

    def _release(self, blk):
        """Drop one table reference.  A still-indexed block parks in
        the evictable LRU (most-recently-freed last); anything else
        returns to the virgin free list."""
        c = self._ref.get(blk, 1) - 1
        if c > 0:
            self._ref[blk] = c
            return
        self._ref.pop(blk, None)
        if blk in self._hash_of:
            self._cached_free[blk] = None
            self._cached_free.move_to_end(blk)
        else:
            self._free.append(blk)

    def allocate(self, seq_id, num_tokens, tokens=None, adapter=None):
        """Reserve blocks for a sequence's first ``num_tokens`` tokens
        (prefill).  With ``tokens`` (the prompt) the prefix index is
        consulted and every leading cached block is SHARED instead of
        reserved fresh — ``cached_prefix_len()`` reports how many
        tokens the caller may skip.  ``adapter`` keys the chain hashes
        (and is remembered for the sequence's later commits), so
        tenants only ever share cache with themselves.  Raises KeyError
        on duplicate ids, returns False when the pool cannot hold
        it."""
        if seq_id in self._tables:
            raise KeyError(f"sequence {seq_id!r} already allocated")
        # Chaos site: an injected allocation failure fires BEFORE any
        # pool mutation, so a failed admission provably leaks nothing.
        from ...distributed.fault_tolerance.plan import fault_point
        fault_point("serve.alloc_fail")
        if self.state_slots and not self._free_slots:
            return False
        if not all(g.admits() for g in self.window_groups):
            return False
        chain = self._walk_chain(tokens, num_tokens, adapter=adapter)
        hbm_hits = [ref for _, kind, ref in chain if kind == "hbm"]
        host_slots = [ref for _, kind, ref in chain if kind == "host"]
        # host hits avoid the RECOMPUTE but still need a physical block
        # each (the promotion DMAs into a fresh one)
        need = self.blocks_needed(num_tokens) - len(hbm_hits)
        hits_parked = sum(1 for b in hbm_hits if b in self._cached_free)
        if need > len(self._free) + (len(self._cached_free)
                                     - hits_parked):
            return False
        # activate ALL HBM hits before any _take_block so an eviction
        # for a fresh/promoted block can't consume a later chain hit;
        # pin the host slots so our own spills can't evict them either
        for blk in hbm_hits:
            self._activate(blk)
        self._host_pin.update(host_slots)
        failed_h = None
        try:
            table = []
            for h, kind, ref in chain:
                if kind == "hbm":
                    table.append(ref)
                    continue
                blk = self._take_block()
                if self._promote(ref, blk, h):
                    self._ref[blk] = 1
                    table.append(blk)
                    continue
                # transient DMA failure after retries: unwind this
                # attempt (promoted blocks park back in the cache —
                # their transfer DID land) and degrade below
                failed_h = h
                self._free.append(blk)
                break
            if failed_h is None:
                for _ in range(self.blocks_needed(num_tokens)
                               - len(table)):
                    blk = self._take_block()
                    self._ref[blk] = 1
                    table.append(blk)
            else:
                in_table = set(table)
                for blk in table:
                    self._release(blk)
                for blk in hbm_hits:
                    if blk not in in_table:
                        self._release(blk)
        finally:
            self._host_pin.difference_update(host_slots)
        if failed_h is not None:
            # drop the suspect host entry and re-run: the chain walk now
            # stops where the promotion failed, so the lost tail is
            # recomputed — the engine sees a shorter cached prefix,
            # never the failure
            self._drop_host(failed_h)
            return self.allocate(seq_id, num_tokens, tokens,
                                 adapter=adapter)
        self._tables[seq_id] = table
        self._lengths[seq_id] = int(num_tokens)
        if adapter is not None:
            self._seq_adapter[seq_id] = adapter
        if self.state_slots:
            # the slot is zeroed by the request's first chunk, not here
            self._slot_of[seq_id] = self._free_slots.pop()
            if tokens is not None:
                self.prefix_bypassed += 1
                obs.get_registry().counter(
                    "prefix_cache.bypassed_recurrent").inc()
        for group in self.window_groups:
            # a windowed group's blocks come as the steps write them
            group.open(seq_id)
        if self.window_groups and tokens is not None:
            self.prefix_bypassed_window += 1
            obs.get_registry().counter(
                "prefix_cache.bypassed_window").inc()
        cached = len(chain) * self.block_size
        self._cached_len[seq_id] = cached
        if self.prefix_cache and tokens is not None:
            self._hit_tokens += cached
            self._host_hit_tokens += len(host_slots) * self.block_size
            self._lookup_tokens += int(num_tokens)
        self._update_gauges()
        return True

    def prefix_match_tokens(self, tokens, adapter=None):
        """How many leading tokens of ``tokens`` this pool could serve
        from its prefix cache RIGHT NOW, without allocating anything.
        Used by the data-parallel router to send a request (or a
        failover replay) to the replica already holding its prefix.
        HOST-resident chain links count too — a replica whose prefix
        spilled to its host ring is still the warm target, one
        promotion DMA away instead of a full re-prefill."""
        if tokens is None:
            return 0
        # num_tokens = len+1 lifts the "leave one to compute" cap so a
        # full-prompt match counts every block.
        chain = self._walk_chain(tokens, len(tokens) + 1,
                                 adapter=adapter)
        return len(chain) * self.block_size

    def chain_hashes(self, tokens, adapter=None):
        """The block-granular chain-hash ladder of ``tokens`` —
        ``hashes[b]`` identifies the prefix covering blocks ``0..b``.
        Pure arithmetic over the token ids (no index lookups), so the
        cluster router can hash a prompt ONCE and compare it against
        every host's gossiped digest."""
        bs = self.block_size
        out = []
        h = None
        for b in range(len(tokens) // bs):
            h = self._chain_hash(h, tokens[b * bs:(b + 1) * bs],
                                 adapter=adapter)
            out.append(h)
        return out

    def prefix_digest(self, max_entries=4096):
        """Compact summary of every chain hash this pool can serve —
        BOTH tiers (HBM-indexed and host-spilled) — for gossip.  A set
        membership test against this digest approximates
        ``prefix_match_tokens`` remotely; it is a routing HINT only
        (staleness-bounded by the publisher's heartbeat), never a
        correctness input: a wrong hint just costs a prefix-cache
        miss on the chosen host.  ``max_entries`` bounds the gossip
        message; when truncated, the newest-indexed entries win."""
        hashes = list(self._by_hash.keys()) + list(self._host_of.keys())
        if len(hashes) > max_entries:
            hashes = hashes[-max_entries:]
        return {"hashes": set(hashes), "blocks": len(hashes),
                "block_size": self.block_size,
                "commit_gen": self._commit_gen}

    def cached_prefix_len(self, seq_id):
        """Prompt tokens served from the prefix cache at allocate()
        time — prefill may start at this offset."""
        return self._cached_len.get(seq_id, 0)

    def commit_prefix(self, seq_id, tokens):
        """Index every FULL block covered by ``tokens`` (the sequence's
        written prefix so far) into the prefix cache.  Called by the
        engine after each prefill chunk lands.

        The chain hash is always RECOMPUTED from ``tokens`` and
        verified against a block's stored hash instead of trusted: a
        sequence that truncated mid-chain and regrew with different
        tokens would otherwise keep (and re-anchor!) its stale index
        entry, and a host twin spilled under that hash could later
        promote stale bytes into a fresh allocation.  A mismatch
        de-indexes the block in BOTH tiers before re-indexing."""
        if not self.prefix_cache:
            return
        bs = self.block_size
        table = self._tables[seq_id]
        adapter = self._seq_adapter.get(seq_id)
        n = min(int(len(tokens)), self._lengths[seq_id]) // bs
        h = None
        for b in range(n):
            blk = table[b]
            h = self._chain_hash(h, tokens[b * bs:(b + 1) * bs],
                                 adapter=adapter)
            stored = self._hash_of.get(blk)
            if stored is not None:
                if stored == h:
                    # content verified canonical in HBM: any host twin
                    # of this hash is redundant — drop it so a stale
                    # copy can never outlive the live block
                    self._drop_host(h)
                    continue
                # stale index entry (truncated-then-regrown sequence)
                if self._ref.get(blk, 1) == 1:
                    del self._hash_of[blk]
                    if self._by_hash.get(stored) == blk:
                        del self._by_hash[stored]
                    self._drop_host(stored)
                    self.stale_hash_drops += 1
                    obs.instant("serving.stale_hash", cat="prefill",
                                block=blk, gen=self._commit_gen)
                else:
                    # shared block whose canonical content differs from
                    # OUR tokens: leave the other owners' index alone
                    # and do not claim the hash for this block
                    continue
            other = self._by_hash.get(h)
            if other is None:
                self._hash_of[blk] = h
                self._by_hash[h] = blk
                self._drop_host(h)
            # duplicate content under another canonical block: leave
            # this one unindexed, future lookups hit the canonical one

    def _ensure_writable(self, seq_id, position):
        """Make the block holding ``position`` safe to scatter into.
        Shared block → COW split (device copy + table swap); private
        but still hash-indexed → de-index (the write invalidates the
        cached prefix)."""
        idx = int(position) // self.block_size
        table = self._tables[seq_id]
        if idx >= len(table):
            return
        blk = table[idx]
        if self._ref.get(blk, 1) > 1:
            new = self._take_block()
            self._copy_block(blk, new)
            table[idx] = new
            self._ref[new] = 1
            self._ref[blk] -= 1
            self.cow_splits += 1
            obs.instant("serving.cow_split", cat="decode",
                        src=blk, dst=new)
        elif blk in self._hash_of:
            h = self._hash_of.pop(blk)
            if self._by_hash.get(h) == blk:
                del self._by_hash[h]
            # the write invalidates the content this hash names; a host
            # twin spilled under it would be just as stale
            self._drop_host(h)

    def _copy_block(self, src, dst):
        """Device-side block copy, all layers (the COW split).  Int8
        pools copy the per-slot scale rows alongside the data — a split
        block with stale scales would dequantize to garbage."""
        for pools in self._pools + self._scales:
            for t in pools:
                t._inplace_update(t._value.at[dst].set(t._value[src]))

    def append(self, seq_id, num_tokens=1):
        """Extend a sequence by ``num_tokens`` slots (decode).  Returns
        False (state unchanged) when a needed block isn't available.
        Writing into a still-shared tail block COW-splits it first."""
        length = self._lengths[seq_id]
        table = self._tables[seq_id]
        need = self.blocks_needed(length + num_tokens) - len(table)
        cow = 0
        if length % self.block_size:
            idx = length // self.block_size
            if idx < len(table) and self._ref.get(table[idx], 1) > 1:
                cow = 1                      # split consumes one block
        if need + cow > self.free_blocks:
            return False
        if length % self.block_size:
            self._ensure_writable(seq_id, length)
        for _ in range(need):
            blk = self._take_block()
            self._ref[blk] = 1
            self._tables[seq_id].append(blk)
        self._lengths[seq_id] = length + int(num_tokens)
        for group in self.window_groups:
            group.extend(seq_id, length + int(num_tokens))
        self._update_gauges()
        return True

    def write_window(self, seq_id, start, count):
        """Before a step writes positions ``[start, start + count)`` of a
        sequence: every windowed group gives back the blocks wholly
        behind the window of ``start`` and holds blocks up to the last
        position.  Returns the blocks given back (over groups)."""
        gone = 0
        for group in self.window_groups:
            gone += group.release_passed(seq_id, start)
            group.extend(seq_id, int(start) + int(count))
        return gone

    def truncate(self, seq_id, length):
        """Shrink a sequence back to ``length`` tokens, releasing whole
        blocks past the new end (refcount-aware: a shared block just
        drops one reference — its content is NEVER touched, so rolling
        back decode slots that were reserved but never dispatched
        cannot corrupt a prefix another sequence still reads)."""
        length = int(length)
        if length > self._lengths[seq_id]:
            raise ValueError(
                f"truncate({seq_id!r}, {length}) beyond current "
                f"length {self._lengths[seq_id]}")
        table = self._tables[seq_id]
        keep = self.blocks_needed(length)
        while len(table) > keep:
            self._release(table.pop())
        for group in self.window_groups:
            group.truncate(seq_id, length)
        if length < self._lengths[seq_id]:
            # stale-guard epoch: anything spilled to the host ring
            # before this point must be re-verified against recomputed
            # token hashes before it can be trusted again
            self._commit_gen += 1
        if length % self.block_size:
            # the new end cuts INTO a block; if that block is indexed
            # and exclusively ours, the regrow will overwrite its tail
            # — de-index it (both tiers) now rather than trusting the
            # commit-time verify alone
            idx = length // self.block_size
            if idx < len(table):
                blk = table[idx]
                if self._ref.get(blk, 1) == 1 and blk in self._hash_of:
                    h = self._hash_of.pop(blk)
                    if self._by_hash.get(h) == blk:
                        del self._by_hash[h]
                    self._drop_host(h)
        self._lengths[seq_id] = length
        self._update_gauges()

    def __contains__(self, seq_id):
        return seq_id in self._tables

    def free(self, seq_id, tokens=None):
        """Drop a sequence's references.  With ``tokens`` (its full
        written token list) every full block is indexed into the prefix
        cache FIRST, so a preempted-and-requeued request — or the next
        request sharing the prompt — re-enters through `allocate` with
        its prefix credit intact.  Children release before parents so
        LRU eviction consumes the chain tip first."""
        if seq_id not in self._tables:
            return 0
        if tokens is not None:
            self.commit_prefix(seq_id, tokens)
        blocks = self._tables.pop(seq_id)
        self._lengths.pop(seq_id, None)
        self._cached_len.pop(seq_id, None)
        self._seq_adapter.pop(seq_id, None)
        slot = self._slot_of.pop(seq_id, None)
        if slot is not None:
            # the state is dropped with the slot: a requeued request
            # recomputes it from its first chunk, as it does its blocks
            self._free_slots.append(slot)
        for blk in reversed(blocks):
            self._release(blk)
        for group in self.window_groups:
            group.free(seq_id)
        self._update_gauges()
        return len(blocks)

    def length(self, seq_id):
        return self._lengths[seq_id]

    def sequences(self):
        return list(self._tables)

    @property
    def prefix_hit_rate(self):
        """Fraction of looked-up prompt tokens served from the cache."""
        return self._hit_tokens / max(1, self._lookup_tokens)

    @property
    def host_hit_rate(self):
        """Fraction of looked-up prompt tokens served from the HOST
        tier specifically (promotions; subset of prefix_hit_rate)."""
        return self._host_hit_tokens / max(1, self._lookup_tokens)

    # -- cross-pool transfer (disaggregated prefill -> decode) -----------
    def _no_state_transfer(self):
        if self.state_slots or self.window_groups or self.latent:
            raise NotImplementedError(
                "a sequence with per-request state (recurrent layers, "
                "pooled keys), a windowed group's blocks or latent rows "
                "cannot move between pools yet: the handoff payload "
                "carries the full group's K/V blocks only")

    def export_sequence(self, seq_id):
        """The sequence's paged KV state as a host-side
        :class:`HandoffPayload` — per-layer stacked block data (+ int8
        scale tables) in table order, read with one device gather per
        layer per side through the same DMA accounting as the host
        tier.  The sequence stays allocated; callers typically
        ``free(tokens=...)`` afterwards so the blocks park
        prefix-indexed for the NEXT request sharing the prompt."""
        from .attention import kv_blocks_gather
        from ...core.pipeline import get_window
        self._no_state_transfer()
        table = self._tables[seq_id]
        nbytes = len(table) * self.bytes_per_block
        t0 = time.perf_counter()
        with _dma_span("export", nbytes, blocks=len(table),
                       seq=str(seq_id)):
            k, v, ks, vs = kv_blocks_gather(self, table)
            get_window().admit(k + v, label="kv:dma:export")
            payload = HandoffPayload(
                [np.asarray(x) for x in k],
                [np.asarray(x) for x in v],
                ks and [np.asarray(x) for x in ks],
                vs and [np.asarray(x) for x in vs],
                self.block_size, self._jdtype)
        _observe_dma("export", nbytes, time.perf_counter() - t0)
        return payload

    def import_sequence(self, seq_id, tokens, length, payload,
                        adapter=None):
        """Adopt a sequence prefilled in ANOTHER pool: allocate blocks
        here, device-put every block the local prefix cache doesn't
        already hold from ``payload``, and commit the chain hashes so
        refcounts/COW/sharing behave as if the prefill ran locally.
        All-or-nothing — returns False (nothing mutated) when capacity
        is short; payload geometry must match this pool."""
        if seq_id in self._tables:
            raise KeyError(f"sequence {seq_id!r} already allocated")
        self._no_state_transfer()
        if (int(payload.block_size) != self.block_size
                or payload.kv_dtype != str(self._jdtype)):
            raise ValueError(
                f"payload geometry {payload.kv_dtype}x"
                f"{payload.block_size} does not match pool "
                f"{self._jdtype}x{self.block_size}")
        # Chaos site: fires BEFORE any pool mutation (like alloc_fail),
        # so an injected import failure provably leaks nothing.
        from ...distributed.fault_tolerance.plan import fault_point
        fault_point("serve.import_fail")
        length = int(length)
        # num_tokens = length+1 lifts the leave-one-to-compute cap:
        # nothing is left to compute, the payload carries every byte
        chain = self._walk_chain(tokens, length + 1, adapter=adapter)
        hbm_hits = [ref for _, kind, ref in chain if kind == "hbm"]
        host_slots = [ref for _, kind, ref in chain if kind == "host"]
        need = self.blocks_needed(length) - len(hbm_hits)
        hits_parked = sum(1 for b in hbm_hits if b in self._cached_free)
        if need > len(self._free) + (len(self._cached_free)
                                     - hits_parked):
            return False
        for blk in hbm_hits:
            self._activate(blk)
        self._host_pin.update(host_slots)
        table = []
        try:
            for h, kind, ref in chain:
                if kind == "hbm":
                    table.append(ref)
                else:
                    blk = self._take_block()
                    self._promote(ref, blk, h)
                    self._ref[blk] = 1
                    table.append(blk)
            fresh_start = len(table)
            for _ in range(self.blocks_needed(length) - len(table)):
                blk = self._take_block()
                self._ref[blk] = 1
                table.append(blk)
            if fresh_start < len(table):
                from .attention import kv_blocks_scatter
                from ...core.pipeline import get_window
                src = np.arange(fresh_start, len(table))
                nbytes = len(src) * self.bytes_per_block
                t0 = time.perf_counter()
                with _dma_span("import", nbytes, blocks=len(src),
                               seq=str(seq_id)):
                    puts = kv_blocks_scatter(
                        self, table[fresh_start:],
                        [a[src] for a in payload.k],
                        [a[src] for a in payload.v],
                        payload.k_scales
                        and [a[src] for a in payload.k_scales],
                        payload.v_scales
                        and [a[src] for a in payload.v_scales])
                    get_window().admit(puts, label="kv:dma:import")
                _observe_dma("import", nbytes,
                             time.perf_counter() - t0)
        except BaseException:
            for blk in reversed(table):
                self._release(blk)
            raise
        finally:
            self._host_pin.difference_update(host_slots)
        self._tables[seq_id] = table
        self._lengths[seq_id] = length
        if adapter is not None:
            self._seq_adapter[seq_id] = adapter
        for group in self.window_groups:
            # a windowed group's blocks come as the steps write them
            group.open(seq_id)
        if self.window_groups and tokens is not None:
            self.prefix_bypassed_window += 1
            obs.get_registry().counter(
                "prefix_cache.bypassed_window").inc()
        cached = len(chain) * self.block_size
        self._cached_len[seq_id] = cached
        if self.prefix_cache and tokens is not None:
            self._hit_tokens += cached
            self._host_hit_tokens += len(host_slots) * self.block_size
            self._lookup_tokens += length
            self.commit_prefix(seq_id, tokens)
        obs.instant("serving.kv_import", cat="dma", seq=str(seq_id),
                    blocks=len(table), transferred=len(table) - cached
                    // self.block_size)
        self._update_gauges()
        return True

    # -- device-side driving arrays --------------------------------------
    def slot_mapping(self, seq_id, start, count):
        """Flat pool slots for positions [start, start+count) — the
        scatter targets for newly computed K/V."""
        table = self._tables[seq_id]
        pos = np.arange(int(start), int(start) + int(count))
        blocks = np.asarray(table, np.int32)[pos // self.block_size]
        return (blocks * self.block_size
                + (pos % self.block_size)).astype(np.int32)

    def block_table(self, seq_id, width=None):
        """The sequence's block table padded to ``width`` (default: the
        pool's fixed table_width) with the pad block 0."""
        width = int(width or self.table_width)
        table = self._tables[seq_id]
        if len(table) > width:
            raise ValueError(
                f"sequence {seq_id!r} spans {len(table)} blocks "
                f"> table width {width}")
        out = np.zeros(width, np.int32)
        out[:len(table)] = table
        return out

    # -- gauges ----------------------------------------------------------
    def _update_gauges(self):
        used = self.blocks_in_use
        self.high_water = max(self.high_water, used)
        reg = obs.get_registry()
        reg.gauge("serving.kv_blocks_total").set(self.num_blocks - 1)
        reg.gauge("serving.kv_blocks_in_use").set(used)
        reg.gauge("serving.kv_utilization").set(
            used / max(1, self.num_blocks - 1))
        reg.gauge("serving.kv_blocks_shared").set(self.shared_blocks)
        reg.gauge("serving.prefix_hit_rate").set(self.prefix_hit_rate)
        if self.state_slots:
            reg.gauge("state.slots_live").set(len(self._slot_of))
        if self.host is not None:
            reg.gauge("serving.host_blocks_used").set(
                len(self._host_lru))
            reg.gauge("serving.host_hit_rate").set(self.host_hit_rate)

    def stats(self):
        # MIGRATION: block counts are split by tier — "hbm_blocks" is
        # the device pool ("num_blocks" stays as its alias), the
        # "host_*" family covers the spill ring
        return {
            "num_blocks": self.num_blocks - 1,
            "hbm_blocks": self.num_blocks - 1,
            "block_size": self.block_size,
            "kv_dtype": str(self._jdtype),
            "bytes_per_block": self.bytes_per_block,
            "blocks_in_use": self.blocks_in_use,
            "free_blocks": self.free_blocks,
            "logical_blocks": self.logical_blocks,
            "physical_blocks": self.blocks_in_use,
            "shared_blocks": self.shared_blocks,
            "cached_free_blocks": len(self._cached_free),
            "cow_splits": self.cow_splits,
            "prefix_hit_rate": self.prefix_hit_rate,
            "high_water": self.high_water,
            "pool_bytes": self.pool_bytes,
            "sequences": len(self._tables),
            "host_blocks": self.host.num_slots if self.host else 0,
            "host_blocks_used": len(self._host_lru),
            "host_pool_bytes": self.host.nbytes if self.host else 0,
            "host_spills": self.host_spills,
            "host_promotes": self.host_promotes,
            "host_evictions": self.host_evictions,
            "host_hit_rate": self.host_hit_rate,
            "stale_hash_drops": self.stale_hash_drops,
            "commit_gen": self._commit_gen,
            "state_slots": self.state_slots,
            "state_slots_live": len(self._slot_of),
            "state_pool_bytes": self.state_pool_bytes,
            "compressed_pool_bytes": self.compressed_pool_bytes,
            "prefix_bypassed_recurrent": self.prefix_bypassed,
            "prefix_bypassed_window": self.prefix_bypassed_window,
            "full_pool_bytes": self.full_pool_bytes,
            "window_groups": [g.stats() for g in self.window_groups],
            "window_pool_bytes": sum(g.pool_bytes
                                     for g in self.window_groups),
            "window_high_water": sum(g.high_water
                                     for g in self.window_groups),
            "window_blocks_released": sum(g.released
                                          for g in self.window_groups),
        }

    def __repr__(self):
        return (f"PagedKVCache(blocks={self.num_blocks - 1}x"
                f"{self.block_size}, layers={self.num_layers}, "
                f"in_use={self.blocks_in_use}, "
                f"shared={self.shared_blocks}, "
                f"high_water={self.high_water})")
