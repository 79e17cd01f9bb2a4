"""Paged KV pool + block allocator (PagedAttention substrate, paper §3.4).

Device layout:
  k_pool, v_pool: (L, num_blocks, block_size, KVH, Dh)
  (MLA archs store the latent as KVH=1, Dh = r + rope_dim)

Block 0 is a reserved scratch block (inactive decode slots write there), so
allocatable ids are 1..num_blocks-1. The allocator hands out lowest-index
blocks first so that shrinking can usually drop a free tail; ``resize`` grows
by concatenation (ids stable) and shrinks only when the tail is free — the
engine defers shrink otherwise, matching the "release when pressure subsides"
semantics rather than forcibly compacting live sequences.

SSM archs use :class:`StatePool` (per-slot recurrent state) — the paper's KV
elasticity adapted to attention-free models (DESIGN.md §4).
"""
from __future__ import annotations

import functools
import heapq
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import kv_codec


def kv_block_bytes(cfg: ModelConfig, block_size: int,
                   dtype_bytes: int = 2) -> int:
    """Device bytes of ONE block across all layers (k+v)."""
    L = cfg.n_layers
    if cfg.family == "ssm":
        return 0
    if cfg.mla is not None:
        width = cfg.mla.kv_lora_rank + cfg.mla.qk_rope_head_dim
        return L * block_size * width * dtype_bytes          # latent only
    kvh, dh = cfg.n_kv_heads, cfg.resolved_head_dim
    return L * block_size * 2 * kvh * dh * dtype_bytes


class BlockAllocator:
    """Lowest-id-first allocator over a heapq free list.

    O(log n) alloc/release (was: full re-sort on every release), so the host
    scheduler stays linear in blocks touched per step."""

    def __init__(self, num_blocks: int):
        # block 0 reserved as scratch; ascending list is already a valid heap
        self.num_blocks = num_blocks
        self.free: List[int] = list(range(1, num_blocks))

    @property
    def n_free(self) -> int:
        return len(self.free)

    @property
    def n_used(self) -> int:
        return self.num_blocks - 1 - len(self.free)

    def alloc(self, n: int) -> Optional[List[int]]:
        if n > len(self.free):
            return None
        return [heapq.heappop(self.free) for _ in range(n)]

    def release(self, ids: List[int]) -> None:
        for b in ids:
            assert 0 < b < self.num_blocks
            heapq.heappush(self.free, b)

    def grow(self, new_num_blocks: int) -> None:
        assert new_num_blocks >= self.num_blocks
        # fresh ids exceed every id already in the heap, so appending them
        # preserves the heap invariant (parents are all smaller).
        self.free.extend(range(self.num_blocks, new_num_blocks))
        self.num_blocks = new_num_blocks

    def shrinkable_to(self) -> int:
        """Smallest pool size droppable right now (free tail only).

        Builds a set of the free list (O(len(free))) and walks down from the
        top id while it is free — computed from the free structure alone
        (no set(range(num_blocks)) materialization as before)."""
        if self.n_used == 0:
            return 1
        free_set = set(self.free)
        b = self.num_blocks - 1
        while b in free_set:
            b -= 1
        return b + 1

    def shrink(self, new_num_blocks: int) -> bool:
        if new_num_blocks < self.shrinkable_to():
            return False
        self.free = [b for b in self.free if b < new_num_blocks]
        heapq.heapify(self.free)
        self.num_blocks = new_num_blocks
        return True


@functools.lru_cache(maxsize=None)
def _zeros_on(device):
    """``zeros(shape, dtype)`` computed on ``device`` and committed there.
    ``jnp.zeros(..., device=d)`` fills the array on the default device and
    then copies it over: for a replica on another chip that is, for a
    moment, a second pool on the first replica's chip."""
    return jax.jit(jnp.zeros, static_argnums=(0, 1),
                   out_shardings=jax.sharding.SingleDeviceSharding(device))


def write_blocks(pool, ids, blocks, layer=None):
    """``pool[:, ids[j]] = blocks[:, j]`` — or, with ``layer``,
    ``pool[layer, ids[j]] = blocks[j]`` — one block at a time. On the TPU
    a scatter or gather of whole ``(bs, KVH, Dh)`` blocks wants the pool in
    another tiled layout and copies all of it there first (AOT compile for
    a v5e); a loop of slice updates leaves a donated pool in place."""
    blocks = blocks.astype(pool.dtype)
    if layer is not None:
        blocks = blocks[None]
    lead = 0 if layer is None else layer

    def put(j, pool):
        blk = jax.lax.dynamic_slice_in_dim(blocks, j, 1, axis=1)
        return jax.lax.dynamic_update_slice(
            pool, blk, (lead, ids[j]) + (0,) * (pool.ndim - 2))
    return jax.lax.fori_loop(0, ids.shape[0], put, pool)


def read_blocks(pool, ids):
    """``pool[:, ids]`` for a pool ``(L, cap, ...)``, one block at a time
    (see :func:`write_blocks`): no copy of the pool."""
    out = jnp.zeros((pool.shape[0], ids.shape[0]) + pool.shape[2:],
                    pool.dtype)

    def get(j, out):
        blk = jax.lax.dynamic_slice_in_dim(pool, ids[j], 1, axis=1)
        return jax.lax.dynamic_update_slice_in_dim(out, blk, j, axis=1)
    return jax.lax.fori_loop(0, ids.shape[0], get, out)


def _read(a, idx):
    """``a[:, idx]`` for a pool (slice reads) or a sidecar row."""
    return a[:, idx] if a.ndim == 2 else read_blocks(a, idx)


@jax.jit
def _get_blocks(arrays, idx):
    """``a[:, idx]`` for every array: the export half of a migration."""
    return tuple(_read(a, idx) for a in arrays)


def _write(a, idx, v):
    """``a[:, idx] = v`` for a pool ``(L, cap, ...)`` (slice updates) or a
    sidecar row ``(L, cap)`` (a scatter: a few MB at most)."""
    v = jnp.broadcast_to(jnp.asarray(v, a.dtype),
                         (a.shape[0], idx.shape[0]) + a.shape[2:])
    return a.at[:, idx].set(v) if a.ndim == 2 else write_blocks(a, idx, v)


# Device-side pool updates are jitted programs that donate the arrays they
# rewrite, so XLA updates them in place. An eager ``a.at[idx].set(x)`` on an
# array the pool still references builds a second array of the full pool's
# size, and a pool sized to most of HBM has no room for two of itself.
@functools.partial(jax.jit, donate_argnums=0)
def _set_blocks(arrays, idx, values):
    """``arrays[i][:, idx] = values[i]`` for every i, in place."""
    return tuple(_write(a, idx, v) for a, v in zip(arrays, values))


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _move_blocks(payload, params, src, dst):
    """Copy blocks ``src`` onto ``dst`` in every array, in place; the
    ``params`` (sidecar scale/zero) rows of ``src`` are zeroed after."""
    payload = tuple(write_blocks(a, dst, read_blocks(a, src))
                    for a in payload)
    params = tuple(a.at[:, dst].set(a[:, src]).at[:, src].set(0)
                   for a in params)
    return payload, params


@functools.partial(jax.jit, static_argnames="bits", donate_argnums=(0, 1, 2, 3))
def _quantize_into(fp, q, scale, zero, idx, *, bits):
    """Compress blocks ``idx`` of one pool into its sidecar, in place: the
    int8 payload and per-(layer, block) scale/zero are written, the fp slot
    zeroed."""
    qv, s, z = kv_codec.affine_quantize_jnp(read_blocks(fp, idx), bits)
    return (_write(fp, idx, 0), _write(q, idx, qv), _write(scale, idx, s),
            _write(zero, idx, z))


@functools.partial(jax.jit, donate_argnums=(0, 2, 3))
def _dequantize_into(fp, q, scale, zero, idx):
    """Inverse of :func:`_quantize_into`, in place: ``q*scale+zero`` back
    to the fp slot, scale/zero rows zeroed (the stale payload is inert)."""
    deq = kv_codec.affine_dequantize_jnp(read_blocks(q, idx), scale[:, idx],
                                         zero[:, idx], fp.dtype)
    return (_write(fp, idx, deq), _write(scale, idx, 0),
            _write(zero, idx, 0))


class PagedKVPool:
    """Owns the device pool arrays + allocator.

    **Capacity bucketing** (default on): the device arrays are preallocated
    to the power-of-two bucket of the logical block count, and the allocator
    tracks ``num_blocks`` separately. A morph-tick grow/shrink that stays
    within the current bucket is an O(1) host-side metadata update — no
    device pool copy, and (since jitted callables key on the *array* shape)
    no new decode executable. Cross-bucket resizes copy exactly once per
    bucket transition, so the pool contributes at most
    ``log2(max_blocks)`` shapes to the jit cache. ``copies`` counts device
    pool copies for the benchmarks/tests. Disable with
    ``bucket_capacity=False`` to recover the seed's copy-per-resize
    behaviour (capacity == num_blocks at all times). ``max_capacity`` caps
    the bucket (never below the logical count): the owner passes the most
    blocks its budget can ever hold, so rounding up to a power of two never
    allocates device memory the budget does not have.
    """

    def __init__(self, cfg: ModelConfig, num_blocks: int, block_size: int,
                 dtype=jnp.float32, *, bucket_capacity: bool = True,
                 max_capacity: Optional[int] = None,
                 device=None, materialize: bool = True):
        self.cfg = cfg
        self.block_size = block_size
        self.dtype = dtype
        self.bucket_capacity = bucket_capacity
        self.max_capacity = max_capacity
        # arrays are made on and committed to ``device`` (None: JAX's
        # default device, uncommitted)
        self._zeros = jnp.zeros if device is None else _zeros_on(device)
        L = cfg.n_layers
        if cfg.mla is not None:
            width = cfg.mla.kv_lora_rank + cfg.mla.qk_rope_head_dim
            self.kvh, self.dh = 1, width
        else:
            self.kvh, self.dh = cfg.n_kv_heads, cfg.resolved_head_dim
        self.capacity = self._cap_bucket(num_blocks)
        self.copies = 0
        shape = (L, self.capacity, block_size, self.kvh, self.dh)
        if materialize:
            self.k = self._zeros(shape, dtype)
            self.v = (self._zeros(shape, dtype) if cfg.mla is None
                      else self._zeros((1,), dtype))   # MLA: latent only
        else:
            # simulated compute keeps the block accounting only: a
            # paper-scale pool is gigabytes no sim path ever reads
            self.k = self.v = None
        self.alloc = BlockAllocator(num_blocks)
        # quantized-KV sidecar (quantize-cold relief tier), allocated lazily
        # on the first real quantize so quant-off engines pay nothing:
        # int8 mirrors of the pools + one (scale, zero) pair per
        # (layer, block). A quantized block's fp slot is zeroed and its
        # sidecar filled; an fp block's sidecar rows are zero — the
        # kernels' branch-free FMA (fp + q*scale + zero) is exact for both.
        self.qk = self.qv = None
        self.k_scale = self.v_scale = None
        self.k_zero = self.v_zero = None
        self.qbits: Dict[int, int] = {}   # block id -> quant width (host map)

    def _cap_bucket(self, n: int) -> int:
        """Physical capacity for ``n`` logical blocks."""
        if not self.bucket_capacity:
            return n
        b = 1
        while b < n:
            b *= 2
        if self.max_capacity is not None:
            b = min(b, max(n, self.max_capacity))
        return b

    @property
    def num_blocks(self) -> int:
        return self.alloc.num_blocks

    def usage(self) -> float:
        cap = self.num_blocks - 1
        return self.alloc.n_used / cap if cap else 0.0

    def blocks_for(self, n_tokens: int) -> int:
        if self.cfg.family == "ssm":
            return 0                      # attention-free: state slots only
        return -(-n_tokens // self.block_size)

    # ------------------------------------------------------------------
    def resize(self, new_num_blocks: int) -> bool:
        """O(delta) elastic resize. Returns success.

        Within the current capacity bucket this is metadata-only (allocator
        grow / free-tail shrink). Crossing a bucket boundary grows by
        concatenation / slices the tail — one device copy per transition.
        """
        old = self.num_blocks
        if new_num_blocks == old:
            return True
        if new_num_blocks > old:
            new_cap = self._cap_bucket(new_num_blocks)
            if new_cap > self.capacity:
                if self.k is not None:
                    pad = [(0, 0)] * self.k.ndim
                    pad[1] = (0, new_cap - self.capacity)
                    self.k = jnp.pad(self.k, pad)
                    if self.cfg.mla is None:
                        self.v = jnp.pad(self.v, pad)
                self._resize_sidecar(new_cap)
                self.capacity = new_cap
                self.copies += 1
            self.alloc.grow(new_num_blocks)
            return True
        if not self.alloc.shrink(new_num_blocks):
            return False
        new_cap = self._cap_bucket(new_num_blocks)
        if new_cap < self.capacity:
            if self.k is not None:
                self.k = self.k[:, :new_cap]
                if self.cfg.mla is None:
                    self.v = self.v[:, :new_cap]
            self._resize_sidecar(new_cap)
            self.capacity = new_cap
            self.copies += 1
        return True

    def _resize_sidecar(self, new_cap: int) -> None:
        """Pad / slice the quant sidecar with the pools on bucket crossings
        (fresh rows are zero == fp semantics)."""
        if self.qk is None:
            return
        if new_cap > self.capacity:
            d = new_cap - self.capacity
            pad4 = [(0, 0)] * self.qk.ndim
            pad4[1] = (0, d)
            self.qk = jnp.pad(self.qk, pad4)
            if self.qv.ndim > 1:
                self.qv = jnp.pad(self.qv, pad4)
            pad2 = [(0, 0), (0, d)]
            self.k_scale = jnp.pad(self.k_scale, pad2)
            self.v_scale = jnp.pad(self.v_scale, pad2)
            self.k_zero = jnp.pad(self.k_zero, pad2)
            self.v_zero = jnp.pad(self.v_zero, pad2)
        else:
            self.qk = self.qk[:, :new_cap]
            if self.qv.ndim > 1:
                self.qv = self.qv[:, :new_cap]
            self.k_scale = self.k_scale[:, :new_cap]
            self.v_scale = self.v_scale[:, :new_cap]
            self.k_zero = self.k_zero[:, :new_cap]
            self.v_zero = self.v_zero[:, :new_cap]

    # ------------------------------------------------------------------
    # quantize-cold tier: in-place per-block compression + sidecar params
    # ------------------------------------------------------------------
    def _ensure_sidecar(self) -> None:
        if self.qk is not None:
            return
        L = self.k.shape[0]
        self.qk = self._zeros(self.k.shape, jnp.int8)
        self.qv = (self._zeros(self.v.shape, jnp.int8) if self.v.ndim > 1
                   else self._zeros((1,), jnp.int8))
        # four distinct arrays: the in-place updates donate them one by one
        self.k_scale, self.v_scale, self.k_zero, self.v_zero = (
            self._zeros((L, self.capacity), jnp.float32) for _ in range(4))

    def mark_quantized(self, ids: Sequence[int], bits: int = 8) -> List[int]:
        """Accounting-only quantize (sim compute): tracks which blocks are
        held quantized without touching device arrays. Returns the ids
        actually transitioned (already-quantized ids are skipped)."""
        kv_codec.qmax_for_bits(bits)
        fresh = [b for b in ids if b not in self.qbits]
        for b in fresh:
            self.qbits[b] = bits
        return fresh

    def mark_dequantized(self, ids: Sequence[int]) -> List[int]:
        done = [b for b in ids if b in self.qbits]
        for b in done:
            del self.qbits[b]
        return done

    def quantize_blocks(self, ids: Sequence[int], bits: int = 8) -> List[int]:
        """Compress the listed blocks in place: fill the int8 mirror and
        per-(layer, block) scale/zero, zero the fp slot. Already-quantized
        ids are skipped; returns the ids actually compressed."""
        fresh = [b for b in ids if b not in self.qbits]
        if not fresh:
            return []
        self._ensure_sidecar()
        idx = jnp.asarray(fresh, jnp.int32)
        self.k, self.qk, self.k_scale, self.k_zero = _quantize_into(
            self.k, self.qk, self.k_scale, self.k_zero, idx, bits=bits)
        if self.v.ndim > 1:
            self.v, self.qv, self.v_scale, self.v_zero = _quantize_into(
                self.v, self.qv, self.v_scale, self.v_zero, idx, bits=bits)
        return self.mark_quantized(fresh, bits)

    def dequantize_blocks(self, ids: Sequence[int]) -> List[int]:
        """Restore the listed blocks to fp in place (quantize inverse):
        write `q*scale+zero` back to the fp slot, zero the sidecar rows."""
        held = [b for b in ids if b in self.qbits]
        if not held or self.qk is None:
            return self.mark_dequantized(held)
        idx = jnp.asarray(held, jnp.int32)
        self.k, self.k_scale, self.k_zero = _dequantize_into(
            self.k, self.qk, self.k_scale, self.k_zero, idx)
        if self.v.ndim > 1:
            self.v, self.v_scale, self.v_zero = _dequantize_into(
                self.v, self.qv, self.v_scale, self.v_zero, idx)
        return self.mark_dequantized(held)

    def clear_quant(self, ids: Sequence[int]) -> List[int]:
        """Forget quant state of *freed* blocks. Only the scale/zero rows
        need zeroing — with scale=0/zero=0 the stale int8 payload
        contributes nothing to the kernels' dequant FMA, and the fp slot
        was already zeroed at quantize time (fresh appends overwrite it)."""
        held = [b for b in ids if b in self.qbits]
        if not held:
            return []
        if self.qk is not None:
            (self.k_scale, self.k_zero, self.v_scale,
             self.v_zero) = _set_blocks(
                (self.k_scale, self.k_zero, self.v_scale, self.v_zero),
                jnp.asarray(held, jnp.int32), (0.0,) * 4)
        return self.mark_dequantized(held)

    def kv_quant_bundle(self):
        """The kernels' (qk, qv, k_scale, v_scale, k_zero, v_zero) operand
        bundle (L-leading arrays, sliced per layer by the caller), or None
        while no block is quantized — so quant-idle steps trace and run the
        plain kernels."""
        if self.qk is None or not self.qbits:
            return None
        qv = self.qv if self.qv.ndim > 1 else self.qk
        vs = self.v_scale
        vz = self.v_zero
        return (self.qk, qv, self.k_scale, vs, self.k_zero, vz)

    def move_blocks(self, src: Sequence[int], dst: Sequence[int]) -> None:
        """Copy each block ``src[i]``'s full state (fp slot + quant sidecar
        + quant map) onto ``dst[i]``, in place — tail-compaction support.
        The vacated ids' scale/zero rows are zeroed, so they read as pure
        fp from the kernels' dequant FMA once the caller frees and
        reallocates them. Simulated pools move the quant map only."""
        if self.k is not None and src:
            si = jnp.asarray(list(src), jnp.int32)
            di = jnp.asarray(list(dst), jnp.int32)
            payload = [self.k] + ([self.v] if self.v.ndim > 1 else [])
            params = []
            if self.qk is not None:
                payload += [self.qk] + ([self.qv] if self.qv.ndim > 1 else [])
                params = [self.k_scale, self.v_scale, self.k_zero,
                          self.v_zero]
            payload, params = _move_blocks(tuple(payload), tuple(params),
                                           si, di)
            payload = list(payload)
            self.k = payload.pop(0)
            if self.v.ndim > 1:
                self.v = payload.pop(0)
            if self.qk is not None:
                self.qk = payload.pop(0)
                if self.qv.ndim > 1:
                    self.qv = payload.pop(0)
                (self.k_scale, self.v_scale, self.k_zero,
                 self.v_zero) = params
        for s, d in zip(src, dst):
            if s in self.qbits:
                self.qbits[d] = self.qbits.pop(s)

    # ------------------------------------------------------------------
    # cross-replica state transfer (request migration / prefix migration)
    # ------------------------------------------------------------------
    def gather_blocks(self, ids: Sequence[int]):
        """Copy the listed blocks to host memory: ``(k, v)`` numpy arrays of
        shape ``(L, len(ids), block_size, KVH, Dh)`` (``v`` is None for MLA
        latent pools). This is the export half of paged-KV migration — the
        contents travel, the ids do not (the importer allocates its own)."""
        idx = jnp.asarray(list(ids), jnp.int32)
        if self.cfg.mla is None and self.v.ndim > 1:
            k, v = _get_blocks((self.k, self.v), idx)
            return np.asarray(k), np.asarray(v)
        (k,) = _get_blocks((self.k,), idx)
        return np.asarray(k), None

    def scatter_blocks(self, ids: Sequence[int], k, v=None) -> None:
        """Write migrated block contents into freshly-allocated local ids
        (the import half of paged-KV migration)."""
        idx = jnp.asarray(list(ids), jnp.int32)
        if v is not None and self.cfg.mla is None and self.v.ndim > 1:
            self.k, self.v = _set_blocks((self.k, self.v), idx, (k, v))
        else:
            (self.k,) = _set_blocks((self.k,), idx, (k,))

    def gather_quant_blocks(self, ids: Sequence[int]):
        """Host copies of the listed *quantized* blocks' compressed state:
        ``(qk, qv, k_scale, v_scale, k_zero, v_zero)`` with block axis
        ``len(ids)``. The export half of round-trip-free migration — the
        int8 payload and sidecar params travel as-is, never dequantized."""
        assert self.qk is not None and all(b in self.qbits for b in ids), \
            "gather_quant_blocks on non-quantized blocks"
        idx = jnp.asarray(list(ids), jnp.int32)
        has_v = self.qv.ndim > 1
        out = [np.asarray(a) for a in _get_blocks(
            (self.qk, self.k_scale, self.v_scale, self.k_zero, self.v_zero)
            + ((self.qv,) if has_v else ()), idx)]
        qk, ks, vs, kz, vz = out[:5]
        return (qk, out[5] if has_v else None, ks, vs, kz, vz)

    def scatter_quant_blocks(self, ids: Sequence[int], payload,
                             bits: int = 8) -> None:
        """Import half of round-trip-free migration: write a
        ``gather_quant_blocks`` payload into freshly-allocated local ids —
        the blocks arrive (and stay) quantized, fp slots zeroed."""
        self._ensure_sidecar()
        qk, qv, ks, vs, kz, vz = payload
        idx = jnp.asarray(list(ids), jnp.int32)
        self.qk, self.k_scale, self.k_zero, self.k = _set_blocks(
            (self.qk, self.k_scale, self.k_zero, self.k), idx,
            (qk, ks, kz, 0))
        if qv is not None and self.qv.ndim > 1:
            self.qv, self.v_scale, self.v_zero, self.v = _set_blocks(
                (self.qv, self.v_scale, self.v_zero, self.v), idx,
                (qv, vs, vz, 0))
        self.mark_quantized(list(ids), bits)


class PrefixCacheEntry:
    """One cached full KV block of a prompt prefix (radix-chain node)."""

    __slots__ = ("key", "parent_key", "block_id", "ref", "children",
                 "last_used", "level", "tol")

    def __init__(self, key: int, parent_key: Optional[int], block_id: int,
                 level: int, now: float, tol: Optional[str] = None):
        self.key = key
        self.parent_key = parent_key
        self.block_id = block_id
        self.level = level            # swap level the KV was computed under
        self.tol = tol                # tolerance tag: None = exact fp at
        #                               `level`; "int8"/"int4" = requantized
        #                               through the KV codec on a hit
        self.ref = 0                  # live requests holding this block
        self.children = 0             # cached entries chained off this one
        self.last_used = now


class PrefixCache:
    """Refcounted shared-prefix KV block cache (radix-style chained hashes).

    Full, block-aligned prompt prefixes are published here on request finish
    instead of being freed: each block is keyed by the chained hash of
    ``(parent_key, block_tokens)``, so a lookup walks the chain from block 0
    and stops at the first miss — longest-prefix match.

    Keys are **level-independent**: the writer's swap level is recorded on
    the entry (``level``), not folded into the hash, so one cached copy per
    prefix serves every level and the cache survives morph transitions —
    exactly when a burst-driven level change would previously have gone
    100% cold. A hit at the writer's level is bit-exact; a *cross-level*
    hit is resolved by the engine's configured policy: adopt the blocks
    as-is (tolerance-tagged on the request — numerically the KV of a
    different precision stack) or requantize them through the shared KV
    codec (entry tagged ``tol="int8"/"int4"``, served via in-kernel
    dequant thereafter).

    Blocks with ``ref == 0`` stay resident but are the cheapest relief tier
    in the engine: they are reclaimed LRU (leaf-first, so chains never dangle
    unreachable interior nodes) before quantize-cold, live-KV shrink,
    preemption, or a quantized layer swap. ``ref > 0`` blocks are pinned —
    copy-on-write is structural: only *full* prefix blocks are ever shared,
    so a holder's writes (later prompt chunks, decode appends) always land
    in its own private blocks past the shared boundary.
    """

    _SEED = 0x9E3779B97F4A7C15          # chain seed (any fixed odd constant)

    def __init__(self, block_size: int):
        self.block_size = block_size
        self.entries: Dict[int, PrefixCacheEntry] = {}
        self.by_block: Dict[int, PrefixCacheEntry] = {}
        # counters (engine/bench observability)
        self.lookups = 0
        self.hits = 0
        self.tokens_reused = 0
        self.inserted_blocks = 0
        self.evicted_blocks = 0
        self.cross_level_hits = 0     # blocks served across a level change
        self.requant_hits = 0         # blocks requantized on a cross hit

    # -- chain keys ------------------------------------------------------
    @classmethod
    def chain_key(cls, prev_key: Optional[int],
                  block_tokens: Sequence[int]) -> int:
        """One chain link: the single place the key formula lives (lookup
        and publish must agree bit-for-bit). Level-independent by design —
        the writer's level lives on the entry, not in the key."""
        return hash((cls._SEED if prev_key is None else prev_key,
                     tuple(block_tokens)))

    def chain_keys(self, tokens: Sequence[int], n_blocks: int) -> List[int]:
        """Chained hashes of the first ``n_blocks`` full blocks."""
        bs = self.block_size
        keys: List[int] = []
        h: Optional[int] = None
        for b in range(n_blocks):
            h = self.chain_key(h, tokens[b * bs:(b + 1) * bs])
            keys.append(h)
        return keys

    # -- stats -----------------------------------------------------------
    @property
    def resident_blocks(self) -> int:
        return len(self.entries)

    @property
    def evictable_blocks(self) -> int:
        return sum(1 for e in self.entries.values() if e.ref == 0)

    # -- lookup / pinning ------------------------------------------------
    def match(self, tokens: Sequence[int], level: int, max_blocks: int,
              now: float) -> List[PrefixCacheEntry]:
        """Longest cached block-aligned prefix of ``tokens``.

        ``level`` is the *reader's* current swap level — it no longer
        restricts the match (keys are level-independent); it classifies
        hit blocks for the counters: an entry written at another level
        counts toward ``cross_level_hits`` and the engine applies its
        adopt / requantize policy to it.

        Matched entries are pinned (ref++) and LRU-touched; the caller owns
        the references and must hand every block back through ``release``.
        """
        self.lookups += 1
        matched: List[PrefixCacheEntry] = []
        for key in self.chain_keys(tokens, max_blocks):
            e = self.entries.get(key)
            if e is None:
                break
            matched.append(e)
        for e in matched:
            e.ref += 1
            e.last_used = now
            if e.level != level:
                self.cross_level_hits += 1
        if matched:
            self.hits += 1
            self.tokens_reused += len(matched) * self.block_size
        return matched

    def peek(self, tokens: Sequence[int],
             max_blocks: int) -> List[PrefixCacheEntry]:
        """Longest cached block-aligned prefix *without* pinning, touching
        LRU stamps, or counting a lookup — the read-only probe the cluster
        uses to decide whether a peer replica's cache is worth migrating."""
        matched: List[PrefixCacheEntry] = []
        for key in self.chain_keys(tokens, max_blocks):
            e = self.entries.get(key)
            if e is None:
                break
            matched.append(e)
        return matched

    def release(self, block_id: int, now: float) -> bool:
        """Drop one reference to a cached block. Returns True when the block
        belongs to the cache (the caller must NOT free it to the allocator);
        False means the block is not cached and stays caller-owned."""
        e = self.by_block.get(block_id)
        if e is None:
            return False
        assert e.ref > 0, f"release of unpinned cached block {block_id}"
        e.ref -= 1
        e.last_used = now
        return True

    # -- publish ---------------------------------------------------------
    def insert(self, key: int, parent_key: Optional[int], block_id: int,
               level: int, now: float, tol: Optional[str] = None) -> bool:
        """Publish a finished request's private full block (``level`` =
        the swap level its KV was computed under; ``tol`` carries a
        tolerance tag for non-exact content). Returns True when the cache
        took ownership (resident at ref 0); False when the key or block is
        already cached — first writer wins (one copy per prefix now that
        keys are level-independent) and the caller keeps/frees the block."""
        if key in self.entries or block_id in self.by_block:
            return False
        if parent_key is not None and parent_key not in self.entries:
            return False                      # chain broken: parent evicted
        e = PrefixCacheEntry(key, parent_key, block_id, level, now, tol)
        self.entries[key] = e
        self.by_block[block_id] = e
        if parent_key is not None:
            self.entries[parent_key].children += 1
        self.inserted_blocks += 1
        return True

    def requantize_entry(self, e: PrefixCacheEntry, level: int,
                         bits: int) -> None:
        """Record a requantize-on-hit: the entry's content went through the
        shared KV codec (the caller performed the pool-side quantize), so
        it now serves every level via in-kernel dequant — tagged, and
        re-homed at the requesting level."""
        e.level = level
        e.tol = f"int{bits}"
        self.requant_hits += 1

    # -- eviction (tier-1 relief) ----------------------------------------
    def _drop(self, e: PrefixCacheEntry) -> int:
        del self.entries[e.key]
        del self.by_block[e.block_id]
        if e.parent_key is not None:
            parent = self.entries.get(e.parent_key)
            if parent is not None:
                parent.children -= 1
        self.evicted_blocks += 1
        return e.block_id

    def evict_lru(self, n: int) -> List[int]:
        """Reclaim up to ``n`` idle cached blocks, least-recently-used leaves
        first. Returns the freed block ids (caller releases to allocator)."""
        freed: List[int] = []
        heap = [(e.last_used, e.key) for e in self.entries.values()
                if e.ref == 0 and e.children == 0]
        heapq.heapify(heap)
        while heap and len(freed) < n:
            _, key = heapq.heappop(heap)
            e = self.entries.get(key)
            if e is None or e.ref or e.children:
                continue
            parent = (self.entries.get(e.parent_key)
                      if e.parent_key is not None else None)
            freed.append(self._drop(e))
            # an interior node becomes evictable once its last child goes
            if parent is not None and parent.ref == 0 \
                    and parent.children == 0:
                heapq.heappush(heap, (parent.last_used, parent.key))
        return freed

    def evict_block_ids_at_or_above(self, limit: int) -> List[int]:
        """Reclaim idle cached blocks with id >= ``limit`` (pool-shrink
        support: the free tail must really be free). Pinned blocks up there
        block the shrink — the engine defers, as for any live block."""
        freed: List[int] = []
        while True:
            doomed = [e for e in self.entries.values()
                      if e.ref == 0 and e.children == 0
                      and e.block_id >= limit]
            if not doomed:
                return freed
            for e in doomed:
                freed.append(self._drop(e))

    # -- invariants (tests) ----------------------------------------------
    def check(self, alloc: "BlockAllocator") -> None:
        free = set(alloc.free)
        child_counts: Dict[int, int] = {}
        for e in self.entries.values():
            assert self.by_block[e.block_id] is e
            assert e.block_id not in free, \
                f"cached block {e.block_id} is also on the free list"
            assert e.ref >= 0
            if e.parent_key is not None:
                assert e.parent_key in self.entries, \
                    f"entry {e.key} dangles off evicted parent"
                child_counts[e.parent_key] = \
                    child_counts.get(e.parent_key, 0) + 1
        for key, e in self.entries.items():
            assert e.children == child_counts.get(key, 0)
        assert len(self.by_block) == len(self.entries)


class StatePool:
    """Per-slot recurrent state pool for SSM/hybrid layers."""

    def __init__(self, cfg: ModelConfig, slots: int):
        from repro.models.mamba import mamba_init_state
        self.cfg = cfg
        self.slots = slots
        kinds = [k for k in _ssm_layer_indices(cfg)]
        self.layers = kinds
        st = mamba_init_state(cfg, slots)
        self.conv = jnp.stack([st["conv"]] * len(kinds)) if kinds else None
        self.ssm = jnp.stack([st["ssm"]] * len(kinds)) if kinds else None

    def state_bytes_per_slot(self) -> int:
        if self.conv is None:
            return 0
        per = (self.conv[0, 0].size * self.conv.dtype.itemsize
               + self.ssm[0, 0].size * self.ssm.dtype.itemsize)
        return per * len(self.layers)


def _ssm_layer_indices(cfg: ModelConfig) -> List[int]:
    from repro.models.lm import layer_kinds
    return [i for i, k in enumerate(layer_kinds(cfg))
            if k in ("mamba", "hybrid")]
