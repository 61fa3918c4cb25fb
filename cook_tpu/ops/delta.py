"""Device-resident incremental cycle state: the delta scatter-apply
kernel and the device base mirror (ISSUE 7; ROADMAP item 2).

The production fused cycle used to rebuild its stacked [P, T] wire
arrays on the host and re-upload them every cycle — the "host staging
wall" that dominated step_cycle once the kernels were fast.  This module
is the mechanism that replaces the rebuild with incremental view
maintenance (Omega's shared-state insight one level down; McSherry-style
deltas):

* the pack's per-cycle wire arrays (``rows`` row permutation + ``flags``
  admission bits, CompactPoolCycleInputs) live in DEVICE-RESIDENT
  buffers across cycles;
* each cycle the driver diffs the freshly staged host arrays against its
  host shadow (delta extraction — native/pack.cpp when built) and
  dispatches :func:`apply_pack_delta`, a jitted scatter of just the
  changed positions, instead of uploading the world;
* a full repack happens only on an index compaction fence, a bucket
  regrow / group reshape, a kernel-dispatch fault (degrading like every
  other kernel, ``cook_kernel_fallback_total``), or when the delta is so
  large the full upload is cheaper.

Flag-bit constants live here (not in parallel/sharded.py) so the state
and sched layers can reason about wire flags without importing the mesh
layer; parallel/sharded re-exports them under the same names.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np

from ..utils.metrics import registry
from . import telemetry
from .padding import bucket

F32 = np.float32

# flag bits of CompactPoolCycleInputs.flags (one wire byte per task)
FLAG_PENDING = 1
FLAG_VALID = 2
FLAG_ENQUEUE_OK = 4
FLAG_LAUNCH_OK = 8
FLAG_USER_FIRST = 16   # first row of a user segment

# delta batches are padded to power-of-two buckets so the scatter
# executable is reused across cycles (min floor keeps tiny deltas from
# compiling log2(min) variants)
_DELTA_MIN_BUCKET = 256

# value codec tags of the delta scatter (static in the executable's key)
ROWS_WIDE = 0    # i32 absolute rows, no transform
ROWS_I16 = 1     # int16 delta vs position
ROWS_I8 = 2      # int8 delta vs position


def note_wide(field: str) -> None:
    """Count one lossless-narrow negotiation that fell back to the wide
    form (the contract: quantization is lossless-or-wide, and wide is
    always COUNTED so an operator can see it never engaging)."""
    registry.counter_inc("cook_quant_wide_fallback", labels={"field": field})


def pack_flags(pending: np.ndarray, valid: np.ndarray,
               is_first: np.ndarray, enqueue_ok=None,
               launch_ok=None) -> np.ndarray:
    """The wire flags byte, packed ONE way for every producer (the fused
    pack and the compact rank path must never drift on bit layout).
    ``enqueue_ok``/``launch_ok`` default to all-accept when omitted —
    note the rank kernel simply ignores those bits."""
    flags = (pending.astype(np.uint8) * FLAG_PENDING
             + valid.astype(np.uint8) * FLAG_VALID
             + is_first.astype(np.uint8) * FLAG_USER_FIRST)
    if enqueue_ok is not None:
        flags += enqueue_ok.astype(np.uint8) * FLAG_ENQUEUE_OK
    if launch_ok is not None:
        flags += launch_ok.astype(np.uint8) * FLAG_LAUNCH_OK
    return flags


def _donate_default() -> bool:
    """Donate the resident buffers into the scatter only where XLA
    honors input-output aliasing (TPU/GPU).  On CPU donation is ignored
    with a warning per call — the copy is cheap there anyway."""
    import jax
    return jax.default_backend() not in ("cpu",)


class _Replicated:
    """Where the delta kernels' host inputs go: onto every device of the
    pool mesh (``NamedSharding(mesh, P())``), so a scatter into the
    pool-sharded resident buffers and an append to the replicated base
    mirror read their operands from the device that runs them and
    nothing crosses between devices at dispatch.  ``mesh_fn`` gives the
    mesh when asked (the fused driver builds its mesh lazily); on one
    device, and without one, it is the plain uncommitted
    ``jnp.asarray``."""

    def __init__(self, mesh_fn=None):
        self._mesh_fn = mesh_fn

    @property
    def mesh(self):
        return None if self._mesh_fn is None else self._mesh_fn()

    @property
    def replicas(self) -> int:
        """Copies a put makes: what an upload's bytes are counted by
        (``h2d_bytes`` is the sum over devices)."""
        mesh = self.mesh
        return 1 if mesh is None else int(mesh.size)

    def put(self, a):
        mesh = self.mesh
        if mesh is None or mesh.size == 1:
            import jax.numpy as jnp
            return jnp.asarray(a)
        import jax
        from jax.sharding import NamedSharding, PartitionSpec
        return jax.device_put(a, NamedSharding(mesh, PartitionSpec()))


class _StagedDelta:
    """One staged (h2d-in-flight) scatter batch: the padded device
    arrays whose host->device copies started at :meth:`stage` time.
    Holding it across the current cycle's kernel dispatch is the
    DOUBLE-BUFFERED form (ISSUE 14): the next cycle's delta bytes move
    while the current kernel computes, because every stage allocates
    FRESH host buffers — nothing rewrites memory an in-flight copy still
    reads."""

    __slots__ = ("shape", "kb", "codec", "idx", "vals", "flags", "nbytes")

    def __init__(self, shape, kb, codec, idx, vals, flags, nbytes):
        self.shape = shape
        self.kb = kb
        self.codec = codec
        self.idx = idx
        self.vals = vals
        self.flags = flags
        self.nbytes = nbytes


class PackDeltaApplier:
    """Caches one jitted scatter executable per (buffer shape, delta
    bucket, value codec); donation re-uses the old buffer's device
    memory so the resident pack never doubles its footprint during the
    update.

    With ``quantize=True`` (``Config.quantized_wire``) row values are
    coded as deltas against their own target position, so a steady-state
    scatter row costs 4 (idx) + 1-2 (value) + 1 (flag) bytes instead of 9 —
    losslessly, with automatic wide fallback when a batch's deltas
    overflow the narrow width.

    With ``mesh`` (a zero-argument callable giving it) the buffers are
    that mesh's pool-sharded resident pack: the delta batch is placed on every
    device and the scatter's outputs keep the buffers' sharding."""

    def __init__(self, donate: Optional[bool] = None, mesh=None):
        self._fns: Dict[Tuple, object] = {}
        self._donate = donate
        # the delta batch goes to every device of the buffers' mesh
        self._to = _Replicated(mesh)

    def _fn(self, shape: Tuple[int, ...], kb: int, codec: int = 0):
        key = (shape, kb, codec)
        fn = self._fns.get(key)
        if fn is None:
            import jax
            import jax.numpy as jnp
            if self._donate is None:
                self._donate = _donate_default()
            T = shape[-1]

            def _apply(rows_buf, flags_buf, idx, rows_v, flags_v):
                flat_r = rows_buf.reshape(-1)
                flat_f = flags_buf.reshape(-1)
                if codec != ROWS_WIDE:
                    # position-relative decode; the padding sentinel's
                    # garbage value is dropped by its OOB index anyway
                    rows_v32 = rows_v.astype(jnp.int32) + (idx % T)
                else:
                    rows_v32 = rows_v
                # padding idx entries are == buffer size: OOB, dropped
                flat_r = flat_r.at[idx].set(rows_v32, mode="drop")
                flat_f = flat_f.at[idx].set(flags_v, mode="drop")
                return (flat_r.reshape(rows_buf.shape),
                        flat_f.reshape(flags_buf.shape))

            # over a mesh the outputs are pinned to the buffers' own
            # placement: they are the next scatter's (and the cycle's)
            # inputs, and a placement of the compiler's choosing would
            # miss the warmed executables and reshard at every dispatch
            pinned = {}
            mesh = self._to.mesh
            if mesh is not None and mesh.size > 1:
                from ..parallel.mesh import pool_sharding
                pinned["out_shardings"] = (pool_sharding(mesh),) * 2
            fn = telemetry.instrument_jit("delta.apply", jax.jit(
                _apply,
                donate_argnums=(0, 1) if self._donate else (), **pinned))
            self._fns[key] = fn
        return fn

    def stage(self, shape: Tuple[int, ...], idx: np.ndarray,
              rows_vals: np.ndarray, flags_vals: np.ndarray,
              quantize: bool = False) -> _StagedDelta:
        """Pad, negotiate the value codec, and START the host->device
        copies for one delta batch.  Split from :meth:`commit` so a
        pipelined driver's stage-(k+1) h2d overlaps cycle k's in-flight
        kernel (the double-buffering half of ISSUE 14's wire work)."""
        n_flat = int(np.prod(shape))
        T = int(shape[-1])
        k = int(idx.size)
        kb = min(bucket(max(k, 1), minimum=_DELTA_MIN_BUCKET), n_flat)
        if kb < k:  # bucket clamped under the delta: caller should repack
            raise ValueError(f"delta larger than buffer ({k} > {n_flat})")
        idx_p = np.full(kb, n_flat, dtype=np.int32)  # OOB sentinel pad
        idx_p[:k] = idx
        codec = ROWS_WIDE
        if quantize and k:
            delta = rows_vals.astype(np.int64) - (idx.astype(np.int64) % T)
            lo, hi = int(delta.min()), int(delta.max())
            if -128 <= lo and hi <= 127:
                codec, dt = ROWS_I8, np.int8
            elif -32768 <= lo and hi <= 32767:
                codec, dt = ROWS_I16, np.int16
            else:
                # the lossless-or-wide contract counts EVERY wide
                # fallback (an operator must be able to see the narrow
                # path never engaging)
                note_wide("delta")
        if codec != ROWS_WIDE:
            rows_p = np.zeros(kb, dtype=dt)
            rows_p[:k] = delta.astype(dt)
        else:
            rows_p = np.zeros(kb, dtype=np.int32)
            rows_p[:k] = rows_vals
        flags_p = np.zeros(kb, dtype=np.uint8)
        flags_p[:k] = flags_vals
        nbytes = (idx_p.nbytes + rows_p.nbytes + flags_p.nbytes) \
            * self._to.replicas
        telemetry.count_transfer("h2d", nbytes)
        put = self._to.put
        return _StagedDelta(tuple(shape), kb, codec, put(idx_p),
                            put(rows_p), put(flags_p), nbytes)

    def commit(self, rows_dev, flags_dev, st: _StagedDelta):
        """Dispatch the scatter against a previously staged batch."""
        fn = self._fn(st.shape, st.kb, st.codec)
        return fn(rows_dev, flags_dev, st.idx, st.vals, st.flags)

    def apply(self, rows_dev, flags_dev, idx: np.ndarray,
              rows_vals: np.ndarray, flags_vals: np.ndarray,
              quantize: bool = False):
        """Scatter the delta batch into the resident buffers; returns the
        (new_rows_dev, new_flags_dev) device arrays.  ``idx`` holds flat
        positions into the raveled buffer."""
        st = self.stage(tuple(rows_dev.shape), idx, rows_vals,
                        flags_vals, quantize=quantize)
        return self.commit(rows_dev, flags_dev, st)


# chunk appends to the base mirror are padded to power-of-two buckets
# from this floor up (one executable per capacity and bucket)
APPEND_MIN_BUCKET = 1024

_append_fn = None


def append_chunk(base, chunk, off):
    """Donating chunk append into a base-mirror column: ONE jitted
    entry point for every mirror of the process (jit caches one
    executable per shape), so a mirror rebuilt after a device fault —
    and the boot warm-up — reuse the same executables."""
    global _append_fn
    if _append_fn is None:
        import jax
        from jax import lax
        _append_fn = telemetry.instrument_jit(
            "delta.append", jax.jit(
                lambda b, c, o: lax.dynamic_update_slice(
                    b, c, (o,) + (0,) * (c.ndim - 1)),
                donate_argnums=0))
    return _append_fn(base, chunk, off)


class DeviceBaseMirror:
    """Device-resident mirror of the columnar index's immutable res/disk
    base columns: rows are append-only while the compaction epoch is
    unchanged, so steady-state cycles upload only the NEW rows (one
    bucketed chunk append); a compaction epoch change or capacity
    overflow triggers a full (re)upload.  Shared by the fused driver and
    the columnar rank path."""

    def __init__(self, mesh=None):
        self._floor = 0                   # least capacity of an upload
        # the mirror is replicated over the pool mesh: every shard
        # gathers its own pools' rows from its own copy
        self.placement = _Replicated(mesh)
        self.reset()

    def reset(self) -> None:
        """Let go of the device buffers (the next sync uploads whole);
        a reserved capacity stays."""
        self._key: Optional[int] = None   # compaction epoch mirrored
        self._n = 0                       # rows synced
        self._cap = 0                     # device buffer capacity
        self._res = None                  # f32[cap, 4] on device
        self._disk = None                 # f32[cap] on device

    @property
    def capacity(self) -> int:
        return self._cap

    def reserve(self, capacity: int) -> None:
        """No (re)upload allocates less than ``capacity`` rows from now
        on: the capacity is a shape of every kernel the mirror feeds,
        so a warm-up that compiled for one pins it here."""
        self._floor = max(self._floor, int(capacity))

    def sync(self, res_base: np.ndarray, disk_base: np.ndarray,
             compactions: int):
        """Bring the device mirror up to the snapshot: full (re)upload on
        a compaction epoch change or capacity overflow, else one bucketed
        chunk append of the rows added since the last cycle.  Returns the
        (res, disk) device arrays (capacity-padded)."""
        put, replicas = self.placement.put, self.placement.replicas
        n = res_base.shape[0]
        full = (self._key != compactions or n > self._cap)
        if not full and n > self._n:
            k = n - self._n
            kb = bucket(k, minimum=APPEND_MIN_BUCKET)
            if self._n + kb > self._cap:
                full = True  # dynamic_update_slice would clamp, not grow
            else:
                chunk = np.zeros((kb, 4), dtype=F32)
                chunk[:k] = res_base[self._n:n]
                dchunk = np.zeros(kb, dtype=F32)
                dchunk[:k] = disk_base[self._n:n]
                off = put(np.asarray(self._n, dtype=np.int32))
                telemetry.count_transfer(
                    "h2d", (chunk.nbytes + dchunk.nbytes) * replicas)
                self._res = append_chunk(self._res, put(chunk), off)
                self._disk = append_chunk(self._disk, put(dchunk), off)
                self._n = n
        if full:
            cap = max(bucket(n, minimum=1024), self._floor)
            res_p = np.zeros((cap, 4), dtype=F32)
            res_p[:n] = res_base
            disk_p = np.zeros(cap, dtype=F32)
            disk_p[:n] = disk_base
            telemetry.count_transfer(
                "h2d", (res_p.nbytes + disk_p.nbytes) * replicas)
            self._res = put(res_p)
            self._disk = put(disk_p)
            self._key, self._n, self._cap = compactions, n, cap
        return self._res, self._disk
