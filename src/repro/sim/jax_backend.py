"""Accelerator-resident batched pricing: the jit/vmap congestion engine.

:class:`JaxBatchSimulator` is the JAX port of ``repro.sim.batch``'s hot
path. Where the NumPy engine gathers ``candidates x phases x ports``
endpoint arrays on the host and prices them through
``Topology.bucket_times``, this engine compiles the whole pricing of a
candidate stack — endpoint gather, crossing-level stride arithmetic,
per-level congestion reduction, and the slab maxima — into one XLA
program with static shapes per (pattern, grid, machine), so a beam
prices with no host<->device round trips inside the loop. On CPU the
jit still wins on the workload the ASI search loops generate (arbitrary
proposer placements, where the NumPy engine's symmetry-folding and
incremental shortcuts cannot fire); on an accelerator the same program
runs device-resident.

Two compiled formulations, chosen per schedule on the host:

**Dense gather** (``mode="dense"``) — for schedules whose (slab,
endpoint) pairs are unique (each tile sends and receives at most once
per slab: trees, rings, halos, shifted panels — everything the registry
builders emit) and bijective candidate rows. The schedule exports
candidate-independent matrices ``M[slab, tile] -> transfer id``
(sentinel for absent), so a candidate's per-port loads are *pure
gathers*: permute columns by the inverse assignment, look up per-level
masked weights, and reduce — per-row segment sums over each level's
``stride`` processors, then the port max. No scatter appears anywhere,
which is what makes XLA:CPU fast here (its scatter lowers to a serial
loop; gathers and contiguous reductions vectorize). The per-level alpha
term folds into the byte weight exactly: ``msgs*alpha + load/beta ==
sum(nbytes + alpha*beta)/beta``.

**Segment scatter** (``mode="scatter"``) — the general fallback (repeat
endpoints per slab, non-bijective rows, or a dense table past the cell
ceiling): the ``bucket_times`` formulation as masked ``segment-sum``
scatter-adds into compact per-level (direction, slab, port) tables with
out-of-bounds drop masking.

The per-level reduction of the dense mode is also available as a Pallas
kernel (``repro.kernels.segment_reduce``, ``use_pallas=True``) — on CPU
it runs in interpret mode as a correctness path, on TPU it lowers to
Mosaic. Mosaic has no float64, so this path takes ``dtype="float32"``
only.

``dtype="float64"`` (the default, run under ``jax.enable_x64``)
reproduces the NumPy reference to ~1e-15 relative — the
registry-wide <=1e-6 parity gate in ``benchmarks/sim_eval.py`` runs in
float64. ``dtype="float32"`` halves bandwidth but accumulates port loads
in single precision: expect ~1e-5 relative drift on large slabs, fine
for search ranking, NOT enough for the parity gate (see
docs/simulator.md "Backends").

Folding flags are accepted for API parity and ignored: the fold and
incremental shortcuts *copy* dense prices bit-for-bit by construction,
so always pricing dense returns identical values — the flags only trade
speed, and on this engine the compiled dense pass is the fast path.
"""
from __future__ import annotations

import dataclasses
import weakref
from contextlib import nullcontext
from typing import Sequence

import numpy as np

from repro.core.machine import DegradedMachine, MachineSpec
from repro.sim.batch import BatchSimulator, ReadyPrices, _count
from repro.sim.collectives import (
    CollectivePattern,
    PackedSchedule,
    packed_schedule,
    register_cache,
)
from repro.sim.topology import Topology

import jax
import jax.numpy as jnp

#: Cell ceiling for the dense-gather mode's (n_unique x ntiles) lookup
#: tables; schedules past it (or with repeated per-slab endpoints) use
#: the segment-scatter formulation.
_DENSE_CELLS_MAX = 1 << 25

#: Per-pricing-call device working-set budget (elements); candidate
#: stacks are chunked so ``chunk * cells_per_candidate`` stays under it.
_MAX_DEVICE_ELEMS = 1 << 24

_DTYPES = ("float64", "float32")


def platform_info() -> dict:
    """What this process's JAX runtime resolved to: platform name and
    device count and kinds. ``repro.apps.run --backend jax`` prints it
    so the device the pricing ran on is always on record."""
    devices = jax.devices()
    return {
        "platform": jax.default_backend(),
        "device_count": len(devices),
        "devices": [d.device_kind for d in devices],
    }


def _x64(dtype: str):
    return jax.enable_x64(True) if dtype == "float64" else nullcontext()


def _pow2_floor(n: int) -> int:
    return 1 << (max(int(n), 1).bit_length() - 1)


def _rows_bijective(a: np.ndarray, nprocs: int) -> bool:
    """True when every stack row is a tile->processor permutation (the
    precondition of the dense-gather mode's inverse-assignment trick)."""
    if a.shape[1] != nprocs or a.size == 0:
        return False
    if int(a.min()) < 0 or int(a.max()) >= nprocs:
        return False
    seen = np.zeros(a.shape, dtype=bool)
    seen[np.arange(a.shape[0])[:, None], a] = True
    return bool(seen.all())


class _ScheduleExport:
    """Device-ready constants of one (PackedSchedule, Topology) pair.

    Host-side numpy views in canonical dtypes (int32 endpoints/slab ids,
    float64 payloads) plus, in dense mode, the candidate-independent
    ``M[slab, tile] -> transfer id`` lookup matrices. Compiled pricing
    callables are built lazily per (mode, dtype, use_pallas) and cached
    here; the export itself is cached on the schedule object, so its
    lifetime (and its jit cache's) tracks the memoized schedule's.
    """

    def __init__(self, sched: PackedSchedule, topo: Topology) -> None:
        self.u = sched.n_unique
        self.T = sched.n_transfers
        self.ntiles = int(np.prod(sched.grid))
        self.strides = tuple(int(s) for s in topo.port_strides)
        self.nports = tuple(int(p) for p in topo.spec.level_ports)
        self.alphas = tuple(float(x) for x in topo.alphas)
        self.betas = tuple(float(x) for x in topo.betas)
        self.nprocs = topo.nprocs
        # Per-level port contention factors of the degraded machine, or
        # None when healthy (dead-proc checks stay host-side in
        # ``_dispatch_slabs`` — a masked proc is a refusal, not a price).
        degraded = topo.degraded
        if degraded is not None and degraded.contention is not None:
            self.cont = tuple(
                np.asarray(degraded.port_contention(lvl), dtype=np.float64)
                for lvl in range(len(topo.spec.shape))
            )
        else:
            self.cont = None
        self.src = sched.src.astype(np.int32)
        self.dst = sched.dst.astype(np.int32)
        self.slab = sched.phase_id.astype(np.int32)
        self.nbytes = np.asarray(sched.nbytes, dtype=np.float64)
        key = self.slab.astype(np.int64) * self.ntiles
        unique_endpoints = self.T == 0 or all(
            np.unique(key + e).size == self.T for e in (self.src, self.dst)
        )
        self.mode = (
            "dense"
            if unique_endpoints and self.ntiles == self.nprocs
            and self.u * self.ntiles <= _DENSE_CELLS_MAX
            else "scatter"
        )
        if self.mode == "dense":
            ids = np.arange(self.T, dtype=np.int32)
            self.Ms = np.full((self.u, self.ntiles), self.T, np.int32)
            self.Md = np.full((self.u, self.ntiles), self.T, np.int32)
            self.Ms[self.slab, self.src] = ids
            self.Md[self.slab, self.dst] = ids
        if max(2 * self.u * p for p in self.nports) >= 2 ** 31:
            raise ValueError(
                "schedule's congestion table exceeds int32 indexing; "
                "use the NumPy batch engine for this scale"
            )
        self._fns: dict = {}

    # ------------------------------------------------------------ chunking
    def chunk(self, mode: str) -> int:
        if mode == "dense":
            cells = 2 * self.u * self.ntiles
        else:
            cells = sum(2 * self.u * p for p in self.nports) + 4 * self.T
        return _pow2_floor(max(1, _MAX_DEVICE_ELEMS // max(cells, 1)))

    # ------------------------------------------------- compiled callables
    def fn(self, mode: str, dtype: str, use_pallas: bool,
           donate: bool = False):
        """The jitted pricing callable for one formulation. ``donate``
        hands the chunk's device input buffer to XLA for reuse — worth it
        only when a stack spans several chunks (each chunk's input is
        dead the moment its program launches) and only off-CPU (the CPU
        backend does not implement donation and warns)."""
        if use_pallas and self.cont is not None:
            # The Pallas tables fold alpha into one byte weight per
            # transfer; per-port contention needs the byte and alpha
            # terms reduced separately, so route contended machines
            # through the plain dense build (numerically identical).
            use_pallas = False
        key = (mode, dtype, use_pallas, donate)
        hit = self._fns.get(key)
        if hit is None:
            dt = jnp.float64 if dtype == "float64" else jnp.float32
            if mode == "dense":
                raw = (self._build_dense_pallas(dt) if use_pallas
                       else self._build_dense(dt))
            else:
                raw = self._build_scatter(dt)
            hit = jax.jit(raw, donate_argnums=(0,) if donate else ())
            self._fns[key] = hit
        return hit

    def _level_masks(self, src, dst):
        """Per-level exactly-crossing masks from stride arithmetic:
        ``src // stride[L] != dst // stride[L]`` first differs at the
        crossing level and stays different inward."""
        masks = []
        outer = jnp.zeros(src.shape, dtype=bool)
        for s in self.strides:
            diff = (src // s) != (dst // s)
            masks.append(diff & ~outer)
            outer = outer | diff
        return masks

    def _build_dense(self, dt):
        exp = self

        def row(a_row):
            src = a_row[jnp.asarray(exp.src)]
            dst = a_row[jnp.asarray(exp.dst)]
            inv = jnp.zeros((exp.ntiles,), jnp.int32).at[a_row].set(
                jnp.arange(exp.ntiles, dtype=jnp.int32))
            nb = jnp.asarray(exp.nbytes, dtype=dt)
            zero = jnp.zeros((1,), dtype=dt)
            out = jnp.zeros((exp.u,), dtype=dt)
            masks = exp._level_masks(src, dst)
            for L, (stride, ports, al, be) in enumerate(
                    zip(exp.strides, exp.nports, exp.alphas, exp.betas)):
                cl = (jnp.asarray(exp.cont[L], dtype=dt)
                      if exp.cont is not None else None)
                if stride == 1:
                    # One message per (slab, port, direction): the slab
                    # time at this level is a pure segment-max of the
                    # per-transfer times; under contention the slower of
                    # the transfer's two ports sets its drain.
                    if cl is None:
                        t = al + nb / be
                    else:
                        t = al + nb * jnp.maximum(cl[src], cl[dst]) / be
                    t1 = jnp.concatenate(
                        [jnp.where(masks[L], t, 0.0), zero])
                    out = jnp.maximum(out, t1[jnp.asarray(exp.Ms)]
                                      .max(axis=1))
                else:
                    # Port loads by gather: column-permute M by the
                    # inverse assignment, look up masked byte weights
                    # (alpha folded in), sum each subtree's `stride`
                    # processors, max over ports, both directions.
                    if cl is None:
                        w = jnp.concatenate(
                            [jnp.where(masks[L], nb + al * be, 0.0), zero])
                        eg = (w[jnp.asarray(exp.Ms)[:, inv]]
                              .reshape(exp.u, ports, stride).sum(axis=2))
                        ing = (w[jnp.asarray(exp.Md)[:, inv]]
                               .reshape(exp.u, ports, stride).sum(axis=2))
                    else:
                        # Contention scales a port's *byte* drain but not
                        # its per-message alpha, so the folded weight
                        # splits: bytes (scaled per port after the
                        # segment sum) + alpha*beta (unscaled).
                        wb = jnp.concatenate(
                            [jnp.where(masks[L], nb, 0.0), zero])
                        wa = jnp.concatenate(
                            [jnp.where(masks[L], jnp.full_like(nb, al * be),
                                       0.0), zero])
                        Msi = jnp.asarray(exp.Ms)[:, inv]
                        Mdi = jnp.asarray(exp.Md)[:, inv]
                        eg = (wb[Msi].reshape(exp.u, ports, stride)
                              .sum(axis=2) * cl[None, :]
                              + wa[Msi].reshape(exp.u, ports, stride)
                              .sum(axis=2))
                        ing = (wb[Mdi].reshape(exp.u, ports, stride)
                               .sum(axis=2) * cl[None, :]
                               + wa[Mdi].reshape(exp.u, ports, stride)
                               .sum(axis=2))
                    out = jnp.maximum(
                        out,
                        jnp.maximum(eg.max(axis=1), ing.max(axis=1)) / be,
                    )
            return out

        return jax.vmap(row)

    def _build_dense_pallas(self, dt):
        """Dense mode with the per-level reduction routed through the
        Pallas segment-reduce kernel (tables materialize per chunk, then
        ``segment_rowmax`` reduces them; numerically identical on CPU
        interpret mode, Mosaic-lowered on TPU)."""
        from repro.kernels import ops as kops

        exp = self

        def tables(a_row):
            src = a_row[jnp.asarray(exp.src)]
            dst = a_row[jnp.asarray(exp.dst)]
            inv = jnp.zeros((exp.ntiles,), jnp.int32).at[a_row].set(
                jnp.arange(exp.ntiles, dtype=jnp.int32))
            nb = jnp.asarray(exp.nbytes, dtype=dt)
            zero = jnp.zeros((1,), dtype=dt)
            masks = exp._level_masks(src, dst)
            tabs = []
            for L, (stride, al, be) in enumerate(
                    zip(exp.strides, exp.alphas, exp.betas)):
                if stride == 1:
                    t1 = jnp.concatenate(
                        [jnp.where(masks[L], al + nb / be, 0.0), zero])
                    tabs.append(t1[jnp.asarray(exp.Ms)])
                else:
                    w = jnp.concatenate(
                        [jnp.where(masks[L], nb + al * be, 0.0), zero])
                    tabs.append(w[jnp.asarray(exp.Ms)[:, inv]])
                    tabs.append(w[jnp.asarray(exp.Md)[:, inv]])
            return tuple(tabs)

        batched = jax.vmap(tables)

        def fn(a):
            tabs = batched(a)
            n = a.shape[0]
            out = jnp.zeros((n, exp.u), dtype=dt)
            i = 0
            for stride, be in zip(exp.strides, exp.betas):
                if stride == 1:
                    red = kops.segment_rowmax(
                        tabs[i].reshape(n * exp.u, exp.ntiles), 1)
                    out = jnp.maximum(out, red.reshape(n, exp.u))
                    i += 1
                else:
                    for _ in range(2):
                        red = kops.segment_rowmax(
                            tabs[i].reshape(n * exp.u, exp.ntiles), stride)
                        out = jnp.maximum(out,
                                          red.reshape(n, exp.u) / be)
                        i += 1
            return out

        return fn

    def _build_scatter(self, dt):
        """The general formulation: masked segment-sum scatter-adds into
        per-level (direction, slab, port) tables, out-of-bounds indices
        dropped. Handles repeated per-slab endpoints (alltoall) and
        non-bijective placements."""
        exp = self

        def row(a_row):
            src = a_row[jnp.asarray(exp.src)]
            dst = a_row[jnp.asarray(exp.dst)]
            slab = jnp.asarray(exp.slab)
            nb = jnp.asarray(exp.nbytes, dtype=dt)
            out = jnp.zeros((exp.u,), dtype=dt)
            masks = exp._level_masks(src, dst)
            for L, (stride, ports, al, be) in enumerate(
                    zip(exp.strides, exp.nports, exp.alphas, exp.betas)):
                oob = jnp.int32(2 * exp.u * ports)
                base = slab * ports
                cell = jnp.concatenate([
                    jnp.where(masks[L], base + src // stride, oob),
                    jnp.where(masks[L], oob // 2 + base + dst // stride,
                              oob),
                ])
                if exp.cont is None:
                    w = jnp.where(masks[L], nb + al * be, 0.0)
                    ws = jnp.concatenate([w, w])
                else:
                    # Scale each transfer's byte load by its port's
                    # contention factor per direction; alpha unscaled.
                    cl = jnp.asarray(exp.cont[L], dtype=dt)
                    ws = jnp.concatenate([
                        jnp.where(masks[L],
                                  nb * cl[src // stride] + al * be, 0.0),
                        jnp.where(masks[L],
                                  nb * cl[dst // stride] + al * be, 0.0),
                    ])
                tab = jnp.zeros((2 * exp.u * ports,), dtype=dt).at[cell].add(
                    ws, mode="drop")
                out = jnp.maximum(
                    out,
                    (tab / be).reshape(2, exp.u, ports).max(axis=(0, 2)),
                )
            return out

        return jax.vmap(row)


#: Live schedules carrying a ``_jax_exports`` cache, held weakly (by
#: ``id`` — PackedSchedule's ndarray fields make it unhashable, ruling
#: out a WeakSet; dead ids are pruned automatically and a recycled id
#: simply overwrites) plus hit/miss counters, so ``repro.sim
#: .collectives.cache_stats()`` can report the compiled-program
#: population and ``clear_caches()`` can reclaim it.
_EXPORT_HOSTS: "weakref.WeakValueDictionary[int, PackedSchedule]" = \
    weakref.WeakValueDictionary()
_EXPORT_STATS = {"hits": 0, "misses": 0}


def _exports_clear() -> None:
    for sched in list(_EXPORT_HOSTS.values()):
        cache = getattr(sched, "_jax_exports", None)
        if cache:
            cache.clear()
    for key in _EXPORT_STATS:
        _EXPORT_STATS[key] = 0


def _exports_stats() -> dict:
    size = sum(len(getattr(sched, "_jax_exports", ()) or ())
               for sched in _EXPORT_HOSTS.values())
    return {"size": size, **_EXPORT_STATS}


register_cache("jax_exports", _exports_clear, _exports_stats)


def _export_for(sched: PackedSchedule, topo: Topology) -> _ScheduleExport:
    """The (schedule, topology) export, cached on the schedule object so
    compiled programs are shared by every engine pricing that schedule
    and die with it."""
    cache = getattr(sched, "_jax_exports", None)
    if cache is None:
        cache = {}
        object.__setattr__(sched, "_jax_exports", cache)
        _EXPORT_HOSTS[id(sched)] = sched
    key = (topo.spec, topo.alphas, topo.betas, topo.degraded)
    hit = cache.get(key)
    if hit is None:
        _EXPORT_STATS["misses"] += 1
        hit = cache[key] = _ScheduleExport(sched, topo)
    else:
        _EXPORT_STATS["hits"] += 1
    return hit


_SHARDINGS: dict = {}


def _device_put_chunk(blk: np.ndarray):
    """Stage one candidate chunk on device. Multi-device hosts shard the
    leading (candidate) axis — rows are independent under ``vmap``, so
    jit partitions the whole program with no cross-device traffic; chunk
    shapes are powers of two, so any power-of-two device count divides
    them. Uneven or single-device cases fall back to one replica."""
    devices = jax.devices()
    nd = len(devices)
    if nd > 1 and blk.shape[0] % nd == 0:
        sharding = _SHARDINGS.get(nd)
        if sharding is None:
            mesh = jax.sharding.Mesh(np.asarray(devices), ("candidates",))
            sharding = _SHARDINGS[nd] = jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec("candidates"))
        return jax.device_put(blk, sharding)
    return jnp.asarray(blk)


@dataclasses.dataclass(frozen=True)
class JaxBatchSimulator(BatchSimulator):
    """The batched engine with device-compiled congestion pricing.

    Same contract as :class:`BatchSimulator` (stacks of tile->processor
    placements in, steady-state seconds out; ``fold``/``incremental``
    accepted but moot — see the module docstring); ``price_stacks``
    detects ``prices_independently`` and lets each stack run as its own
    compiled program instead of joining the host gather pass.
    """

    dtype: str = "float64"
    use_pallas: bool = False

    #: Each stack prices as one compiled program; do not concatenate
    #: into the NumPy congestion pass (checked by ``price_stacks``).
    prices_independently = True

    def __post_init__(self) -> None:
        super().__post_init__()
        if self.dtype not in _DTYPES:
            raise ValueError(
                f"dtype must be one of {_DTYPES}, got {self.dtype!r}"
            )
        if self.use_pallas and self.dtype == "float64":
            raise ValueError(
                "use_pallas=True needs dtype='float32': the Pallas "
                "segment-reduce kernel lowers to Mosaic, which has no "
                "float64"
            )

    def phase_durations(self, assignments: np.ndarray, *,
                        fold: bool = True,
                        incremental: bool = True) -> np.ndarray:
        """(N, n_phases) congestion-priced phase times, the whole stack
        as chunked invocations of one compiled program. ``fold`` and
        ``incremental`` are accepted for interface parity and ignored:
        both shortcuts copy dense prices bit-exactly, so dense pricing
        returns the same values either way."""
        del fold, incremental
        a = self._flat_assignments(assignments)
        n, sched = a.shape[0], self.schedule
        if sched.n_transfers == 0 or n == 0 or sched.n_phases == 0:
            return np.zeros((n, sched.n_phases), dtype=np.float64)
        slab_times = self._slab_times(a)
        _count("pairs_priced",
               n * int((np.diff(sched.starts) > 0).sum()))
        return slab_times[:, sched.phase_map]

    def _dispatch_slabs(self, a: np.ndarray) -> list[tuple]:
        """Launch the stack's chunked pricing programs and return the
        in-flight ``(device_output, take)`` pairs without waiting.

        JAX dispatch is asynchronous on every backend: each ``fn`` call
        returns as soon as the program is enqueued, so by the time the
        first chunk finishes the rest are already queued behind it —
        double-buffered by the runtime — and the host is free to expand
        the next candidate group. Oversize stacks that split into
        several chunks donate each chunk's input buffer back to XLA
        (off-CPU only; the CPU backend does not implement donation)."""
        exp = _export_for(self.schedule, self.topology)
        degraded = self.topology.degraded
        if degraded is not None and degraded.dead_procs:
            # Masked procs are unplaceable: refuse on the host before any
            # device dispatch (same contract as Topology.bucket_times).
            self.topology.check_placeable(a)
        mode = exp.mode
        if mode == "dense" and not _rows_bijective(a, exp.nprocs):
            mode = "scatter"      # dense needs invertible rows
        n = a.shape[0]
        chunk = min(exp.chunk(mode), _pow2_floor(2 * n - 1) if n else 1)
        donate = n > chunk and jax.default_backend() != "cpu"
        a32 = np.ascontiguousarray(a, dtype=np.int32)
        parts: list[tuple] = []
        with _x64(self.dtype):
            fn = exp.fn(mode, self.dtype, self.use_pallas, donate)
            for lo in range(0, n, chunk):
                blk = a32[lo:lo + chunk]
                take = blk.shape[0]
                if take < chunk:      # pad to the compiled chunk shape
                    blk = np.concatenate(
                        [blk, np.broadcast_to(blk[-1:],
                                              (chunk - take, blk.shape[1]))])
                parts.append((fn(_device_put_chunk(blk)), take))
        return parts

    @staticmethod
    def _collect_slabs(parts: list[tuple], n: int, u: int) -> np.ndarray:
        """Block on the in-flight chunk programs (oldest first — the
        device finishes them in dispatch order) and assemble the full
        (N, n_unique) slab-time matrix on the host."""
        out = np.empty((n, u), dtype=np.float64)
        lo = 0
        for dev, take in parts:
            out[lo:lo + take] = np.asarray(dev)[:take]
            lo += take
        return out

    def _slab_times(self, a: np.ndarray) -> np.ndarray:
        exp = _export_for(self.schedule, self.topology)
        return self._collect_slabs(self._dispatch_slabs(a), a.shape[0],
                                   exp.u)

    def step_times_async(self, assignments: np.ndarray, *,
                         fold: bool = True,
                         incremental: bool = True) -> "ReadyPrices":
        """Dispatch the whole stack's pricing and return immediately with
        a deferred handle; ``result()`` blocks on the device outputs and
        closes the step recurrence. Between dispatch and ``result()`` the
        host is free — this is the overlap the tuner's streaming pipeline
        lives on. Values are bit-identical to :meth:`step_times` (same
        programs, same chunking; only the wait moves)."""
        del fold, incremental     # moot — see phase_durations
        a = self._flat_assignments(assignments)
        n, sched = a.shape[0], self.schedule
        if sched.n_transfers == 0 or n == 0 or sched.n_phases == 0:
            return ReadyPrices(self._close_steps(
                np.zeros((n, sched.n_phases), dtype=np.float64)))
        parts = self._dispatch_slabs(a)
        _count("pairs_priced",
               n * int((np.diff(sched.starts) > 0).sum()))
        return _InFlightPrices(self, parts, n)


class _InFlightPrices:
    """Deferred step times of one dispatched stack: the chunk programs
    are already running on the device; ``result()`` blocks on their
    outputs (oldest chunk first), assembles slab times, and closes the
    step recurrence. Idempotent — the device buffers are dropped after
    the first materialization."""

    __slots__ = ("_sim", "_parts", "_n", "_value")

    def __init__(self, sim: "JaxBatchSimulator", parts: list[tuple],
                 n: int) -> None:
        self._sim = sim
        self._parts = parts
        self._n = n
        self._value: np.ndarray | None = None

    def result(self) -> np.ndarray:
        if self._value is None:
            sim = self._sim
            sched = sim.schedule
            exp = _export_for(sched, sim.topology)
            slab_times = sim._collect_slabs(self._parts, self._n, exp.u)
            self._parts = []
            self._value = sim._close_steps(slab_times[:, sched.phase_map])
        return self._value


def to_jax(engine: BatchSimulator, *, dtype: str = "float64",
           use_pallas: bool = False) -> JaxBatchSimulator:
    """The JAX twin of a NumPy batch engine (same schedule/topology/step
    closure, compiled pricing)."""
    return JaxBatchSimulator(
        topology=engine.topology, schedule=engine.schedule,
        compute_s=engine.compute_s, backpressure=engine.backpressure,
        steps=engine.steps, dtype=dtype, use_pallas=use_pallas,
    )


def jax_batch_simulator(pattern: CollectivePattern, spec: MachineSpec,
                        grid: Sequence[int], *, step_flops: float,
                        elem_bytes: int = 4, backpressure: int = 2,
                        steps: int = 3,
                        alphas: tuple[float, ...] | None = None,
                        dtype: str = "float64",
                        use_pallas: bool = False,
                        degraded: "DegradedMachine | None" = None
                        ) -> JaxBatchSimulator:
    """Build the JAX engine for one (pattern, machine, grid) point —
    the device-compiled counterpart of ``batch_simulator``."""
    grid = tuple(int(g) for g in grid)
    return JaxBatchSimulator(
        topology=Topology.from_spec(spec, alphas=alphas, degraded=degraded),
        schedule=packed_schedule(pattern, grid, elem_bytes=elem_bytes),
        compute_s=float(step_flops) / (spec.nprocs * spec.peak_flops),
        backpressure=backpressure,
        steps=steps,
        dtype=dtype,
        use_pallas=use_pallas,
    )


__all__ = [
    "JaxBatchSimulator",
    "jax_batch_simulator",
    "platform_info",
    "to_jax",
]
