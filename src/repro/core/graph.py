"""Ladybirds-style task-graph specification model (paper §3).

An application is a *sequence* of tasks. Each task reads and writes a set of
named :class:`Packet`\\ s with statically known sizes. Packets obey SSA: each
packet is written by exactly one task (or is an *external* input, conceptually
written by a virtual task 0). Packets marked ``keep=True`` are application
outputs, conceptually read by a virtual task ``n_t + 1`` — they must survive
the final burst.

The analysis products mirror the paper's §4.2 definitions:

* ``writer[p]``  — index of the task writing ``p`` (0 for external packets).
* ``l_inf[p]``   — last task index that reads or writes ``p``
  (``n_t + 1`` for ``keep`` packets).
* ``last_touch_before(k, p)`` — the paper's ``l_k(p)``: the highest index
  ``< k`` of a task touching ``p``; 0 when no earlier task touches it. For an
  external packet this is 0, so it is loaded by the first burst that uses it.

Indices are 1-based throughout (task 1 .. n_t), matching the paper's notation;
index 0 is the virtual "before the application" state.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Iterable, List, Mapping, Optional, Sequence, Tuple

import numpy as np

__all__ = ["Packet", "Task", "TaskGraph", "GraphBuilder", "GraphArrays",
           "GraphCSRArrays", "stack_graph_arrays", "stack_csr_arrays",
           "dense_export_nbytes"]


@dataclasses.dataclass(frozen=True)
class Packet:
    """A fixed-size unit of data exchanged between tasks.

    ``c0_weight`` scales the fixed (per-DMA-initiation) component of the
    transfer cost model. Sub-packets of a contiguous array that are always
    transferred as one coalesced DMA batch use ``c0_weight = 1/len(array)``
    to amortize the initiation cost (see DESIGN.md: coalescing note).
    """

    name: str
    nbytes: int
    c0_weight: float = 1.0
    keep: bool = False          # application output: must survive the last burst
    external: bool = False      # present in NVM before the application starts
    meta: Any = None            # optional payload (shape/dtype for the runtime)

    def __post_init__(self) -> None:
        if self.nbytes < 0:
            raise ValueError(f"packet {self.name!r}: negative size")


@dataclasses.dataclass(frozen=True)
class Task:
    """One atomic kernel invocation (paper: a *task*)."""

    name: str
    reads: Tuple[str, ...]
    writes: Tuple[str, ...]
    cost: float                               # E_task (units: whatever the cost model uses)
    fn: Optional[Callable[..., Mapping[str, Any]]] = None  # runtime body (optional)

    def __post_init__(self) -> None:
        if self.cost < 0:
            raise ValueError(f"task {self.name!r}: negative cost")
        if len(set(self.writes)) != len(self.writes):
            raise ValueError(f"task {self.name!r}: duplicate writes")
        if len(set(self.reads)) != len(self.reads):
            raise ValueError(f"task {self.name!r}: duplicate reads")
        if set(self.reads) & set(self.writes):
            raise ValueError(
                f"task {self.name!r}: packet both read and written — model "
                "'inout' as a read of the old version plus a write of a new one (SSA)"
            )


@dataclasses.dataclass(frozen=True)
class GraphArrays:
    """Dense, padded, cost-model-independent export of a :class:`TaskGraph`.

    The burst recurrence (§4.2, see :mod:`.burst`) only ever inspects, per
    task ``j`` (1-based) and per packet it touches: the packet's transfer
    size, its DMA-initiation weight, its last touch strictly before ``j``
    (``l_j``), its writer, and its overall last use (``l_∞``). Those are
    exported here as rectangular arrays — one row per task, one column per
    read/write *slot* — so that graphs of different sizes pad to a common
    shape and batch together under ``jax.vmap`` (see
    :mod:`repro.core.partition_jax`).

    Shapes: ``e_task`` is ``(N,)``; read arrays are ``(N, R)``; write arrays
    are ``(N, W)`` where ``N ≥ n_tasks`` and R/W are ≥ the per-task maximum
    read/write counts. Padded slots have ``*_valid == 0`` and contribute
    exactly zero to every cost term (their bytes/weights are zeroed too).
    Cost-model scalars (E_s, c0/c1 per direction) are *not* baked in — the
    same export serves the FRAM, PCIe-offload, remat, and HBM-bytes models.

    Index conventions match the paper: tasks are 1-based, ``read_lt == 0``
    means "never touched before" (external / first use), ``read_writer == 0``
    means external, and ``l_∞ == n_tasks + 1`` marks kept outputs.
    """

    n_tasks: int
    e_task: np.ndarray       # (N,)   f64  task execution cost, 0-padded
    read_bytes: np.ndarray   # (N, R) f64  |p| per read slot
    read_c0w: np.ndarray     # (N, R) f64  c0_weight per read slot
    read_lt: np.ndarray      # (N, R) i32  l_j(p): last touch strictly before j
    read_writer: np.ndarray  # (N, R) i32  writer(p) (0 = external)
    read_linf: np.ndarray    # (N, R) i32  l_∞(p) of the read packet
    read_valid: np.ndarray   # (N, R) f64  1.0 for real slots, 0.0 padding
    write_bytes: np.ndarray  # (N, W) f64
    write_c0w: np.ndarray    # (N, W) f64
    write_linf: np.ndarray   # (N, W) i32  l_∞(p) of the written packet
    write_valid: np.ndarray  # (N, W) f64

    @property
    def n_pad(self) -> int:
        return int(self.e_task.shape[-1])

    @property
    def r_pad(self) -> int:
        return int(self.read_bytes.shape[-1])

    @property
    def w_pad(self) -> int:
        return int(self.write_bytes.shape[-1])

    def padded(self, n_pad: int, r_pad: int, w_pad: int) -> "GraphArrays":
        """Re-pad to a (larger) common shape, for cross-graph batching."""
        if n_pad < self.n_pad or r_pad < self.r_pad or w_pad < self.w_pad:
            raise ValueError(
                f"cannot shrink padding {(self.n_pad, self.r_pad, self.w_pad)} "
                f"to {(n_pad, r_pad, w_pad)}"
            )

        def pad(a: np.ndarray, *target: int) -> np.ndarray:
            widths = [(0, t - s) for t, s in zip(target, a.shape)]
            return np.pad(a, widths)

        return GraphArrays(
            n_tasks=self.n_tasks,
            e_task=pad(self.e_task, n_pad),
            read_bytes=pad(self.read_bytes, n_pad, r_pad),
            read_c0w=pad(self.read_c0w, n_pad, r_pad),
            read_lt=pad(self.read_lt, n_pad, r_pad),
            read_writer=pad(self.read_writer, n_pad, r_pad),
            read_linf=pad(self.read_linf, n_pad, r_pad),
            read_valid=pad(self.read_valid, n_pad, r_pad),
            write_bytes=pad(self.write_bytes, n_pad, w_pad),
            write_c0w=pad(self.write_c0w, n_pad, w_pad),
            write_linf=pad(self.write_linf, n_pad, w_pad),
            write_valid=pad(self.write_valid, n_pad, w_pad),
        )


def dense_export_nbytes(n_tasks: int, r_slots: int, w_slots: int) -> int:
    """Bytes :meth:`TaskGraph.to_arrays` would materialize, without building it.

    Used by the engine's ``backend="auto"`` policy and the benchmarks: on the
    full head-count graph the ``(N, R)`` rectangle alone is ~238 MB of float64
    (R ≈ 5452 because the sort task reads every score packet), which is why
    skewed-degree graphs route to the CSR export instead.
    """
    n, r, w = int(n_tasks), int(r_slots), int(w_slots)
    f64 = 8 * (n + 3 * n * r + 3 * n * w)  # e_task; read/write bytes,c0w,valid
    i32 = 4 * (3 * n * r + n * w)          # read lt,writer,linf; write linf
    return f64 + i32


@dataclasses.dataclass(frozen=True)
class GraphCSRArrays:
    """Compressed (CSR-style) slot export of a :class:`TaskGraph`.

    Same per-slot quantities as :class:`GraphArrays`, but the ``(N, R)`` /
    ``(N, W)`` rectangles are flattened task-major into flat slot arrays with
    row pointers: task ``j`` (1-based) owns read slots
    ``read_ptr[j-1]:read_ptr[j]`` and write slots
    ``write_ptr[j-1]:write_ptr[j]``, in declaration order. Export size is
    O(n_tasks + nnz) instead of O(n_tasks × max_degree) — the full 5458-task
    head-count graph (whose sort task reads 5452 score packets and would
    force a ~1 GB dense export) compresses to ~400 kB.

    This is the feed for the Pallas sweep kernel
    (:mod:`repro.kernels.partition_sweep`): the issue's ``slot_task_ptr`` /
    ``slot_cost`` / ``slot_lt`` / ``slot_writer`` / ``slot_linf`` operands are
    ``read_ptr`` plus the per-slot arrays below, with byte counts turned into
    costs at solve time (the export stays cost-model-independent, exactly
    like :class:`GraphArrays`).

    Padding is CSR-natural: extra ``e_task`` rows carry pointer ``nnz`` (no
    slots), and padded slot entries are never addressed by any pointer range,
    so padded graphs solve identically — that is what
    :func:`stack_csr_arrays` relies on.
    """

    n_tasks: int
    e_task: np.ndarray        # (N,)      f64  task execution cost, 0-padded
    read_ptr: np.ndarray      # (N+1,)    i32  row pointers into the read slots
    read_bytes: np.ndarray    # (nnz_r,)  f64  |p| per read slot
    read_c0w: np.ndarray      # (nnz_r,)  f64  c0_weight per read slot
    read_lt: np.ndarray       # (nnz_r,)  i32  l_j(p): last touch strictly before j
    read_writer: np.ndarray   # (nnz_r,)  i32  writer(p) (0 = external)
    read_linf: np.ndarray     # (nnz_r,)  i32  l_∞(p) of the read packet
    write_ptr: np.ndarray     # (N+1,)    i32  row pointers into the write slots
    write_bytes: np.ndarray   # (nnz_w,)  f64
    write_c0w: np.ndarray     # (nnz_w,)  f64
    write_linf: np.ndarray    # (nnz_w,)  i32

    @property
    def n_pad(self) -> int:
        return int(self.e_task.shape[-1])

    @property
    def nnz_reads(self) -> int:
        return int(self.read_bytes.shape[-1])

    @property
    def nnz_writes(self) -> int:
        return int(self.write_bytes.shape[-1])

    @property
    def nbytes(self) -> int:
        """Total bytes of the export (benchmarked against the dense path)."""
        return int(
            sum(
                getattr(self, f.name).nbytes
                for f in dataclasses.fields(self)
                if f.name != "n_tasks"
            )
        )

    def padded(self, n_pad: int, r_pad: int, w_pad: int) -> "GraphCSRArrays":
        """Re-pad to a (larger) common (N, nnz_r, nnz_w), for batching."""
        if n_pad < self.n_pad or r_pad < self.nnz_reads or w_pad < self.nnz_writes:
            raise ValueError(
                f"cannot shrink padding {(self.n_pad, self.nnz_reads, self.nnz_writes)} "
                f"to {(n_pad, r_pad, w_pad)}"
            )

        def pad_ptr(ptr: np.ndarray) -> np.ndarray:
            return np.pad(ptr, (0, n_pad - self.n_pad), mode="edge")

        def pad1(a: np.ndarray, target: int) -> np.ndarray:
            return np.pad(a, (0, target - a.shape[0]))

        return GraphCSRArrays(
            n_tasks=self.n_tasks,
            e_task=pad1(self.e_task, n_pad),
            read_ptr=pad_ptr(self.read_ptr),
            read_bytes=pad1(self.read_bytes, r_pad),
            read_c0w=pad1(self.read_c0w, r_pad),
            read_lt=pad1(self.read_lt, r_pad),
            read_writer=pad1(self.read_writer, r_pad),
            read_linf=pad1(self.read_linf, r_pad),
            write_ptr=pad_ptr(self.write_ptr),
            write_bytes=pad1(self.write_bytes, w_pad),
            write_c0w=pad1(self.write_c0w, w_pad),
            write_linf=pad1(self.write_linf, w_pad),
        )


def stack_csr_arrays(arrays: Sequence[GraphCSRArrays]) -> GraphCSRArrays:
    """Stack CSR exports of different graphs into one batch (leading axis B).

    All arrays re-pad to the largest (N, nnz_r, nnz_w) in the batch;
    ``n_tasks`` becomes a ``(B,)`` int array. Mirrors
    :func:`stack_graph_arrays` for the compressed layout — this is what
    :func:`repro.core.partition_jax._sweep_jax_batched` feeds the Pallas
    backend (one compiled kernel serves every graph in the batch).
    """
    if not arrays:
        raise ValueError("empty batch")
    n = max(a.n_pad for a in arrays)
    r = max(max(a.nnz_reads for a in arrays), 1)
    w = max(max(a.nnz_writes for a in arrays), 1)
    padded = [a.padded(n, r, w) for a in arrays]
    fields = {
        f.name: np.stack([getattr(a, f.name) for a in padded])
        for f in dataclasses.fields(GraphCSRArrays)
        if f.name != "n_tasks"
    }
    return GraphCSRArrays(
        n_tasks=np.array([a.n_tasks for a in arrays], dtype=np.int32),  # type: ignore[arg-type]
        **fields,
    )


def stack_graph_arrays(arrays: Sequence[GraphArrays]) -> GraphArrays:
    """Stack exports of different graphs into one batch (leading axis B).

    All arrays are re-padded to the largest (N, R, W) in the batch;
    ``n_tasks`` becomes an ``(B,)`` int array. The result is what
    :func:`repro.core.partition_jax._sweep_jax_batched` vmaps over.
    """
    if not arrays:
        raise ValueError("empty batch")
    n = max(a.n_pad for a in arrays)
    r = max(a.r_pad for a in arrays)
    w = max(a.w_pad for a in arrays)
    padded = [a.padded(n, r, w) for a in arrays]
    fields = {
        f.name: np.stack([getattr(a, f.name) for a in padded])
        for f in dataclasses.fields(GraphArrays)
        if f.name != "n_tasks"
    }
    return GraphArrays(
        n_tasks=np.array([a.n_tasks for a in arrays], dtype=np.int32),  # type: ignore[arg-type]
        **fields,
    )


class TaskGraph:
    """A validated sequential application with explicit data dependencies."""

    def __init__(self, tasks: Sequence[Task], packets: Iterable[Packet]):
        self.tasks: List[Task] = list(tasks)
        self.packets: Dict[str, Packet] = {}
        for p in packets:
            if p.name in self.packets:
                raise ValueError(f"duplicate packet {p.name!r}")
            self.packets[p.name] = p
        self._validate()
        self._analyze()

    # -- construction helpers -------------------------------------------------

    @property
    def n_tasks(self) -> int:
        return len(self.tasks)

    def task(self, index: int) -> Task:
        """1-based task accessor (paper notation)."""
        return self.tasks[index - 1]

    def _validate(self) -> None:
        writer: Dict[str, int] = {}
        for p in self.packets.values():
            if p.external:
                writer[p.name] = 0
        for idx, t in enumerate(self.tasks, start=1):
            for name in t.reads:
                if name not in self.packets:
                    raise ValueError(f"task {t.name!r} reads unknown packet {name!r}")
                if name not in writer:
                    raise ValueError(
                        f"task {t.name!r} (index {idx}) reads packet {name!r} "
                        "before it is written"
                    )
            for name in t.writes:
                if name not in self.packets:
                    raise ValueError(f"task {t.name!r} writes unknown packet {name!r}")
                if name in writer:
                    raise ValueError(
                        f"packet {name!r} written twice (SSA violation): "
                        f"by task {writer[name]} and task {idx}"
                    )
                writer[name] = idx
        for p in self.packets.values():
            if p.name not in writer:
                raise ValueError(f"packet {p.name!r} is never written and not external")
        self._writer = writer

    def _analyze(self) -> None:
        n = self.n_tasks
        # l_inf: last task touching each packet; keep-packets get n+1.
        l_inf: Dict[str, int] = {name: self._writer[name] for name in self.packets}
        for idx, t in enumerate(self.tasks, start=1):
            for name in t.reads:
                l_inf[name] = max(l_inf[name], idx)
        for p in self.packets.values():
            if p.keep:
                l_inf[p.name] = n + 1
        self.l_inf = l_inf

        # Per (task, read packet): the paper's l_k(p) — last touch strictly
        # before k. 0 when untouched before (external / first use).
        last_touch: Dict[str, int] = {
            name: (0 if self.packets[name].external else None)  # type: ignore[dict-item]
            for name in self.packets
        }
        self.read_last_touch: List[Tuple[int, ...]] = []  # aligned with tasks (0-based list)
        for idx, t in enumerate(self.tasks, start=1):
            row = []
            for name in t.reads:
                lt = last_touch[name]
                assert lt is not None  # _validate guarantees written-before-read
                row.append(lt)
            self.read_last_touch.append(tuple(row))
            for name in t.reads:
                last_touch[name] = idx
            for name in t.writes:
                last_touch[name] = idx

    # -- derived quantities ----------------------------------------------------

    def writer(self, packet: str) -> int:
        """Index of the task writing ``packet`` (0 = external)."""
        return self._writer[packet]

    def total_task_cost(self) -> float:
        """E_app: the cost of executing all tasks with no partitioning overhead."""
        return float(sum(t.cost for t in self.tasks))

    def total_packet_bytes(self) -> int:
        """Static size of all application data (used by the naive baseline)."""
        return int(sum(p.nbytes for p in self.packets.values()))

    def live_packets(self, boundary: int) -> List[str]:
        """Packets that are live across the boundary after task ``boundary``.

        A packet is live at boundary ``b`` (between tasks b and b+1) iff it was
        written at or before ``b`` and is used after ``b``.
        """
        out = []
        for name, p in self.packets.items():
            w = self._writer[name]
            if w <= boundary and self.l_inf[name] > boundary:
                out.append(name)
        return out

    def subgraph(self, lo: int, hi: int) -> "TaskGraph":
        """Tasks lo..hi (1-based inclusive) as a standalone graph.

        Packets produced before ``lo`` and read inside become external; packets
        produced inside and used after ``hi`` become ``keep``.
        """
        names = set()
        for k in range(lo, hi + 1):
            t = self.task(k)
            names.update(t.reads)
            names.update(t.writes)
        pkts = []
        for name in names:
            p = self.packets[name]
            w = self._writer[name]
            pkts.append(
                dataclasses.replace(
                    p,
                    external=(w < lo),
                    keep=(self.l_inf[name] > hi and w >= lo),
                )
            )
        return TaskGraph(self.tasks[lo - 1 : hi], pkts)

    def to_arrays(
        self,
        n_pad: Optional[int] = None,
        r_pad: Optional[int] = None,
        w_pad: Optional[int] = None,
    ) -> GraphArrays:
        """Export the §4.2 analysis products as dense padded arrays.

        ``n_pad`` / ``r_pad`` / ``w_pad`` override the natural task / read-slot
        / write-slot counts (must be ≥ them) so that different graphs share a
        shape and batch under ``vmap``. See :class:`GraphArrays` for the
        exact per-field semantics.
        """
        if n_pad is None and r_pad is None and w_pad is None:
            cached = getattr(self, "_arrays_cache", None)
            if cached is not None:
                return cached
        n = self.n_tasks
        nat_r = max((len(t.reads) for t in self.tasks), default=0)
        nat_w = max((len(t.writes) for t in self.tasks), default=0)
        N = n if n_pad is None else int(n_pad)
        R = max(nat_r if r_pad is None else int(r_pad), 1)
        W = max(nat_w if w_pad is None else int(w_pad), 1)
        if N < n or R < nat_r or W < nat_w:
            raise ValueError(
                f"padding ({N},{R},{W}) smaller than natural ({n},{nat_r},{nat_w})"
            )

        e_task = np.zeros(N, dtype=np.float64)
        rb = np.zeros((N, R), dtype=np.float64)
        rc0 = np.zeros((N, R), dtype=np.float64)
        rlt = np.zeros((N, R), dtype=np.int32)
        rwr = np.zeros((N, R), dtype=np.int32)
        rli = np.zeros((N, R), dtype=np.int32)
        rv = np.zeros((N, R), dtype=np.float64)
        wb = np.zeros((N, W), dtype=np.float64)
        wc0 = np.zeros((N, W), dtype=np.float64)
        wli = np.zeros((N, W), dtype=np.int32)
        wv = np.zeros((N, W), dtype=np.float64)

        for idx, t in enumerate(self.tasks):
            e_task[idx] = t.cost
            for r, (name, lt) in enumerate(zip(t.reads, self.read_last_touch[idx])):
                p = self.packets[name]
                rb[idx, r] = p.nbytes
                rc0[idx, r] = p.c0_weight
                rlt[idx, r] = lt
                rwr[idx, r] = self._writer[name]
                rli[idx, r] = self.l_inf[name]
                rv[idx, r] = 1.0
            for w, name in enumerate(t.writes):
                p = self.packets[name]
                wb[idx, w] = p.nbytes
                wc0[idx, w] = p.c0_weight
                wli[idx, w] = self.l_inf[name]
                wv[idx, w] = 1.0

        out = GraphArrays(
            n_tasks=n,
            e_task=e_task,
            read_bytes=rb, read_c0w=rc0, read_lt=rlt,
            read_writer=rwr, read_linf=rli, read_valid=rv,
            write_bytes=wb, write_c0w=wc0, write_linf=wli, write_valid=wv,
        )
        if n_pad is None and r_pad is None and w_pad is None:
            self._arrays_cache = out  # graphs are immutable once built
        return out

    def to_csr_arrays(
        self,
        n_pad: Optional[int] = None,
        r_pad: Optional[int] = None,
        w_pad: Optional[int] = None,
    ) -> GraphCSRArrays:
        """Export the §4.2 analysis products in the compressed slot layout.

        Semantics match :meth:`to_arrays` slot-for-slot (same per-task
        ordering, so the two exports are mutually reconstructible); only the
        container changes from padded rectangles to flat arrays + row
        pointers. ``n_pad``/``r_pad``/``w_pad`` grow the task count and the
        read/write slot pools for cross-graph batching (must be ≥ natural).
        """
        if n_pad is None and r_pad is None and w_pad is None:
            cached = getattr(self, "_csr_cache", None)
            if cached is not None:
                return cached
        n = self.n_tasks
        r_ptr = [0]
        rb: List[float] = []
        rc0: List[float] = []
        rlt: List[int] = []
        rwr: List[int] = []
        rli: List[int] = []
        w_ptr = [0]
        wb: List[float] = []
        wc0: List[float] = []
        wli: List[int] = []
        for idx, t in enumerate(self.tasks):
            for name, lt in zip(t.reads, self.read_last_touch[idx]):
                p = self.packets[name]
                rb.append(p.nbytes)
                rc0.append(p.c0_weight)
                rlt.append(lt)
                rwr.append(self._writer[name])
                rli.append(self.l_inf[name])
            r_ptr.append(len(rb))
            for name in t.writes:
                p = self.packets[name]
                wb.append(p.nbytes)
                wc0.append(p.c0_weight)
                wli.append(self.l_inf[name])
            w_ptr.append(len(wb))

        out = GraphCSRArrays(
            n_tasks=n,
            e_task=np.array([t.cost for t in self.tasks], dtype=np.float64),
            read_ptr=np.array(r_ptr, dtype=np.int32),
            read_bytes=np.array(rb, dtype=np.float64),
            read_c0w=np.array(rc0, dtype=np.float64),
            read_lt=np.array(rlt, dtype=np.int32),
            read_writer=np.array(rwr, dtype=np.int32),
            read_linf=np.array(rli, dtype=np.int32),
            write_ptr=np.array(w_ptr, dtype=np.int32),
            write_bytes=np.array(wb, dtype=np.float64),
            write_c0w=np.array(wc0, dtype=np.float64),
            write_linf=np.array(wli, dtype=np.int32),
        )
        if n_pad is not None or r_pad is not None or w_pad is not None:
            out = out.padded(
                n if n_pad is None else int(n_pad),
                max(len(rb) if r_pad is None else int(r_pad), 1),
                max(len(wb) if w_pad is None else int(w_pad), 1),
            )
        else:
            self._csr_cache = out  # graphs are immutable once built
        return out

    def __repr__(self) -> str:  # pragma: no cover
        return f"TaskGraph(n_tasks={self.n_tasks}, n_packets={len(self.packets)})"


class GraphBuilder:
    """Incremental builder mirroring a Ladybirds metakernel.

    >>> b = GraphBuilder()
    >>> b.packet("img", 9600)
    >>> b.task("sense", reads=(), writes=("img",), cost=0.1319)
    >>> g = b.build()
    """

    def __init__(self) -> None:
        self._packets: List[Packet] = []
        self._tasks: List[Task] = []

    def packet(self, name: str, nbytes: int, **kw: Any) -> str:
        self._packets.append(Packet(name, nbytes, **kw))
        return name

    def packet_array(self, name: str, count: int, nbytes_each: int, **kw: Any) -> List[str]:
        """A contiguous array of ``count`` sub-packets with amortized DMA init."""
        w = 1.0 / count
        return [
            self.packet(f"{name}[{i}]", nbytes_each, c0_weight=w, **kw)
            for i in range(count)
        ]

    def task(
        self,
        name: str,
        reads: Sequence[str] = (),
        writes: Sequence[str] = (),
        cost: float = 0.0,
        fn: Optional[Callable[..., Mapping[str, Any]]] = None,
    ) -> None:
        self._tasks.append(Task(name, tuple(reads), tuple(writes), float(cost), fn))

    def build(self) -> TaskGraph:
        return TaskGraph(self._tasks, self._packets)
