"""Lowered-program (HLO) text parsing for the SPMD sharding analyzer.

The only artifact that shows what actually runs on the chips is the
post-SPMD-partitioning HLO of a compiled executable (``Compiled.as_text()``):
that is where GSPMD has already turned every sharding annotation into
concrete ``all-gather`` / ``all-reduce`` / ``all-to-all`` /
``collective-permute`` / ``reduce-scatter`` instructions with real shapes
and replica groups. This module extracts that collective schedule as
structured records — shapes, dtypes, group sizes, estimated bytes moved per
device, and the XLA ``metadata op_name`` naming the op that *forced* the
collective (a reshard inserted to feed a ``dot_general`` carries the dot's
op_name) — for the ``PTA2xx`` passes in :mod:`.spmd`.

Nothing here imports jax: the input is plain HLO text, so the parser also
serves the CLI (``python -m paddle_tpu.analysis --hlo dump.txt``) on files
produced by ``XLA_FLAGS=--xla_dump_to`` or ``Compiled.as_text()`` from any
process.
"""
from __future__ import annotations

import hashlib
import itertools
import math
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = [
    "HloCollective",
    "COLLECTIVE_KINDS",
    "parse_shapes",
    "shape_bytes",
    "parse_collectives",
    "collective_counts",
    "moved_bytes",
    "moved_bytes_by_type",
    "total_moved_bytes",
    "schedule_fingerprint",
    "entry_memory_lower_bound",
]

#: collective opcodes the SPMD partitioner inserts (async ``-start`` forms
#: included; their ``-done`` halves are bookkeeping and are skipped)
COLLECTIVE_KINDS = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast",
)

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1,
}

# one typed array shape: "f32[4,32,192]{1,0,2}" (layout suffix optional)
_SHAPE_RE = re.compile(r"\b([a-z]\d*[a-z0-9]*)\[([\d,]*)\](?:\{[^}]*\})?")
# one named instruction: "%name = <result-shape(s)> opcode(...)"
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*?)\s+"
    r"(" + "|".join(COLLECTIVE_KINDS) + r")(-start)?\(")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^=]*?)\}\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")
_CHANNEL_RE = re.compile(r"channel_id=(\d+)")
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')
_SOURCE_RE = re.compile(r'source_file="([^"]*)"(?:\s+source_line=(\d+))?')
_PARAM_RE = re.compile(r"^\s*(?:ROOT\s+)?%[\w.\-]+\s*=\s*(.*?)\s+parameter\(\d+\)")
# a computation's header: "%name (arg: shape, ...) -> result-shape {"
_COMPUTATION_RE = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s+\((.*)\)\s*->\s*(.*?)\s*\{\s*$")


def parse_shapes(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """Every typed array shape in ``text`` as ``(dtype, dims)`` — a tuple
    result like ``(f32[8]{0}, f32[8]{0})`` yields one entry per element."""
    out = []
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue  # opcode fragments that merely look like a dtype
        out.append((dt, tuple(int(d) for d in dims.split(",") if d)))
    return out


def shape_bytes(shapes: Sequence[Tuple[str, Tuple[int, ...]]]) -> int:
    total = 0
    for dt, dims in shapes:
        n = 1
        for d in dims:
            n *= d
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total


@dataclass
class HloCollective:
    """One collective instruction lifted out of optimized HLO text."""

    kind: str                                   # e.g. "all-gather"
    name: str                                   # HLO instruction name
    index: int                                  # order within the module
    line: int                                   # 1-based line in the text
    result_shapes: List[Tuple[str, Tuple[int, ...]]] = field(default_factory=list)
    operand_shapes: List[Tuple[str, Tuple[int, ...]]] = field(default_factory=list)
    group_size: int = 1                         # devices per replica group
    num_groups: int = 1
    groups: Tuple[Tuple[int, ...], ...] = ()     # the replica groups' device ids
    channel_id: Optional[int] = None
    op_name: str = ""                           # metadata: the forcing op
    source: str = ""                            # "file:line" when recorded

    @property
    def result_bytes(self) -> int:
        return shape_bytes(self.result_shapes)

    @property
    def operand_bytes(self) -> int:
        return shape_bytes(self.operand_shapes)

    def signature(self) -> str:
        """Order/shape fingerprint row: stable across ranks iff the rank
        compiled the same collective at the same schedule position."""
        shapes = ";".join(f"{dt}{list(dims)}" for dt, dims in self.result_shapes)
        return f"{self.kind}[g{self.group_size}x{self.num_groups}]({shapes})"

    def describe(self) -> str:
        loc = f" at {self.source}" if self.source else ""
        via = f" (inserted for {self.op_name.rsplit('/', 1)[-1]})" if self.op_name else ""
        return (f"{self.kind} '{self.name}' over {self.group_size}-device "
                f"groups, ~{moved_bytes(self):,} bytes moved per device per "
                f"dispatch{via}{loc}")


def _parse_groups(line: str) -> Tuple[Tuple[int, ...], ...]:
    """The replica groups, each a tuple of device ids, from either spelling:
    explicit ``{{0,1},{2,3}}`` or iota ``[num_groups,group_size]<=[dims]``
    with an optional ``T(perm)`` (the ids 0..N-1 laid out as ``dims``,
    transposed by ``perm``, then cut into the groups in row-major order)."""
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        ngr, gsz = int(m.group(1)), int(m.group(2))
        dims = [int(d) for d in m.group(3).split(",")]
        perm = [int(d) for d in m.group(4).split(",")] if m.group(4) else list(range(len(dims)))
        strides = [math.prod(dims[i + 1:]) for i in range(len(dims))]
        ids = [sum(i * strides[p] for i, p in zip(index, perm))
               for index in itertools.product(*(range(dims[p]) for p in perm))]
        return tuple(tuple(ids[g * gsz:(g + 1) * gsz]) for g in range(ngr))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return tuple(tuple(int(t) for t in g.split(",") if t.strip()) for g in m.group(1).split("},{"))
    return ()


def parse_collectives(hlo_text: str) -> List[HloCollective]:
    """Every collective instruction in ``hlo_text``, in program order.

    Works on the optimized (post-partitioning) module text; each transfer
    counts once. Async pairs are collapsed onto their ``-start`` half. The
    TPU compiler's two spellings are read for what they run as. One async
    collective is written out three times, in the wrapped computations of
    its start, of the work it overlaps (``async_collective_fusion``) and of
    its done (a ``custom-call`` to ``AsyncCollectiveDone``): the start's
    counts. An ``all-reduce`` with the ``dynamic-slice`` of its result
    inside a fused computation named ``all-reduce-scatter`` is the chip's
    reduce-scatter, from the fusion's operand to its result.
    """
    out: List[HloCollective] = []
    first_of_computation = 0        # out[first_of_computation:] belong to the computation being read
    wrapper = False                 # inside an async_collective_fusion
    fused_scatter = None            # (operand text, result text) inside an all-reduce-scatter fusion
    for lineno, line in enumerate(hlo_text.splitlines(), 1):
        head = _COMPUTATION_RE.match(line)
        if head is not None:
            first_of_computation = len(out)
            fused_scatter = (head.group(2), head.group(3)) if head.group(1).startswith("all-reduce-scatter") else None
            wrapper = head.group(1).startswith("async_collective_fusion")
            continue
        if 'custom_call_target="AsyncCollectiveDone"' in line:
            del out[first_of_computation:]      # the tail: the indices of what stays are still 0..len(out)-1
            continue
        m = None if wrapper else _INSTR_RE.match(line)
        if m is None:
            continue
        name, result, kind = m.group(1), m.group(2), m.group(3)
        # operands: everything inside the call parens, up to the attr list
        tail = line[m.end():]
        depth, end = 1, len(tail)
        for i, ch in enumerate(tail):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        operands = tail[:end]
        groups = _parse_groups(line)
        ch_m = _CHANNEL_RE.search(line)
        if fused_scatter is not None and kind == "all-reduce":
            kind, (operands, result) = "reduce-scatter", fused_scatter
        op_m = _OPNAME_RE.search(line)
        src_m = _SOURCE_RE.search(line)
        src = ""
        if src_m:
            src = src_m.group(1).rsplit("/", 1)[-1]
            if src_m.group(2):
                src += f":{src_m.group(2)}"
        out.append(HloCollective(
            kind=kind, name=name, index=len(out), line=lineno,
            result_shapes=parse_shapes(result),
            operand_shapes=parse_shapes(operands),
            group_size=max(1, len(groups[0])) if groups else 1, num_groups=max(1, len(groups)), groups=groups,
            channel_id=int(ch_m.group(1)) if ch_m else None,
            op_name=op_m.group(1) if op_m else "",
            source=src))
    return out


def collective_counts(collectives: Sequence[HloCollective]) -> Dict[str, int]:
    counts: Dict[str, int] = {}
    for c in collectives:
        counts[c.kind] = counts.get(c.kind, 0) + 1
    return counts


def moved_bytes_by_type(collectives: Sequence[HloCollective]) -> Dict[str, int]:
    """``{"all-gather bf16": bytes, ...}``: the estimate of :func:`moved_bytes`
    summed per kind and element type (a tuple result's first member names the
    type), so that one line says what crosses the wire and how wide."""
    totals: Dict[str, int] = {}
    for c in collectives:
        shapes = c.result_shapes or c.operand_shapes
        key = f"{c.kind} {shapes[0][0] if shapes else '?'}"
        totals[key] = totals.get(key, 0) + moved_bytes(c)  # noqa: PTA104 (host-side, never traced)
    return totals


def moved_bytes(c: HloCollective) -> int:
    """Estimated bytes moved per device for one execution of ``c``.

    Standard ring-algorithm accounting over a group of g devices:
    all-gather / reduce-scatter / all-to-all move (g-1)/g of the full
    buffer; all-reduce is a reduce-scatter + all-gather (2x); a permute
    sends the whole shard once. An estimate, not a measurement — but it is
    exact enough to rank reshards and to make "this PartitionSpec costs
    40 MB of gathers per step" a machine-checkable statement.
    """
    if c.kind in ("collective-permute", "collective-broadcast"):
        # point-to-point: groups are source_target_pairs, the shard moves once
        return int(c.result_bytes)
    g = max(1, c.group_size)
    if g == 1:
        return 0
    frac = (g - 1) / g
    if c.kind == "all-gather":
        return int(c.result_bytes * frac)
    if c.kind == "reduce-scatter":
        return int(c.operand_bytes * frac)
    if c.kind == "all-reduce":
        return int(2 * c.result_bytes * frac)
    if c.kind == "all-to-all":
        return int(c.result_bytes * frac)
    if c.kind in ("collective-permute", "collective-broadcast"):
        return int(c.result_bytes)
    return int(c.result_bytes)


def total_moved_bytes(collectives: Sequence[HloCollective]) -> int:
    return sum(moved_bytes(c) for c in collectives)


def schedule_fingerprint(collectives: Sequence[HloCollective]) -> str:
    """Digest of the ordered (kind, groups, shapes) sequence. Two ranks
    whose lowered programs would issue different collective sequences —
    the deadlock class ``diagnostic_barrier`` only catches after it hangs —
    get different fingerprints *before* dispatch."""
    h = hashlib.sha256()
    for c in collectives:
        h.update(c.signature().encode())
        h.update(b"\n")
    return h.hexdigest()


def entry_memory_lower_bound(hlo_text: str) -> int:
    """A cheap per-device memory floor from text alone: the entry
    computation's parameter buffers plus the largest single instruction
    result. The live-set peak is at least this; the real analyzer prefers
    ``Compiled.memory_analysis()`` and uses this only for ``--hlo`` files
    where no executable exists."""
    param_bytes = 0
    largest = 0
    in_entry = False
    for line in hlo_text.splitlines():
        if line.startswith("ENTRY"):
            in_entry = True
            continue
        if in_entry and line.startswith("}"):
            in_entry = False
        m = _PARAM_RE.match(line) if in_entry else None
        if m:
            param_bytes += shape_bytes(parse_shapes(m.group(1)))
            continue
        if in_entry and "=" in line:
            head = line.split("=", 1)[1]
            paren = head.find("(")
            largest = max(largest, shape_bytes(parse_shapes(
                head[:paren] if paren > 0 else head)))
    return param_bytes + largest
