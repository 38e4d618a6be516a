"""Sharding X-ray: structured auditing of compiled-collective traffic.

GSPMD auto-partitioning (Xu et al., 2021) decides which collectives a
program actually runs — and a single mis-pinned sharding silently turns
into an all-gather on the hot path. This module walks a compiled
executable's HLO text and produces a per-program **collective
inventory**: op kind (all-reduce / reduce-scatter / all-gather /
collective-permute / all-to-all), bytes moved estimated from the
operand/result shapes, and ICI-vs-DCN attribution by folding each op's
``replica_groups`` against the slice-major device assignment
(:mod:`..parallel.mesh`: device ``d`` lives in slice
``d // (num_devices // num_slices)``).

On top of the inventory sits **involuntary-reshard detection**: each
program declares a :class:`CollectiveContract` — the set of collective
kinds its sharding layout *explains* (derived in
:func:`..parallel.sharding.collective_contract_for_train` /
``collective_contract_for_params``). Any collective outside the
contract, and any sharding-changing SPMD copy in a program whose
contract forbids them, becomes a violation naming the offending HLO op
— surfaced as a ``sharding_violation`` anomaly record, a
flight-recorder event and the ``SHARDING`` section of
``accelerate-tpu diagnose``.

Everything here is host-side text analysis over ``Compiled.as_text()``:
record-only, no retracing, no numerics impact. Bytes are *algorithmic*
ring estimates (``(g-1)/g`` of the payload per participant), not wire
measurements — good enough to rank programs and regression-track
DCN bytes/step, not a NIC counter.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Any, Iterable, Optional

from ..logging import get_logger

logger = get_logger(__name__)

#: the collective op kinds the auditor inventories (HLO opcode names)
COLLECTIVE_KINDS = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "collective-permute",
    "all-to-all",
    "collective-broadcast",
)

#: sharding-changing SPMD copies (manual/auto boundary reshards). These
#: are legitimate inside shard_map bodies; a program whose contract
#: forbids all resharding flags them.
RESHARD_COPY = "reshard-copy"
_RESHARD_CUSTOM_CALLS = (
    '"SPMDFullToShardShape"',
    '"SPMDShardToFullShape"',
)

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1, "f8e4m3fnuz": 1,
    "f8e5m2fnuz": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

#: dtype-prefixed shape token, e.g. ``f32[8,16]`` / ``bf16[]`` —
#: replica_groups' bare ``[2,4]<=[8]`` deliberately does NOT match
_SHAPE_RE = re.compile(
    r"\b(" + "|".join(sorted(_DTYPE_BYTES, key=len, reverse=True))
    + r")\[([0-9,]*)\]"
)

_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%(?P<name>[^\s=]+)\s*=\s*")
#: computation header: ``%name (params) -> type {`` / ``ENTRY %main (...) {``
_COMPUTATION_RE = re.compile(r"^\s*(?:ENTRY\s+)?%?(?P<name>[^\s(]+)\s*\(.*\{\s*$")
#: XLA:TPU has no reduce-scatter instruction in its optimized HLO: it emits
#: a fusion computation of this name holding ``all-reduce`` + ``dynamic-slice``
_TPU_REDUCE_SCATTER_FUSION = "all-reduce-scatter"

#: explicit replica-group list: ``replica_groups={{0,1},{2,3}}``
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{(\{[0-9,{}\s]*\})\}")
#: iota format: ``replica_groups=[2,4]<=[8]`` or ``[2,4]<=[4,2]T(1,0)``
_GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[(?P<dims>[0-9,]+)\]<=\[(?P<src>[0-9,]+)\]"
    r"(?:T\((?P<perm>[0-9,]+)\))?"
)
_NUM_PARTITIONS_RE = re.compile(r"num_partitions=(\d+)")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d.strip():
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def parse_replica_groups(text: str) -> Optional[list[list[int]]]:
    """Extract the replica groups from one HLO instruction line.

    Handles both formats XLA prints: the explicit nested list
    ``{{0,1,2,3},{4,5,6,7}}`` and the iota form ``[2,4]<=[8]`` (an
    ``arange(prod(src)).reshape(src)[.transpose(perm)].reshape(dims)``
    — each row is one group). Returns None when the line carries no
    ``replica_groups`` attribute (= one group of every device).
    """
    m = _GROUPS_LIST_RE.search(text)
    if m is not None:
        groups = []
        for grp in re.findall(r"\{([0-9,\s]*)\}", m.group(1)):
            members = [int(x) for x in grp.split(",") if x.strip()]
            if members:
                groups.append(members)
        return groups
    m = _GROUPS_IOTA_RE.search(text)
    if m is not None:
        dims = [int(x) for x in m.group("dims").split(",")]
        src = [int(x) for x in m.group("src").split(",")]
        perm = (
            [int(x) for x in m.group("perm").split(",")]
            if m.group("perm") else None
        )
        total = 1
        for d in src:
            total *= d
        flat = list(range(total))
        # reshape(src) [+ transpose(perm)] + reshape(dims) without numpy
        if perm is not None:
            # index arithmetic: value at multi-index i (src layout) moves
            # to position perm-permuted
            strides = [0] * len(src)
            acc = 1
            for i in range(len(src) - 1, -1, -1):
                strides[i] = acc
                acc *= src[i]
            t_shape = [src[p] for p in perm]
            t_strides = [strides[p] for p in perm]
            out = []
            idx = [0] * len(t_shape)
            for _ in range(total):
                out.append(sum(i * s for i, s in zip(idx, t_strides)))
                for ax in range(len(t_shape) - 1, -1, -1):
                    idx[ax] += 1
                    if idx[ax] < t_shape[ax]:
                        break
                    idx[ax] = 0
            flat = out
        # iota dims are [num_groups, group_size]; a single dim is one
        # group of everyone
        group_size = dims[-1] if len(dims) > 1 else dims[0]
        n_groups = total // group_size if group_size else 1
        return [
            flat[i * group_size:(i + 1) * group_size]
            for i in range(n_groups)
        ]
    return None


@dataclass
class CollectiveOp:
    """One collective instruction in a compiled program's HLO."""

    op_name: str        # the HLO instruction name, e.g. "all-gather.7"
    kind: str           # one of COLLECTIVE_KINDS or RESHARD_COPY
    operand_bytes: int
    result_bytes: int
    bytes_moved: int    # algorithmic ring estimate per participant
    group_size: int
    replica_groups: Optional[list[list[int]]]
    fabric: str         # "ici" | "dcn"
    is_async: bool = False

    def as_dict(self) -> dict:
        return {
            "op": self.op_name,
            "kind": self.kind,
            "bytes_moved": int(self.bytes_moved),
            "operand_bytes": int(self.operand_bytes),
            "result_bytes": int(self.result_bytes),
            "group_size": int(self.group_size),
            "fabric": self.fabric,
        }


@dataclass(frozen=True)
class CollectiveContract:
    """The collective kinds a program's sharding layout explains.

    ``allowed`` is a frozenset of :data:`COLLECTIVE_KINDS` members (plus
    optionally :data:`RESHARD_COPY` for programs that legitimately cross
    shard_map boundaries). ``origin`` names the layout the contract was
    derived from — it travels onto every violation so the finding reads
    "all-to-all not explained by zero2(dp=2,fsdp=4)" rather than a bare
    op name.
    """

    allowed: frozenset = frozenset()
    origin: str = ""
    notes: tuple = ()

    def permits(self, kind: str) -> bool:
        return kind in self.allowed

    def as_dict(self) -> dict:
        return {
            "allowed": sorted(self.allowed),
            "origin": self.origin,
            "notes": list(self.notes),
        }


#: serving under fully-replicated params: NO collective is explained
CONTRACT_ZERO = CollectiveContract(
    allowed=frozenset(), origin="replicated",
)


def estimate_bytes_moved(
    kind: str, operand_bytes: int, result_bytes: int, group_size: int
) -> int:
    """Algorithmic per-participant wire bytes for one collective.

    Ring estimates (the TPU torus runs ring schedules): a
    ``g``-member all-gather moves ``(g-1)/g`` of the full result past
    each participant; reduce-scatter the mirror of that over its input;
    all-reduce = reduce-scatter + all-gather (2x); all-to-all
    re-distributes ``(g-1)/g`` of the payload; a permute forwards the
    whole operand.
    """
    g = max(int(group_size), 1)
    frac = (g - 1) / g if g > 1 else 0.0
    if kind == "all-gather":
        return int(result_bytes * frac)
    if kind == "reduce-scatter":
        return int(operand_bytes * frac)
    if kind == "all-reduce":
        return int(2 * operand_bytes * frac)
    if kind == "all-to-all":
        return int(operand_bytes * frac)
    if kind == "collective-permute":
        return int(operand_bytes)
    if kind == "collective-broadcast":
        return int(result_bytes * frac)
    return int(operand_bytes)


def _classify_fabric(
    groups: Optional[list[list[int]]],
    num_devices: int,
    num_slices: int,
) -> str:
    """ICI vs DCN for one collective: under the slice-major assignment
    slice(d) = d // (num_devices // num_slices); any replica group whose
    members span more than one slice crosses the data-center network."""
    if num_slices <= 1 or num_devices <= 0:
        return "ici"
    per_slice = max(num_devices // num_slices, 1)
    for grp in groups if groups else [list(range(num_devices))]:
        slices = {d // per_slice for d in grp}
        if len(slices) > 1:
            return "dcn"
    return "ici"


def _operand_region(line: str, start: int) -> str:
    """The text inside the op's balanced parens starting at ``start``
    (the index of the opening paren)."""
    depth = 0
    for i in range(start, len(line)):
        ch = line[i]
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                return line[start + 1:i]
    return line[start + 1:]


_OP_TOKEN_RE = re.compile(
    r"\b(" + "|".join(COLLECTIVE_KINDS) + r")(-start|-done)?\("
)
_OPERAND_NAME_RE = re.compile(r"%([^\s,()]+)")


def _result_type_bytes(rest: str) -> int:
    """Bytes of an instruction's result type — ``rest`` is the line after
    ``%name = ``: either one shape token (``f32[4,3]{1,0} parameter(0)``)
    or a parenthesized tuple of them."""
    if rest.startswith("("):
        region = _operand_region(rest, 0)
    else:
        region = rest.split(" ", 1)[0]
    return sum(_shape_bytes(d, s) for d, s in _SHAPE_RE.findall(region))


def parse_hlo_collectives(
    hlo_text: str,
    *,
    num_devices: Optional[int] = None,
    num_slices: int = 1,
) -> list[CollectiveOp]:
    """Walk HLO text and inventory every collective instruction.

    Async pairs count once (the ``-start`` carries the shapes; the
    ``-done`` is skipped). Sharding-changing SPMD copies
    (``SPMDFullToShardShape`` / ``SPMDShardToFullShape`` custom calls)
    are inventoried as kind :data:`RESHARD_COPY` with zero wire bytes —
    they matter as contract evidence, not as traffic.
    """
    if num_devices is None:
        m = _NUM_PARTITIONS_RE.search(hlo_text)
        num_devices = int(m.group(1)) if m else 1
    ops: list[CollectiveOp] = []
    # result bytes of every instruction seen so far, by name: newer XLA
    # prints operands as bare names (``reduce-scatter(%param.1)``, no
    # inline shape), so an operand's size is its definition's result
    defined: dict[str, int] = {}
    computation = ""
    for raw in hlo_text.splitlines():
        im = _INSTR_RE.match(raw)
        if im is None:
            cm = _COMPUTATION_RE.match(raw)
            if cm is not None:
                computation = cm.group("name")
            continue
        # metadata can quote arbitrary op_name strings — cut it off so
        # neither the shape scan nor the op-token scan reads it
        line = raw.split(", metadata=")[0]
        defined[im.group("name")] = _result_type_bytes(line[im.end():])
        om = _OP_TOKEN_RE.search(line)
        if om is not None:
            if om.group(2) == "-done":
                continue  # counted at the matching -start
            kind = om.group(1)
            if kind == "all-reduce" and computation.startswith(
                _TPU_REDUCE_SCATTER_FUSION
            ):
                kind = "reduce-scatter"
            result_part = line[:om.start()]
            operand_part = _operand_region(line, line.index("(", om.start()))
            attr_part = line[om.start():]
            result_bytes = sum(
                _shape_bytes(d, s) for d, s in _SHAPE_RE.findall(result_part)
            )
            operand_bytes = sum(
                _shape_bytes(d, s) for d, s in _SHAPE_RE.findall(operand_part)
            ) or sum(
                defined.get(name, 0)
                for name in _OPERAND_NAME_RE.findall(operand_part)
            )
            groups = parse_replica_groups(attr_part)
            if groups:
                group_size = max(len(g) for g in groups)
            else:
                group_size = max(num_devices, 1)
            moved = estimate_bytes_moved(
                kind, operand_bytes, result_bytes, group_size
            )
            ops.append(CollectiveOp(
                op_name=im.group("name"),
                kind=kind,
                operand_bytes=operand_bytes,
                result_bytes=result_bytes,
                bytes_moved=moved,
                group_size=group_size,
                replica_groups=groups,
                fabric=_classify_fabric(groups, num_devices, num_slices),
                is_async=om.group(2) == "-start",
            ))
            continue
        if any(cc in line for cc in _RESHARD_CUSTOM_CALLS):
            result_bytes = sum(
                _shape_bytes(d, s)
                for d, s in _SHAPE_RE.findall(line.split("custom-call")[0])
            )
            ops.append(CollectiveOp(
                op_name=im.group("name"),
                kind=RESHARD_COPY,
                operand_bytes=result_bytes,
                result_bytes=result_bytes,
                bytes_moved=0,
                group_size=1,
                replica_groups=None,
                fabric="ici",
            ))
    return ops


@dataclass
class ProgramAudit:
    """One program's collective inventory + contract verdict."""

    label: str
    collectives: list[CollectiveOp] = field(default_factory=list)
    contract: Optional[CollectiveContract] = None
    num_devices: int = 1
    num_slices: int = 1
    violations: list[dict] = field(default_factory=list)

    # ------------------------------------------------------------- #
    @property
    def by_kind(self) -> dict[str, int]:
        out: dict[str, int] = {}
        for op in self.collectives:
            out[op.kind] = out.get(op.kind, 0) + 1
        return out

    def _fabric_bytes(self, fabric: str) -> int:
        return sum(
            op.bytes_moved for op in self.collectives if op.fabric == fabric
        )

    @property
    def ici_bytes(self) -> int:
        return self._fabric_bytes("ici")

    @property
    def dcn_bytes(self) -> int:
        return self._fabric_bytes("dcn")

    @property
    def total_bytes_moved(self) -> int:
        return sum(op.bytes_moved for op in self.collectives)

    @property
    def clean(self) -> bool:
        return not self.violations

    def bytes_by_kind_fabric(self) -> dict[str, int]:
        """``"<kind>|<fabric>" -> bytes`` — the Prometheus
        ``collective_bytes{program,kind,fabric}`` payload."""
        out: dict[str, int] = {}
        for op in self.collectives:
            key = f"{op.kind}|{op.fabric}"
            out[key] = out.get(key, 0) + op.bytes_moved
        return out

    def check_contract(self) -> list[dict]:
        """(Re)derive the violation list from the inventory: every
        collective (or reshard copy) whose kind the contract does not
        permit, each naming the offending HLO op."""
        self.violations = []
        if self.contract is None:
            return self.violations
        for op in self.collectives:
            if self.contract.permits(op.kind):
                continue
            self.violations.append({
                "op": op.op_name,
                "op_kind": op.kind,
                "bytes_moved": int(op.bytes_moved),
                "fabric": op.fabric,
                "group_size": int(op.group_size),
                "reason": (
                    f"{op.kind} not explained by contract "
                    f"[{', '.join(sorted(self.contract.allowed)) or 'none'}]"
                    + (
                        f" ({self.contract.origin})"
                        if self.contract.origin else ""
                    )
                ),
            })
        return self.violations

    # ------------------------------------------------------------- #
    def as_dict(self) -> dict:
        return {
            "label": self.label,
            "num_devices": int(self.num_devices),
            "num_slices": int(self.num_slices),
            "collectives": [op.as_dict() for op in self.collectives],
            "by_kind": self.by_kind,
            "ici_bytes": int(self.ici_bytes),
            "dcn_bytes": int(self.dcn_bytes),
            "total_bytes_moved": int(self.total_bytes_moved),
            "contract": (
                self.contract.as_dict() if self.contract is not None else None
            ),
            "violations": list(self.violations),
            "clean": self.clean,
        }

    def to_record(self) -> dict:
        """The flat ``kind="audit"`` telemetry record payload (the
        per-op inventory stays in :meth:`as_dict`; records carry the
        roll-up plus the full violation list — the evidence travels
        with the alarm)."""
        return {
            "program": self.label,
            "num_collectives": len(self.collectives),
            "by_kind": self.by_kind,
            "ici_bytes": int(self.ici_bytes),
            "dcn_bytes": int(self.dcn_bytes),
            "total_bytes_moved": int(self.total_bytes_moved),
            "bytes_by_kind_fabric": self.bytes_by_kind_fabric(),
            "num_slices": int(self.num_slices),
            "num_devices": int(self.num_devices),
            "contract_allowed": (
                sorted(self.contract.allowed)
                if self.contract is not None else None
            ),
            "contract_origin": (
                self.contract.origin if self.contract is not None else None
            ),
            "violations": list(self.violations),
            "clean": self.clean,
        }


def _default_num_slices() -> int:
    try:
        import jax

        from ..parallel.mesh import resolve_num_slices

        return resolve_num_slices(jax.devices())
    except Exception:  # noqa: BLE001 — audit is never fatal
        return 1


def audit_hlo_text(
    label: str,
    hlo_text: str,
    *,
    contract: Optional[CollectiveContract] = None,
    num_devices: Optional[int] = None,
    num_slices: Optional[int] = None,
) -> ProgramAudit:
    """Audit already-extracted HLO text (the pure core; no jax)."""
    if num_devices is None:
        m = _NUM_PARTITIONS_RE.search(hlo_text)
        num_devices = int(m.group(1)) if m else 1
    if num_slices is None:
        num_slices = _default_num_slices()
    audit = ProgramAudit(
        label=label,
        collectives=parse_hlo_collectives(
            hlo_text, num_devices=num_devices, num_slices=num_slices
        ),
        contract=contract,
        num_devices=int(num_devices),
        num_slices=int(num_slices),
    )
    audit.check_contract()
    return audit


def audit_compiled(
    label: str,
    compiled: Any,
    *,
    contract: Optional[CollectiveContract] = None,
    num_devices: Optional[int] = None,
    num_slices: Optional[int] = None,
) -> Optional[ProgramAudit]:
    """Audit one ``jax.stages.Compiled``: walk ``as_text()`` and return
    the :class:`ProgramAudit` (None when the backend can't render HLO
    text — auditing is best-effort observability, never fatal)."""
    try:
        hlo_text = compiled.as_text()
    except Exception as exc:  # noqa: BLE001
        logger.debug(f"hlo audit({label}): as_text unavailable: {exc}")
        return None
    if not hlo_text:
        return None
    return audit_hlo_text(
        label, hlo_text,
        contract=contract, num_devices=num_devices, num_slices=num_slices,
    )


def summarize_audits(audits: Iterable[ProgramAudit]) -> dict:
    """Roll a set of program audits into the ledger summary stamped
    into soak reports / diagnose: totals per fabric,
    the per-program inventory map, and the (bounded) violation list."""
    audits = list(audits)
    violations: list[dict] = []
    programs: dict[str, dict] = {}
    for a in audits:
        programs[a.label] = {
            "collectives": len(a.collectives),
            "by_kind": a.by_kind,
            "ici_bytes": int(a.ici_bytes),
            "dcn_bytes": int(a.dcn_bytes),
            "violations": len(a.violations),
        }
        for v in a.violations:
            violations.append({"program": a.label, **v})
    return {
        "num_programs_audited": len(audits),
        "collectives_total": sum(len(a.collectives) for a in audits),
        "ici_bytes_total": sum(a.ici_bytes for a in audits),
        "dcn_bytes_total": sum(a.dcn_bytes for a in audits),
        "violations_total": len(violations),
        "violations": violations[:32],
        "programs": programs,
    }
